"""deepseek-v3 — multi-head latent attention (MLA) [hf:deepseek-ai/DeepSeek-V3].

The attention widths only: the model substrate (``repro.models``) has no
latent-attention layer, so this is not an ``ArchConfig`` in ``ARCHS``; the
``mla_prefill`` and ``mla_decode`` kernels (kernels/mla_attention.py) take
their published shapes from it.

Each head's query is ``[q_nope (128), q_rope (64)]``; its key is
``[c_kv W_UK_h (128), k_rope (64)]``, where the latent ``c_kv``
(``kv_lora_rank`` 512) and ``k_rope`` are one per token and shared by every
head; its value is ``c_kv W_UV_h`` (128).
"""
from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class MLAConfig:
    name: str
    source: str
    n_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # YaRN rope scaling
    rope_factor: float
    mscale_all_dim: float
    original_max_position_embeddings: int
    max_position_embeddings: int

    def softmax_scale(self, context: int) -> float:
        """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale^2`` once the
        context exceeds the original positions, as the model's own code
        does (``0.1 * mscale_all_dim * ln(factor) + 1``)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if context > self.original_max_position_embeddings:
            m = 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0
            scale *= m * m
        return scale


CONFIG = MLAConfig(
    name="deepseek-v3",
    source="hf:deepseek-ai/DeepSeek-V3",
    n_heads=128,
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_factor=40.0,
    mscale_all_dim=1.0,
    original_max_position_embeddings=4096,
    max_position_embeddings=163840,
)
