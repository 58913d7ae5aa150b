"""Flash attention (forward) Pallas TPU kernel with GQA-aware KV indexing.

TPU mapping of the paper-agnostic attention hot-spot:
  * grid = (batch, q_heads, q_blocks, kv_blocks); the innermost kv dimension
    executes sequentially on TPU, so the online-softmax running state lives in
    VMEM scratch that persists across kv iterations.
  * BlockSpecs tile Q/K/V into (block_q x head_dim) / (block_k x head_dim)
    VMEM tiles; block sizes are multiples of 128 to keep the MXU matmuls
    hardware-aligned.
  * GQA: the K/V BlockSpec index_map folds the query head onto its KV head
    (h -> h * n_kv // n_heads), so grouped heads read the same KV tile and
    nothing is materialized H-wide in HBM (unlike the XLA path).
  * causal: fully-masked kv blocks are skipped with pl.when - this is the
    ~2x FLOP saving over the XLA blockwise path recorded in §Roofline.

Validated against ref.attention_ref in interpret mode (CPU container); the
TPU target is v5e (16 MB VMEM: worst tile footprint here is
2*(block_q + 2*block_k) * hd * 4B ~ 1.5 MB at the defaults).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, n_kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # skip kv blocks that are entirely masked out
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window is not None:
        live = jnp.logical_and(live, q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, _NEG)

        m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)
        ).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, H, Lq, hd)
    k: jax.Array,  # (B, KV, Lk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, h, lq, hd = q.shape
    n_kv, lk = k.shape[1], k.shape[2]
    assert h % n_kv == 0, (h, n_kv)
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, block_q, lk, block_k)
    nq, nk = lq // block_q, lk // block_k
    scale = 1.0 / (hd**0.5)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, n_kv=n_kv, h=h: (b_, h_ * n_kv // h, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, n_kv=n_kv, h=h: (b_, h_ * n_kv // h, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
