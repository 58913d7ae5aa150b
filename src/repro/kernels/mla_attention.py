"""Multi-head latent attention (DeepSeek-V3 MLA): two Pallas TPU kernels and
the payload programs that run them.

Every token keeps one latent ``c_kv`` (``dc`` wide, 512 in DeepSeek-V3) and
one rope key ``k_rope`` (``dr``, 64), both shared by all heads.  Head h's
key is ``[c_kv W_UK_h, k_rope]`` (``dn + dr``, 192) and its value
``c_kv W_UV_h`` (``dv``, 128), so a score is the sum of two dot products,
``q_nope . k_nope_h + q_rope . k_rope``, and the value is narrower than
the key.

Prefill (``prefill_program``): the latent is decompressed per head with XLA
(``mla.decompress``), then ``mla_prefill`` runs causal flash attention with
the score in two parts (128 + 64 lanes: 192 is no multiple of the MXU's
128), dv != dk, ``k_rope`` read by every head through the index map (it is
never widened to H heads in HBM), and queries that are the last ``Lq``
positions of an ``Lk``-long sequence (``q_offset = Lk - Lq``).

Decode (``decode_program``): the query is absorbed (``mla.absorb``):
``q_c = q_nope W_UK_h^T`` is ``dc`` wide, so the scores read the latent
cache itself.  ``mla_decode`` then folds all H heads into the rows of one
query tile against the one latent KV head: keys ``[c_kv, k_rope]``, values
``c_kv``, read from the same block.  Its output, still latent, is
un-absorbed through ``W_UV_h`` (``mla.unabsorb``).  A decode step sees the
whole cache: no mask.

Both kernels share flash_attention.py's tile rule, band, clamped K/V index
map, masked-block skip and online-softmax update; operands stay in their
dtype on the MXU (bf16 for the payloads) and accumulate in float32.

VMEM at DeepSeek-V3's widths, bf16: prefill at 512 x 1024 tiles, the
double-buffered q_nope, q_rope, k_nope, k_rope, v and output tiles (the
64-wide rope tiles padded to 128 lanes) 2.3 MiB, m, l and the accumulator
0.75 MiB, the two float32 score parts, their sum and p about 9 MiB.
Decode at 1024-key tiles with H 128: the latent and rope tiles
double-buffered 2.5 MiB, the query tiles and output 0.4 MiB, the
accumulator 0.3 MiB, scores and p 1.5 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flash_attention as fa


def _prefill_kernel(
    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, q_offset: int, block_q: int, block_k: int, n_kv_blocks: int, band_fn,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    fa.init_state(ki, m_scr, l_scr, acc_scr)
    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    def step(masked: bool):
        s = (
            fa.dot_nt(qn_ref[0, 0], kn_ref[0, 0]) + fa.dot_nt(qr_ref[0, 0], kr_ref[0, 0])
        ) * scale  # (block_q, block_k)
        if masked:
            s = fa.mask_scores(s, q_start, k_start, causal=True, window=None)
        fa.softmax_update(s, v_ref.at[0, 0], m_scr, l_scr, acc_scr)

    fa.banded(step, qi, ki, q_start, k_start, block_q=block_q, block_k=block_k,
              causal=True, window=None, band_fn=band_fn)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = fa.normalized(l_scr, acc_scr).astype(o_ref.dtype)


def mla_prefill(
    q_nope: jax.Array,  # (B, H, Lq, dn)
    q_rope: jax.Array,  # (B, H, Lq, dr)
    k_nope: jax.Array,  # (B, H, Lk, dn)
    k_rope: jax.Array,  # (B, 1, Lk, dr), shared by every head
    v: jax.Array,  # (B, H, Lk, dv)
    *,
    q_offset: int,
    scale: float,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of queries at key positions ``q_offset ...
    q_offset + Lq - 1`` -> (B, H, Lq, dv)."""
    b, h, lq, dn = q_nope.shape
    dr, lk, dv = q_rope.shape[-1], k_nope.shape[2], v.shape[-1]
    assert k_rope.shape == (b, 1, lk, dr), k_rope.shape
    assert 0 <= q_offset <= lk - lq, (q_offset, lq, lk)
    rule_q, rule_k = fa.default_blocks(lq, lk)
    block_q = min(block_q or rule_q, lq)
    block_k = min(block_k or rule_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, block_q, lk, block_k)
    nq, nk = lq // block_q, lk // block_k
    band_kw = dict(block_q=block_q, block_k=block_k, n_kv_blocks=nk, causal=True,
                   window=None, q_offset=q_offset)
    band_fn = functools.partial(fa.band, **band_kw)
    kv_of = functools.partial(fa.kv_block, **band_kw)

    def q_index(b_, h_, qi, ki):
        return (b_, h_, qi, 0)

    def kv_index(b_, h_, qi, ki):
        return (b_, h_, kv_of(qi, ki), 0)

    def rope_index(b_, h_, qi, ki):
        return (b_, 0, kv_of(qi, ki), 0)

    kernel = functools.partial(
        _prefill_kernel, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk, band_fn=band_fn,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dn), q_index),
            pl.BlockSpec((1, 1, block_q, dr), q_index),
            pl.BlockSpec((1, 1, block_k, dn), kv_index),
            pl.BlockSpec((1, 1, block_k, dr), rope_index),
            pl.BlockSpec((1, 1, block_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, dv), q_nope.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="mla_prefill",
    )(q_nope, q_rope, k_nope, k_rope, v)


def _decode_kernel(qc_ref, qr_ref, c_ref, kr_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, n_kv_blocks: int):
    ki = pl.program_id(1)
    fa.init_state(ki, m_scr, l_scr, acc_scr)
    # the heads are the rows; the latent block is the key's first part
    s = (fa.dot_nt(qc_ref[0], c_ref[0]) + fa.dot_nt(qr_ref[0], kr_ref[0])) * scale  # (H, block_k)
    # ... and the value
    fa.softmax_update(s, c_ref.at[0], m_scr, l_scr, acc_scr)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = fa.normalized(l_scr, acc_scr).astype(o_ref.dtype)


def mla_decode(
    q_c: jax.Array,  # (B, H, dc): absorbed queries
    q_r: jax.Array,  # (B, H, dr)
    c_kv: jax.Array,  # (B, Lk, dc): latent cache, key part and value
    k_rope: jax.Array,  # (B, Lk, dr)
    *,
    scale: float,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """One query per head against the whole latent cache -> (B, H, dc)."""
    b, h, dc = q_c.shape
    dr, lk = q_r.shape[-1], c_kv.shape[1]
    assert c_kv.shape == (b, lk, dc) and k_rope.shape == (b, lk, dr), (c_kv.shape, k_rope.shape)
    block_k = min(block_k or fa.default_blocks(h, lk)[1], lk)
    assert lk % block_k == 0, (lk, block_k)
    nk = lk // block_k

    def q_index(b_, ki):
        return (b_, 0, 0)

    def kv_index(b_, ki):
        return (b_, ki, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, n_kv_blocks=nk),
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, dc), q_index),
            pl.BlockSpec((1, h, dr), q_index),
            pl.BlockSpec((1, block_k, dc), kv_index),
            pl.BlockSpec((1, block_k, dr), kv_index),
        ],
        out_specs=pl.BlockSpec((1, h, dc), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, dc), q_c.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),  # running max m
            pltpu.VMEM((h, 1), jnp.float32),  # running denom l
            pltpu.VMEM((h, dc), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="mla_decode",
    )(q_c, q_r, c_kv, k_rope)


def _project(spec: str, a, w):
    """``einsum(spec, a, w)`` accumulated in float32, in ``a``'s dtype."""
    return jnp.einsum(spec, a, w, preferred_element_type=jnp.float32).astype(a.dtype)


def prefill_program(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, *, scale: float,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    interpret: bool = False):
    """The last ``Lq`` queries of an ``Lk``-token prompt.  c_kv (B, Lk, dc);
    w_uk (dc, H, dn), w_uv (dc, H, dv) -> (B, H, Lq, dv)."""
    with jax.named_scope("mla.decompress"):
        k_nope = _project("blc,chd->bhld", c_kv, w_uk)
        v = _project("blc,chd->bhld", c_kv, w_uv)
    return mla_prefill(
        q_nope, q_rope, k_nope, k_rope, v,
        q_offset=c_kv.shape[1] - q_nope.shape[2], scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def decode_program(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, *, scale: float,
                   block_k: Optional[int] = None, interpret: bool = False):
    """One decode step.  q_nope (B, H, dn), q_rope (B, H, dr), c_kv
    (B, Lk, dc), k_rope (B, Lk, dr) -> (B, H, dv)."""
    with jax.named_scope("mla.absorb"):
        q_c = _project("bhd,chd->bhc", q_nope, w_uk)
    o_c = mla_decode(q_c, q_rope, c_kv, k_rope, scale=scale, block_k=block_k, interpret=interpret)
    with jax.named_scope("mla.unabsorb"):
        return _project("bhc,chd->bhd", o_c, w_uv)
