"""Flash attention (forward) Pallas TPU kernel with GQA-aware KV indexing.

Grid and state
  grid = (batch, q_heads, q_blocks, kv_blocks).  The kv axis runs in order
  on a TPU core, so the online softmax's running max ``m``, denominator
  ``l`` and output accumulator stay in float32 VMEM scratch across it, and
  the output tile is written once, after the last kv block.  GQA: the K/V
  index map folds query head h onto KV head h * KV // H, so the heads of a
  group read the same K/V tiles and nothing is widened to H heads in HBM.

Operand dtype
  q.k^T takes q and k in their own dtype and accumulates in float32: bf16
  payloads run on the MXU's native bf16 path (each product is exact in
  float32), float32 payloads stay float32.  The scores are scaled once, in
  float32.  p is cast to v's dtype before p.v, which accumulates in float32
  again.  m, l and the accumulator are float32.  The dtype comes from the
  operands; there is no switch.

Tiles
  ``default_blocks`` is the one tile rule for every caller without an
  explicit config (ops.flash_attention, registry defaults): block_q is the
  largest of 512, 256 and 128 that divides the query length, block_k the
  largest of 1024, 512, 256 and 128 that divides the key length, each else
  the length itself.  At Grok-1's 8k context that is 512 x 1024: 16 x 8
  blocks per head in place of 64 x 64 at 128 x 128.  Of the pairs timed on
  a v5e there, 512 x 1024 was the fastest (PERF.md).

Masked blocks
  A kv block wholly outside the causal / window band is skipped with
  ``pl.when``, and the K/V index map clamps it onto the nearest live block
  of its q row.  The pipeline copies a block only when its index changes,
  so a skipped block costs one grid step and no DMA.  A live block wholly
  inside the band runs with no mask at all; only blocks that straddle the
  diagonal or the window edge build the iota mask, which sets the scores
  it drops to -inf (their p is then exactly 0).

Shared pieces
  ``band``, ``kv_block``, ``banded`` (the masked-block skip),
  ``mask_scores``, ``init_state``, ``softmax_update`` and ``normalized``
  are the kernel's steps as functions; mla_attention.py builds its two
  kernels from them, with queries offset from the keys (``q_offset``).

VMEM (v5e: 16 MiB scoped) at 512 x 1024 bf16 tiles, hd 128: the q, k, v
and output tiles, double-buffered, 1.5 MiB; m, l (each padded to 128 lanes)
and the accumulator 0.75 MiB; the float32 score and probability tiles and p
in bf16 5 MiB.  About 7 MiB in all; float32 operands add 1.5 MiB of tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_Q_TILES = (512, 256, 128)
_K_TILES = (1024, 512, 256, 128)
_NEG = -1e30


def default_blocks(lq: int, lk: int) -> tuple[int, int]:
    """(block_q, block_k) for sequence lengths ``lq`` and ``lk``: per axis,
    the largest of its tiles that divides the length, else the length."""

    def tile(n: int, tiles: tuple) -> int:
        return next((t for t in tiles if n % t == 0), n)

    return tile(lq, _Q_TILES), tile(lk, _K_TILES)


def band(qi, *, block_q: int, block_k: int, n_kv_blocks: int,
         causal: bool, window: Optional[int], q_offset: int = 0):
    """First and last kv block that q block ``qi`` attends to.  The queries
    sit at key positions ``q_offset + qi * block_q ...``: 0 when queries
    and keys are one sequence, ``Lk - Lq`` when the queries are its end."""
    q_start = qi * block_q
    if q_offset:
        q_start = q_start + q_offset
    first, last = 0, n_kv_blocks - 1
    if causal:
        last = jnp.minimum(last, (q_start + block_q - 1) // block_k)
    if window is not None:
        first = jnp.maximum(q_start + 1 - window, 0) // block_k
    return first, last


def kv_block(qi, ki, **band_kw):
    """The K/V block grid cell (qi, ki) reads: ki inside the band, else the
    nearest live block, which the pipeline already holds, so nothing is
    copied for a masked cell."""
    first, last = band(qi, **band_kw)
    return jnp.minimum(jnp.maximum(ki, first), last)


def init_state(ki, m_scr, l_scr, acc_scr) -> None:
    """Empties the online softmax's state at the first kv block."""

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)


def mask_scores(s, q_start, k_start, *, causal: bool, window: Optional[int]):
    """``s`` with the pairs outside the causal / window band set to -inf
    (their p is then exactly 0); ``q_start`` and ``k_start`` are the key
    positions of its first row and column."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = None
    if causal:
        keep = q_pos >= k_pos
    if window is not None:
        near = (q_pos - k_pos) < window
        keep = near if keep is None else keep & near
    return jnp.where(keep, s, -jnp.inf)


def softmax_update(s, v_ref, m_scr, l_scr, acc_scr) -> None:
    """One online-softmax step: float32 scores ``s`` (rows, block_k) against
    the values ``v_ref`` holds (block_k, dv); p is cast to their dtype for
    the MXU and accumulates in float32."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[...]
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new


def normalized(l_scr, acc_scr):
    """The attention output the state holds after the last kv block."""
    return acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)


def dot_nt(a, b):
    """a (m, d) . b (n, d)^T in float32, operands in their own dtype."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def banded(step, qi, ki, q_start, k_start, *, block_q: int, block_k: int,
           causal: bool, window: Optional[int], band_fn) -> None:
    """Runs ``step(masked)`` for grid cell (qi, ki): not at all outside the
    band (``band_fn``), unmasked for a block wholly inside it, masked for a
    block on its edge.  ``q_start``, ``k_start``: the block's first key
    positions."""
    if not causal and window is None:
        step(masked=False)
        return
    first, last = band_fn(qi)
    live = jnp.logical_and(ki >= first, ki <= last)
    # inside the band: no (query, key) pair of the block is masked
    inside = True
    if causal:
        inside = k_start + block_k - 1 <= q_start
    if window is not None:
        inside = jnp.logical_and(inside, q_start + block_q - 1 - k_start < window)

    @pl.when(jnp.logical_and(live, inside))
    def _inside():
        step(masked=False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(inside)))
    def _edge():
        step(masked=True)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, n_kv_blocks: int, band_fn,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    init_state(ki, m_scr, l_scr, acc_scr)
    q_start = qi * block_q
    k_start = ki * block_k

    def step(masked: bool):
        s = dot_nt(q_ref[0, 0], k_ref[0, 0]) * scale  # (block_q, block_k)
        if masked:
            s = mask_scores(s, q_start, k_start, causal=causal, window=window)
        softmax_update(s, v_ref.at[0, 0], m_scr, l_scr, acc_scr)

    banded(step, qi, ki, q_start, k_start, block_q=block_q, block_k=block_k,
           causal=causal, window=window, band_fn=band_fn)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = normalized(l_scr, acc_scr).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, H, Lq, hd)
    k: jax.Array,  # (B, KV, Lk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, lq, hd = q.shape
    n_kv, lk = k.shape[1], k.shape[2]
    assert h % n_kv == 0, (h, n_kv)
    rule_q, rule_k = default_blocks(lq, lk)
    block_q = min(block_q or rule_q, lq)
    block_k = min(block_k or rule_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, block_q, lk, block_k)
    nq, nk = lq // block_q, lk // block_k
    scale = 1.0 / (hd**0.5)
    band_kw = dict(
        block_q=block_q, block_k=block_k, n_kv_blocks=nk, causal=causal, window=window,
    )
    band_fn = functools.partial(band, **band_kw)
    kv_of = functools.partial(kv_block, **band_kw)

    def kv_index(b_, h_, qi, ki):
        return (b_, h_ * n_kv // h, kv_of(qi, ki), 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk, band_fn=band_fn,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
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
