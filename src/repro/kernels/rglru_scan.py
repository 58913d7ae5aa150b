"""RG-LRU linear-recurrence Pallas TPU kernel (RecurrentGemma).

    h_t = exp(log_a_t) * h_{t-1} + gx_t          (elementwise over d_rnn)

Grid = (batch, d_rnn blocks, sequence blocks).  The sequence axis is the
innermost, sequential one: the (1, block_d) f32 state is carried across it
in VMEM scratch, so a tile holds ``block_l`` timesteps and not the whole
sequence (RecurrentGemma-2B's L=2048 x d_rnn=2560 would not fit VMEM once
the pipeline double-buffers a whole-sequence tile).  Inside a tile the
kernel loads aligned slabs of 8 rows and walks them with static indices,
so every load is a whole (8, 128) f32 tile.  The recurrence is
memory-bound: one load of log_a/gx and one store of y per step.  Gates and
log_a are precomputed outside (dense matmuls that XLA maps to the MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 512
BLOCK_L = 256  # timesteps per grid step (upper bound; see seq_block)
_SLAB = 8  # rows per aligned f32 load


def seq_block(L: int) -> int:
    """Timesteps per grid step: L itself when short, else the largest
    multiple of 8 up to BLOCK_L that divides L (L when none does)."""
    if L <= BLOCK_L:
        return L
    for bl in range(BLOCK_L, 7, -8):
        if L % bl == 0:
            return bl
    return L


def _slab(rows: int) -> int:
    return next(s for s in (_SLAB, 4, 2, 1) if rows % s == 0)


def _rglru_kernel(loga_ref, gx_ref, h0_ref, y_ref, h_ref, h_scr, *, block_l: int, n_l: int):
    li = pl.program_id(2)
    slab = _slab(block_l)

    @pl.when(li == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, block_d)

    def body(i, h):
        base = pl.multiple_of(i * slab, slab)
        a = jnp.exp(loga_ref[0, pl.ds(base, slab), :].astype(jnp.float32))
        g = gx_ref[0, pl.ds(base, slab), :].astype(jnp.float32)
        for s in range(slab):
            h = a[s:s + 1] * h + g[s:s + 1]
            y_ref[0, pl.ds(base + s, 1), :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_l // slab, body, h_scr[...])
    h_scr[...] = h

    @pl.when(li == n_l - 1)
    def _finalize():
        h_ref[0] = h.astype(h_ref.dtype)


def rglru_scan(
    log_a: jax.Array,  # (B, L, dr) fp32
    gx: jax.Array,  # (B, L, dr) fp32
    h0: jax.Array,  # (B, dr) fp32 (zeros if None)
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
):
    """Returns (y (B, L, dr) fp32, h_last (B, dr) fp32)."""
    B, L, dr = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, dr), jnp.float32)
    block_d = min(block_d, dr)
    assert dr % block_d == 0, (dr, block_d)
    block_l = seq_block(L)
    n_l = L // block_l

    kernel = functools.partial(_rglru_kernel, block_l=block_l, n_l=n_l)
    seq_spec = pl.BlockSpec((1, block_l, block_d), lambda b, d, l: (b, l, d))
    # the state rides as (B, 1, dr): a (1, block_d) block whose sublane dim
    # equals the array's, which Mosaic tiles without a partial-row layout
    state_spec = pl.BlockSpec((1, 1, block_d), lambda b, d, l: (b, 0, d))
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, dr // block_d, n_l),
        in_specs=[seq_spec, seq_spec, state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, dr), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, dr), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="rglru_scan",
    )(log_a, gx, h0.reshape(B, 1, dr))
    return y, h_last.reshape(B, dr)
