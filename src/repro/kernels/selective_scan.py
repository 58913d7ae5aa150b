"""Mamba1 selective-scan chunk Pallas TPU kernel.

Computes one sequence chunk of the diagonal SSM recurrence
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t,   y_t = <h_t, C_t>
carrying the (d_inner, N) state in VMEM across the chunk's timesteps.

TPU mapping: grid = (batch, d_inner blocks).  Per grid cell the kernel holds
    x/dt tiles   (chunk, block_d)      ~ chunk*block_d*4B
    B/C tiles    (chunk, N)
    state        (N, block_d) fp32, d_inner on the 128 lanes
entirely in VMEM and walks the chunk sequentially with a fori_loop - the
hardware-aware "materialize (L, d, N) only chunk-wise" trick from the Mamba
paper, re-tiled for VMEM instead of SRAM.  The state is kept transposed
(A and h0 are transposed outside the kernel) so N=16 fills sublanes instead
of padding 16 lanes to 128.  Rows are loaded in aligned 16-row slabs and
indexed statically inside them: a bf16 tile packs 16 rows, so a single-row
dynamic load from it cannot be proven aligned.  block_d defaults to 512
(multiple of the 128-lane width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 512
_SLAB = 16  # rows per load: one packed (16, 128) bf16 tile


def _slab(rows: int) -> int:
    return next(s for s in (_SLAB, 8, 4, 2, 1) if rows % s == 0)


def _column(row, eye):
    """(1, N) row -> (N, 1) column: mask the diagonal, reduce over lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref, *, chunk: int):
    a = a_ref[...].astype(jnp.float32)  # (N, block_d)
    h = h0_ref[0].astype(jnp.float32)  # (N, block_d)
    n = a.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    slab = _slab(chunk)

    def body(i, h):
        # one aligned slab of rows per load (a whole packed tile for bf16 x),
        # then static row indices inside it
        base = pl.multiple_of(i * slab, slab)
        xs = x_ref[0, pl.ds(base, slab), :].astype(jnp.float32)  # (slab, block_d)
        dts = dt_ref[0, pl.ds(base, slab), :].astype(jnp.float32)
        bs = b_ref[0, pl.ds(base, slab), :].astype(jnp.float32)  # (slab, N)
        cs = c_ref[0, pl.ds(base, slab), :].astype(jnp.float32)
        for s in range(slab):
            dt_t = dts[s:s + 1]  # (1, block_d)
            da = jnp.exp(dt_t * a)  # (N, block_d)
            h = da * h + (dt_t * xs[s:s + 1]) * _column(bs[s:s + 1], eye)
            y_t = jnp.sum(h * _column(cs[s:s + 1], eye), axis=0, keepdims=True)
            y_ref[0, pl.ds(base + s, 1), :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // slab, body, h)
    h_ref[0] = h.astype(h_ref.dtype)


def selective_scan_chunk(
    x: jax.Array,  # (B, chunk, di)
    dt: jax.Array,  # (B, chunk, di) fp32
    b: jax.Array,  # (B, chunk, N) fp32
    c: jax.Array,  # (B, chunk, N) fp32
    a: jax.Array,  # (di, N) fp32
    h0: jax.Array,  # (B, di, N) fp32
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
):
    """Returns (y (B, chunk, di) fp32, h_last (B, di, N) fp32)."""
    B, chunk, di = x.shape
    N = b.shape[-1]
    block_d = min(block_d, di)
    assert di % block_d == 0, (di, block_d)
    nd = di // block_d

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nd),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, d: (b_, 0, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, d: (b_, 0, d)),
            pl.BlockSpec((1, chunk, N), lambda b_, d: (b_, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b_, d: (b_, 0, 0)),
            pl.BlockSpec((N, block_d), lambda b_, d: (0, d)),
            pl.BlockSpec((1, N, block_d), lambda b_, d: (b_, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, d: (b_, 0, d)),
            pl.BlockSpec((1, N, block_d), lambda b_, d: (b_, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, chunk, di), jnp.float32),
            jax.ShapeDtypeStruct((B, N, di), jnp.float32),
        ],
        interpret=interpret,
        name="selective_scan",
    )(x, dt, b, c, a.T, h0.swapaxes(1, 2))
    return y, h_last.swapaxes(1, 2)
