"""Grouped (per-expert) matmul Pallas TPU kernel for MoE FFN compute.

Computes y[e] = x[e] @ w[e] for capacity-dispatched expert inputs
x (E, C, D) and stacked expert weights w (E, D, F) - the compute hot-spot of
the MoE layer once tokens have been dispatched.

TPU mapping: grid = (E, C blocks, F blocks, D blocks) with a float32 VMEM
accumulator carried across the innermost (sequential) D dimension; the
operands meet on the MXU in their own dtype and the output tile is cast
once, after the last D block.

Tiles
  ``default_blocks`` is the one tile rule for every caller without an
  explicit config (ops.moe_gmm, registry defaults).  Per axis it takes the
  largest candidate tile that divides the dimension, else the dimension
  itself, and steps block_d, then block_f, then block_c down until the
  tiles fit the VMEM budget.  block_c comes first because it sets how often
  the weight is read: the pipeline copies a w tile once per (C block, F
  block, D block), so with all of C in one block the weight - the largest
  operand, 402.7 MB for a Grok-1 expert - is read once, and x once per F
  block.  At Grok-1's expert (C 1024, D 6144, F 32768) that is 1024 x 1024
  x 512 (block_c x block_f x block_d): 384 grid steps at 341 FLOP/B each,
  against 12,288 steps at 85 FLOP/B with the earlier fixed 128 x 256 x 512.

VMEM
  The x, w and y tiles are double-buffered and the accumulator is float32:
  ``vmem_bytes`` = 2 isz (bc bd + bd bf + bc bf) + 4 bc bf, 12 MiB at the
  Grok-1 tiles in bf16.  The kernel asks Mosaic for that plus the float32
  product of one step (4 bc bf) as its scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C_TILES = (1024, 512, 256, 128)
_F_TILES = (1024, 512, 256, 128)
_D_TILES = (512, 256, 128)
VMEM_BUDGET = 16 * 2**20  # v5e's scoped VMEM default


def vmem_bytes(bc: int, bd: int, bf: int, itemsize: int) -> int:
    """Double-buffered x, w and y tiles plus the float32 accumulator."""
    return 2 * itemsize * (bc * bd + bd * bf + bc * bf) + 4 * bc * bf


def default_blocks(C: int, D: int, F: int, itemsize: int) -> tuple[int, int, int]:
    """(block_c, block_f, block_d) for x (E, C, D) @ w (E, D, F) with
    operands of ``itemsize`` bytes: the largest dividing tiles, block_c kept
    largest, whose ``vmem_bytes`` fit ``VMEM_BUDGET``."""

    def tiles(n: int, candidates: tuple) -> list:
        return [t for t in candidates if n % t == 0] or [n]

    largest_first = [
        (bc, bf, bd)
        for bc in tiles(C, _C_TILES)
        for bf in tiles(F, _F_TILES)
        for bd in tiles(D, _D_TILES)
    ]
    return next(
        (t for t in largest_first if vmem_bytes(t[0], t[2], t[1], itemsize) <= VMEM_BUDGET),
        largest_first[-1],
    )


def _gmm_kernel(x_ref, w_ref, y_ref, acc_scr, *, n_d_blocks: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]  # (block_c, block_d)
    w = w_ref[0]  # (block_d, block_f)
    acc_scr[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(di == n_d_blocks - 1)
    def _finalize():
        y_ref[0] = acc_scr[...].astype(y_ref.dtype)


def moe_gmm(
    x: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int,
    block_f: int,
    block_d: int,
    interpret: bool = False,
) -> jax.Array:
    E, C, D = x.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0
    nc, nf, nd = C // block_c, F // block_f, D // block_d
    vmem = vmem_bytes(block_c, block_d, block_f, x.dtype.itemsize) + 4 * block_c * block_f

    kernel = functools.partial(_gmm_kernel, n_d_blocks=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, block_d, block_f), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, VMEM_BUDGET),
        ),
        interpret=interpret,
        name="moe_gmm",
    )(x, w)
