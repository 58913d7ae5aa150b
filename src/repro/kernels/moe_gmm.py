"""Grouped (per-expert) matmul Pallas TPU kernel for MoE FFN compute.

Computes y[e] = x[e] @ w[e] for capacity-dispatched expert inputs
x (E, C, D) and stacked expert weights w (E, D, F) - the compute hot-spot of
the MoE layer once tokens have been dispatched.

TPU mapping: grid = (E, C blocks, F blocks, D blocks) with an fp32 VMEM
accumulator carried across the innermost (sequential) D dimension; every
block dim is a multiple of 128 so the (block_c x block_d) @ (block_d x
block_f) product runs on the MXU at full tile occupancy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 128
DEFAULT_BLOCK_F = 256
DEFAULT_BLOCK_D = 512


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
    block_c: int = DEFAULT_BLOCK_C,
    block_f: int = DEFAULT_BLOCK_F,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    E, C, D = x.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0
    nc, nf, nd = C // block_c, F // block_f, D // block_d

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
        interpret=interpret,
        name="moe_gmm",
    )(x, w)
