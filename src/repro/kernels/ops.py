"""Jit'd dispatch wrappers over the Pallas kernels.

Off a TPU (registry.interpret_default) the kernels execute in interpret
mode - the kernel body runs step-by-step in Python/XLA so correctness (and
the BlockSpec tiling logic) is fully exercised without Mosaic.  On a real
v5e these same calls lower to Mosaic TPU kernels.

Block-config resolution happens OUTSIDE the jitted functions (block sizes
are static jit arguments, so a cache lookup inside the trace would bake the
first answer in forever): each public wrapper resolves

  explicit caller argument  >  autotuned cache (HYDRA_AUTOTUNE=1 only)
                            >  the kernel's committed default

then calls the private jitted dispatcher.  With the env gate off (the
default) the tuner is never consulted and behavior is bit-identical to the
static defaults; see kernels/autotune.py for the cache.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru_scan as _rg
from repro.kernels import selective_scan as _ss
from repro.kernels.autotune import tuned_config
from repro.kernels.registry import interpret_default


def _resolve(kernel: str, shape: dict, dtype, defaults: dict, explicit: dict) -> dict:
    """explicit arg > tuned cache (env-gated) > committed default."""
    if all(v is not None for v in explicit.values()):
        return explicit
    tuned = tuned_config(kernel, shape, str(jax.numpy.dtype(dtype))) or {}
    return {
        k: v if v is not None else tuned.get(k, defaults[k])
        for k, v in explicit.items()
    }


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def _flash_attention_jit(q, k, v, *, causal, window, block_q, block_k):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_default(),
    )


def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """q (B,H,Lq,hd); k,v (B,KV,Lk,hd) -> (B,H,Lq,hd)."""
    b, h, lq, hd = q.shape
    shape = {
        "B": b, "H": h, "KV": k.shape[1], "L": lq, "hd": hd,
        "causal": causal, "window": window,
    }
    rule_q, rule_k = _fa.default_blocks(lq, k.shape[2])
    cfg = _resolve(
        "flash_attention", shape, q.dtype,
        {"block_q": rule_q, "block_k": rule_k},
        {"block_q": block_q, "block_k": block_k},
    )
    return _flash_attention_jit(
        q, k, v, causal=causal, window=window,
        block_q=cfg["block_q"], block_k=cfg["block_k"],
    )


@partial(jax.jit, static_argnames=("block_d",))
def _selective_scan_jit(x, dt, b, c, a, h0, *, block_d):
    return _ss.selective_scan_chunk(x, dt, b, c, a, h0, block_d=block_d, interpret=interpret_default())


def selective_scan_chunk(x, dt, b, c, a, h0, *, block_d: Optional[int] = None):
    """One SSM chunk: returns (y (B,chunk,di) f32, h_last (B,di,N) f32)."""
    B, chunk, di = x.shape
    shape = {"B": B, "chunk": chunk, "di": di, "N": b.shape[-1]}
    cfg = _resolve(
        "selective_scan", shape, x.dtype,
        {"block_d": _ss.DEFAULT_BLOCK_D}, {"block_d": block_d},
    )
    return _selective_scan_jit(x, dt, b, c, a, h0, block_d=cfg["block_d"])


@partial(jax.jit, static_argnames=("block_d",))
def _rglru_scan_jit(log_a, gx, h0, *, block_d):
    return _rg.rglru_scan(log_a, gx, h0, block_d=block_d, interpret=interpret_default())


def rglru_scan(log_a, gx, h0=None, *, block_d: Optional[int] = None):
    """RG-LRU over a sequence: returns (y (B,L,dr) f32, h_last (B,dr) f32)."""
    B, L, dr = log_a.shape
    shape = {"B": B, "L": L, "dr": dr}
    cfg = _resolve(
        "rglru_scan", shape, log_a.dtype,
        {"block_d": _rg.DEFAULT_BLOCK_D}, {"block_d": block_d},
    )
    return _rglru_scan_jit(log_a, gx, h0, block_d=cfg["block_d"])


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_d"))
def _moe_gmm_jit(x, w, *, block_c, block_f, block_d):
    return _gmm.moe_gmm(x, w, block_c=block_c, block_f=block_f, block_d=block_d, interpret=interpret_default())


def moe_gmm(
    x, w, *,
    block_c: Optional[int] = None,
    block_f: Optional[int] = None,
    block_d: Optional[int] = None,
):
    """Grouped expert matmul: x (E,C,D) @ w (E,D,F) -> (E,C,F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    shape = {"E": E, "C": C, "D": D, "F": F}
    rule_c, rule_f, rule_d = _gmm.default_blocks(C, D, F, x.dtype.itemsize)
    cfg = _resolve(
        "moe_gmm", shape, x.dtype,
        {"block_c": rule_c, "block_f": rule_f, "block_d": rule_d},
        {"block_c": block_c, "block_f": block_f, "block_d": block_d},
    )
    return _moe_gmm_jit(
        x, w, block_c=cfg["block_c"], block_f=cfg["block_f"], block_d=cfg["block_d"]
    )
