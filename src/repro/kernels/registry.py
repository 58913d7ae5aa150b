"""Kernel registry: one shared description of every Pallas kernel.

Each :class:`KernelDef` bundles what the rest of the system needs to treat a
kernel as brokered work rather than a hand-called function:

  make_args     seeded, deterministic problem-instance builder (same seed +
                same shape => bit-identical operands on every host)
  call / ref    the Pallas path (explicit block config + interpret flag) and
                the pure-jnp oracle from kernels/ref.py
  space         the exhaustive block/tile sweep space for a problem shape
  cost          the roofline cost model for one (shape, config) point:
                FLOPs, modeled HBM traffic, VMEM tile footprint, grid cells

The cost model mirrors the BlockSpec tiling: the pipeline copies a block
only when its index changes.  Attention's K/V index map clamps masked-out
cells onto a live block, so K/V traffic counts one tile fetch per *live*
cell, q and output tiles once per q row; FLOPs count live cells only
(``pl.when`` skips the math).  Larger attention blocks trade extra masked
FLOPs for fewer cell launches and less re-fetched K/V — the three-way
frontier the autotuner prunes on (kernels/autotune.py).

Consumers: the autotuner, the ``kind="kernel"`` task runtime
(core/managers/compute.py), benchmarks/kernels_bench.py, and the parity
tests.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as _fa
from repro.kernels import mla_attention as _mla
from repro.kernels import moe_gmm as _gmm
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import selective_scan as _ss

# power-of-two block candidates; a config is admissible only if every block
# divides its dimension (after the kernels' own min(block, dim) clamp)
_BLOCK_CANDIDATES = (32, 64, 128, 256, 512, 1024)


@dataclass(frozen=True)
class Cost:
    """Roofline cost of one (shape, config) point."""

    flops: float
    hbm_bytes: float
    vmem_bytes: float
    grid_cells: int

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per modeled HBM byte)."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0


@dataclass(frozen=True)
class KernelDef:
    name: str
    params: tuple  # config keys, canonical order
    defaults: Callable[[dict, str], dict]  # (shape, dtype) -> the tile rule's config
    make_args: Callable[[dict, str, int], tuple]
    call: Callable[[dict, tuple, dict, bool], Any]
    ref: Callable[[dict, tuple], Any]
    space: Callable[[dict], list]
    cost: Callable[[dict, dict, str], Cost]
    tiny_shape: dict  # default payload shape for kind="kernel" tasks
    smoke_shape: dict  # CI bench shape (BENCH_smoke.json rows)
    full_shape: dict  # nightly sweep shape


def _isz(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def _lanes(n: int) -> int:
    """Minor dims are laid out in 128-lane vregs: a tile pays for whole ones."""
    return -(-n // 128) * 128


def _rows(n: int) -> int:
    """Second-minor dims are laid out in 8-row sublane groups."""
    return -(-n // 8) * 8


def _divisors(dim: int, candidates=_BLOCK_CANDIDATES) -> list:
    out = [c for c in candidates if c <= dim and dim % c == 0]
    return out or [dim]


def shape_sig(shape: dict, dtype: str) -> str:
    """Canonical shape signature used in tune-cache keys: sorted ``k=v``
    pairs + dtype, no spaces (dataset names must be stable strings)."""
    parts = [f"{k}={shape[k]}".lower() for k in sorted(shape)]
    parts.append(f"dtype={dtype}")
    return ",".join(parts)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


def _fa_blocks(shape: dict, config: dict) -> tuple:
    lq = shape["L"]
    bq = min(config["block_q"], lq)
    bk = min(config["block_k"], lq)
    return bq, bk, lq // bq, lq // bk


def _live_cells(nq: int, nk: int, bq: int, bk: int, causal: bool, window, q_offset: int = 0) -> int:
    """Grid cells (qi, ki) inside the causal / window band, the queries at
    key positions ``q_offset + qi * bq ...``."""
    live = 0
    for qi in range(nq):
        q0 = q_offset + qi * bq
        for ki in range(nk):
            ok = True
            if causal:
                ok = ki * bk <= q0 + bq - 1
            if window is not None:
                ok = ok and (q0 - (ki * bk + bk - 1) < window)
            live += ok
    return live


def _fa_live_cells(shape: dict, config: dict) -> int:
    bq, bk, nq, nk = _fa_blocks(shape, config)
    return _live_cells(nq, nk, bq, bk, shape.get("causal", True), shape.get("window"))


def _fa_defaults(shape: dict, dtype: str) -> dict:
    block_q, block_k = _fa.default_blocks(shape["L"], shape["L"])
    return {"block_q": block_q, "block_k": block_k}


def _fa_make_args(shape: dict, dtype: str, seed: int) -> tuple:
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, H, KV, L, hd = shape["B"], shape["H"], shape["KV"], shape["L"], shape["hd"]
    q = jax.random.normal(kq, (B, H, L, hd), jnp.dtype(dtype))
    k = jax.random.normal(kk, (B, KV, L, hd), jnp.dtype(dtype))
    v = jax.random.normal(kv, (B, KV, L, hd), jnp.dtype(dtype))
    return q, k, v


def _fa_call(shape: dict, args: tuple, config: dict, interpret: bool):
    q, k, v = args
    return _fa.flash_attention(
        q, k, v,
        causal=shape.get("causal", True), window=shape.get("window"),
        block_q=config["block_q"], block_k=config["block_k"],
        interpret=interpret,
    )


def _fa_ref(shape: dict, args: tuple):
    q, k, v = args
    return _ref.attention_ref(
        q, k, v, causal=shape.get("causal", True), window=shape.get("window")
    )


def _fa_space(shape: dict) -> list:
    divs = _divisors(shape["L"], candidates=(32, 64, 128, 256, 512))
    return [{"block_q": bq, "block_k": bk} for bq in divs for bk in divs]


def _fa_cost(shape: dict, config: dict, dtype: str) -> Cost:
    B, H, hd = shape["B"], shape["H"], shape["hd"]
    isz = _isz(dtype)
    bq, bk, nq, nk = _fa_blocks(shape, config)
    live = _fa_live_cells(shape, config)
    cells = B * H * nq * nk
    # two MXU matmuls (q@k^T and p@v) per LIVE cell; masked cells skip math
    flops = 4.0 * B * H * live * bq * bk * hd
    # a k tile and a v tile per LIVE cell (masked cells repeat a live block's
    # index, so nothing is copied for them); q read and output written once
    hbm = isz * B * H * (live * 2 * bk * hd + 2 * shape["L"] * hd)
    # q/k/v input tiles and the output tile, each double-buffered by the
    # pipeline, + fp32 scratch (m and l are (bq, 1): a full lane row each)
    vmem = 2 * isz * (bq + 2 * bk + bq) * hd + 4 * bq * (2 * 128 + hd)
    return Cost(flops, float(hbm), float(vmem), cells)


# ---------------------------------------------------------------------------
# mla_prefill / mla_decode (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------


def _mla_make_args(shape: dict, dtype: str, seed: int) -> tuple:
    """q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, after RoPE.  A decode
    shape (no ``Lq``) has one query per head and a (B, Lk, dr) rope cache;
    a prefill shape keeps k_rope as one head every query head reads."""
    kqn, kqr, kc, kr, ku, kv = jax.random.split(jax.random.PRNGKey(seed), 6)
    B, H, Lk, dc, dn, dr, dv = (shape[k] for k in ("B", "H", "Lk", "dc", "dn", "dr", "dv"))
    dt = jnp.dtype(dtype)
    decode = "Lq" not in shape
    q = (B, H) if decode else (B, H, shape["Lq"])
    rope = (B, Lk, dr) if decode else (B, 1, Lk, dr)

    def weight(key, d):
        return (jax.random.normal(key, (dc, H, d), jnp.float32) * (1.0 / dc**0.5)).astype(dt)

    return (
        jax.random.normal(kqn, q + (dn,), dt), jax.random.normal(kqr, q + (dr,), dt),
        jax.random.normal(kc, (B, Lk, dc), dt), jax.random.normal(kr, rope, dt),
        weight(ku, dn), weight(kv, dv),
    )


def _mlap_blocks(shape: dict, config: dict) -> tuple:
    bq, bk = min(config["block_q"], shape["Lq"]), min(config["block_k"], shape["Lk"])
    return bq, bk, shape["Lq"] // bq, shape["Lk"] // bk


def _mlap_defaults(shape: dict, dtype: str) -> dict:
    block_q, block_k = _fa.default_blocks(shape["Lq"], shape["Lk"])
    return {"block_q": block_q, "block_k": block_k}


def _mlap_call(shape: dict, args: tuple, config: dict, interpret: bool):
    return _mla.prefill_program(
        *args, scale=shape["scale"], block_q=config["block_q"], block_k=config["block_k"],
        interpret=interpret,
    )


def _mlap_ref(shape: dict, args: tuple):
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    return _ref.mla_ref(q_nope, q_rope, c_kv, k_rope[:, 0], w_uk, w_uv, scale=shape["scale"])


def _mlap_space(shape: dict) -> list:
    return [
        {"block_q": bq, "block_k": bk}
        for bq in _divisors(shape["Lq"], candidates=(32, 64, 128, 256, 512))
        for bk in _divisors(shape["Lk"])
    ]


def _mla_decompress(shape: dict, isz: int) -> tuple:
    """FLOPs and HBM bytes of the XLA projections around an MLA kernel:
    prefill decompresses the latent into K and V per head; decode absorbs
    the query and un-absorbs the output."""
    B, H, Lk, dc, dn, dv = (shape[k] for k in ("B", "H", "Lk", "dc", "dn", "dv"))
    w = isz * dc * H * (dn + dv)
    if "Lq" in shape:
        return 2.0 * B * Lk * dc * H * (dn + dv), isz * (B * Lk * dc + B * H * Lk * (dn + dv)) + w
    return 2.0 * B * H * dc * (dn + dv), isz * B * H * (dn + 2 * dc + dv) + w


def _mlap_cost(shape: dict, config: dict, dtype: str) -> Cost:
    B, H, Lq, Lk, dn, dr, dv = (shape[k] for k in ("B", "H", "Lq", "Lk", "dn", "dr", "dv"))
    isz = _isz(dtype)
    bq, bk, nq, nk = _mlap_blocks(shape, config)
    live = _live_cells(nq, nk, bq, bk, True, None, q_offset=Lk - Lq)
    xla_flops, xla_bytes = _mla_decompress(shape, isz)
    # per LIVE cell: the two score parts and p.v; masked cells skip math
    flops = 2.0 * B * H * live * bq * bk * (dn + dr + dv) + xla_flops
    # k_nope, k_rope and v tiles per LIVE cell; q tiles and output once a row
    hbm = isz * B * H * (live * bk * (dn + dr + dv) + Lq * (dn + dr + dv)) + xla_bytes
    # the six tiles double-buffered (rope tiles pay whole lanes) + m, l, acc
    vmem = 2 * isz * (bq * (dn + _lanes(dr) + dv) + bk * (dn + _lanes(dr) + dv)) + 4 * bq * (2 * 128 + dv)
    return Cost(flops, float(hbm), float(vmem), B * H * nq * nk)


def _mlad_defaults(shape: dict, dtype: str) -> dict:
    return {"block_k": _fa.default_blocks(shape["H"], shape["Lk"])[1]}


def _mlad_call(shape: dict, args: tuple, config: dict, interpret: bool):
    return _mla.decode_program(*args, scale=shape["scale"], block_k=config["block_k"], interpret=interpret)


def _mlad_ref(shape: dict, args: tuple):
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    out = _ref.mla_ref(q_nope[:, :, None], q_rope[:, :, None], c_kv, k_rope, w_uk, w_uv, scale=shape["scale"])
    return out[:, :, 0]


def _mlad_space(shape: dict) -> list:
    return [{"block_k": bk} for bk in _divisors(shape["Lk"])]


def _mlad_cost(shape: dict, config: dict, dtype: str) -> Cost:
    B, H, Lk, dc, dr = (shape[k] for k in ("B", "H", "Lk", "dc", "dr"))
    isz = _isz(dtype)
    bk = min(config["block_k"], Lk)
    xla_flops, xla_bytes = _mla_decompress(shape, isz)
    # scores against [c_kv, k_rope], then p.c_kv, for every head and key
    flops = 2.0 * B * H * Lk * (dc + dr + dc) + xla_flops
    # the latent and rope caches read once; queries in and output out once
    hbm = isz * (B * Lk * (dc + dr) + B * H * (2 * dc + dr)) + xla_bytes
    vmem = 2 * isz * (H * (2 * dc + _lanes(dr)) + bk * (dc + _lanes(dr))) + 4 * H * (2 * 128 + dc)
    return Cost(flops, float(hbm), float(vmem), B * (Lk // bk))


# ---------------------------------------------------------------------------
# selective_scan
# ---------------------------------------------------------------------------


def _ss_defaults(shape: dict, dtype: str) -> dict:
    return {"block_d": _ss.DEFAULT_BLOCK_D}


def _ss_make_args(shape: dict, dtype: str, seed: int) -> tuple:
    kx, kdt, kb, kc, ka = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, ck, di, N = shape["B"], shape["chunk"], shape["di"], shape["N"]
    x = jax.random.normal(kx, (B, ck, di), jnp.dtype(dtype))
    dt = jax.random.uniform(kdt, (B, ck, di), jnp.float32, 0.001, 0.1)
    b = jax.random.normal(kb, (B, ck, N), jnp.float32)
    c = jax.random.normal(kc, (B, ck, N), jnp.float32)
    a = -jax.random.uniform(ka, (di, N), jnp.float32, 0.5, 2.0)
    h0 = jnp.zeros((B, di, N), jnp.float32)
    return x, dt, b, c, a, h0


def _ss_call(shape: dict, args: tuple, config: dict, interpret: bool):
    return _ss.selective_scan_chunk(
        *args, block_d=config["block_d"], interpret=interpret
    )


def _ss_ref(shape: dict, args: tuple):
    return _ref.selective_scan_chunk_ref(*args)


def _ss_space(shape: dict) -> list:
    return [{"block_d": bd} for bd in _divisors(shape["di"])]


def _ss_cost(shape: dict, config: dict, dtype: str) -> Cost:
    B, ck, di, N = shape["B"], shape["chunk"], shape["di"], shape["N"]
    isz = _isz(dtype)
    bd = min(config["block_d"], di)
    nd = di // bd
    cells = B * nd
    # per timestep per channel: exp-discretize + state update + y reduction
    flops = 6.0 * B * ck * di * N
    # per cell: x/dt in, B/C in (re-fetched per d-block: the config lever),
    # a + h0 in, y + h out
    per_cell = (
        isz * ck * bd + 4 * ck * bd  # x (dtype) + dt (f32)
        + 4 * (2 * ck * N + 2 * bd * N)  # b, c, a, h0
        + 4 * (ck * bd + bd * N)  # y, h_last
    )
    # every block double-buffered: x (dtype), dt, b, c, a^T, h0^T in; y, h^T
    # out.  b/c blocks are (chunk, N) with N padded to whole lanes
    vmem = 2 * (
        isz * ck * bd + 4 * ck * bd + 2 * 4 * ck * _lanes(N)
        + 2 * 4 * _rows(N) * bd + 4 * ck * bd + 4 * _rows(N) * bd
    )
    return Cost(flops, float(cells * per_cell), float(vmem), cells)


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------


def _rg_defaults(shape: dict, dtype: str) -> dict:
    return {"block_d": _rg.DEFAULT_BLOCK_D}


def _rg_make_args(shape: dict, dtype: str, seed: int) -> tuple:
    ka, kg = jax.random.split(jax.random.PRNGKey(seed), 2)
    B, L, dr = shape["B"], shape["L"], shape["dr"]
    log_a = -jax.random.uniform(ka, (B, L, dr), jnp.float32, 0.01, 1.0)
    gx = jax.random.normal(kg, (B, L, dr), jnp.float32)
    h0 = jnp.zeros((B, dr), jnp.float32)
    return log_a, gx, h0


def _rg_call(shape: dict, args: tuple, config: dict, interpret: bool):
    return _rg.rglru_scan(*args, block_d=config["block_d"], interpret=interpret)


def _rg_ref(shape: dict, args: tuple):
    return _ref.rglru_ref(*args)


def _rg_space(shape: dict) -> list:
    return [{"block_d": bd} for bd in _divisors(shape["dr"])]


def _rg_cost(shape: dict, config: dict, dtype: str) -> Cost:
    B, L, dr = shape["B"], shape["L"], shape["dr"]
    bd = min(config["block_d"], dr)
    bl = _rg.seq_block(L)
    cells = B * (dr // bd) * (L // bl)
    # exp + multiply-add per (t, channel); traffic is config-independent
    # (log_a/gx/y each touched once, h tiles sum to B*dr regardless of bd),
    # so the frontier collapses to minimum grid cells: the pruner keeps only
    # the largest admissible block
    flops = 3.0 * B * L * dr
    hbm = 4.0 * (3 * B * L * dr + 2 * B * dr)
    # log_a/gx in and y out as (bl, bd) tiles, h0/h_last as one-row blocks
    # (a full sublane group each), all double-buffered; + the state scratch
    vmem = 4.0 * (2 * (3 * bl * bd + 2 * 8 * bd) + 8 * bd)
    return Cost(flops, hbm, vmem, cells)


# ---------------------------------------------------------------------------
# moe_gmm
# ---------------------------------------------------------------------------


def _gmm_defaults(shape: dict, dtype: str) -> dict:
    block_c, block_f, block_d = _gmm.default_blocks(shape["C"], shape["D"], shape["F"], _isz(dtype))
    return {"block_c": block_c, "block_f": block_f, "block_d": block_d}


def _gmm_make_args(shape: dict, dtype: str, seed: int) -> tuple:
    kx, kw = jax.random.split(jax.random.PRNGKey(seed), 2)
    E, C, D, F = shape["E"], shape["C"], shape["D"], shape["F"]
    scale = 1.0 / (D**0.5)
    x = jax.random.normal(kx, (E, C, D), jnp.dtype(dtype))
    w = (jax.random.normal(kw, (E, D, F), jnp.float32) * scale).astype(jnp.dtype(dtype))
    return x, w


def _gmm_call(shape: dict, args: tuple, config: dict, interpret: bool):
    x, w = args
    return _gmm.moe_gmm(
        x, w,
        block_c=config["block_c"], block_f=config["block_f"],
        block_d=config["block_d"], interpret=interpret,
    )


def _gmm_ref(shape: dict, args: tuple):
    return _ref.moe_gmm_ref(*args)


def _gmm_space(shape: dict) -> list:
    return [
        {"block_c": bc, "block_f": bf, "block_d": bd}
        for bc in _divisors(shape["C"], candidates=(32, 64, 128, 256, 512, 1024))
        for bf in _divisors(shape["F"], candidates=(64, 128, 256, 512, 1024))
        for bd in _divisors(shape["D"], candidates=(128, 256, 512, 1024))
    ]


def _gmm_cost(shape: dict, config: dict, dtype: str) -> Cost:
    E, C, D, F = shape["E"], shape["C"], shape["D"], shape["F"]
    isz = _isz(dtype)
    bc = min(config["block_c"], C)
    bf = min(config["block_f"], F)
    bd = min(config["block_d"], D)
    nc, nf, nd = C // bc, F // bf, D // bd
    cells = E * nc * nf * nd
    flops = 2.0 * E * C * D * F
    # x tiles re-fetched per f-block, w tiles per c-block, y written per
    # d-block (interpret copies the out tile back every cell)
    hbm = isz * (nf * E * C * D + nc * E * D * F + nd * E * C * F)
    return Cost(flops, float(hbm), float(_gmm.vmem_bytes(bc, bd, bf, isz)), cells)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

KERNELS: dict = {
    k.name: k
    for k in (
        KernelDef(
            name="flash_attention",
            params=("block_q", "block_k"),
            defaults=_fa_defaults,
            make_args=_fa_make_args,
            call=_fa_call,
            ref=_fa_ref,
            space=_fa_space,
            cost=_fa_cost,
            tiny_shape={"B": 1, "H": 2, "KV": 1, "L": 128, "hd": 32, "causal": True, "window": None},
            smoke_shape={"B": 1, "H": 4, "KV": 2, "L": 256, "hd": 64, "causal": True, "window": None},
            full_shape={"B": 1, "H": 8, "KV": 2, "L": 512, "hd": 64, "causal": True, "window": None},
        ),
        KernelDef(
            name="mla_prefill",
            params=("block_q", "block_k"),
            defaults=_mlap_defaults,
            make_args=_mla_make_args,
            call=_mlap_call,
            ref=_mlap_ref,
            space=_mlap_space,
            cost=_mlap_cost,
            tiny_shape={"B": 1, "H": 2, "Lq": 32, "Lk": 64, "dc": 64, "dn": 32, "dr": 16, "dv": 32, "scale": 0.2},
            smoke_shape={"B": 1, "H": 4, "Lq": 64, "Lk": 256, "dc": 128, "dn": 64, "dr": 32, "dv": 64, "scale": 0.125},
            full_shape={"B": 1, "H": 8, "Lq": 128, "Lk": 512, "dc": 128, "dn": 128, "dr": 64, "dv": 128, "scale": 0.1},
        ),
        KernelDef(
            name="mla_decode",
            params=("block_k",),
            defaults=_mlad_defaults,
            make_args=_mla_make_args,
            call=_mlad_call,
            ref=_mlad_ref,
            space=_mlad_space,
            cost=_mlad_cost,
            tiny_shape={"B": 2, "H": 8, "Lk": 64, "dc": 64, "dn": 32, "dr": 16, "dv": 32, "scale": 0.2},
            smoke_shape={"B": 2, "H": 16, "Lk": 256, "dc": 128, "dn": 64, "dr": 32, "dv": 64, "scale": 0.125},
            full_shape={"B": 4, "H": 16, "Lk": 512, "dc": 128, "dn": 128, "dr": 64, "dv": 128, "scale": 0.1},
        ),
        KernelDef(
            name="selective_scan",
            params=("block_d",),
            defaults=_ss_defaults,
            make_args=_ss_make_args,
            call=_ss_call,
            ref=_ss_ref,
            space=_ss_space,
            cost=_ss_cost,
            tiny_shape={"B": 1, "chunk": 32, "di": 128, "N": 8},
            smoke_shape={"B": 2, "chunk": 64, "di": 256, "N": 16},
            full_shape={"B": 2, "chunk": 128, "di": 1024, "N": 16},
        ),
        KernelDef(
            name="rglru_scan",
            params=("block_d",),
            defaults=_rg_defaults,
            make_args=_rg_make_args,
            call=_rg_call,
            ref=_rg_ref,
            space=_rg_space,
            cost=_rg_cost,
            tiny_shape={"B": 1, "L": 64, "dr": 128},
            smoke_shape={"B": 2, "L": 128, "dr": 512},
            full_shape={"B": 2, "L": 256, "dr": 1024},
        ),
        KernelDef(
            name="moe_gmm",
            params=("block_c", "block_f", "block_d"),
            defaults=_gmm_defaults,
            make_args=_gmm_make_args,
            call=_gmm_call,
            ref=_gmm_ref,
            space=_gmm_space,
            cost=_gmm_cost,
            tiny_shape={"E": 2, "C": 64, "D": 128, "F": 128},
            smoke_shape={"E": 4, "C": 128, "D": 256, "F": 512},
            full_shape={"E": 8, "C": 256, "D": 512, "F": 512},
        ),
    )
}


def get_kernel(name: str) -> KernelDef:
    kdef = KERNELS.get(name)
    if kdef is None:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(KERNELS)}"
        )
    return kdef


def config_sig(config: dict) -> str:
    """Canonical ``k=v`` string of a block config (event attrs, payloads)."""
    return ",".join(f"{k}={config[k]}" for k in sorted(config))


def interpret_default(device=None) -> bool:
    """The one rule for interpret mode: interpret unless the kernel runs on
    a TPU (``device``, else the default backend)."""
    platform = device.platform if device is not None else jax.default_backend()
    return platform != "tpu"


def published_shapes() -> dict:
    """Each kernel at the widths of a published model that carries it:
    name -> (arch, shape, dtypes).  One chip's share of the model: batch 1,
    one expert, one SSM chunk; for latent attention the last 2,048-token
    chunk of a 16k prompt and one decode step of 32 such sequences (MLA
    runs data-parallel: a chip holds every head of its sequences)."""
    from repro.configs import get_arch
    from repro.configs.deepseek_v3 import CONFIG as dsv3

    llama = get_arch("llama3-8b")
    mamba = get_arch("falcon-mamba-7b")
    rgemma = get_arch("recurrentgemma-2b")
    grok = get_arch("grok-1-314b")
    context = 16384
    mla = {
        "H": dsv3.n_heads, "Lk": context, "dc": dsv3.kv_lora_rank, "dn": dsv3.qk_nope_head_dim,
        "dr": dsv3.qk_rope_head_dim, "dv": dsv3.v_head_dim, "scale": dsv3.softmax_scale(context),
    }
    return {
        "flash_attention": (llama.name, {
            "B": 1, "H": llama.n_heads, "KV": llama.n_kv_heads, "L": 2048,
            "hd": llama.head_dim, "causal": True, "window": None,
        }, ("bfloat16",)),
        "selective_scan": (mamba.name, {
            "B": 1, "chunk": mamba.ssm_chunk, "di": mamba.d_inner, "N": mamba.ssm_state,
        }, ("float32", "bfloat16")),
        "rglru_scan": (rgemma.name, {
            "B": 1, "L": rgemma.local_window, "dr": rgemma.rnn_width,
        }, ("float32",)),
        "moe_gmm": (grok.name, {
            "E": 1, "C": 1024, "D": grok.d_model, "F": grok.d_ff,
        }, ("bfloat16",)),
        "mla_prefill": (dsv3.name, {"B": 1, "Lq": 2048, **mla}, ("bfloat16",)),
        "mla_decode": (dsv3.name, {"B": 32, **mla}, ("bfloat16",)),
    }


# AOT executables and operand builders, one per (kernel, shape, dtype,
# config, device): a kernel task compiles once per device and every later
# task on that device reuses the program
_PROGRAMS: dict = {}
_PROGRAMS_LOCK = threading.Lock()


def _program(key: tuple, build: Callable[[], Any]):
    with _PROGRAMS_LOCK:
        prog = _PROGRAMS.get(key)
        if prog is None:
            prog = _PROGRAMS[key] = build()
        return prog


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: jit names the program, its host events and its
    device ops after the function, so a trace reads ``moe_gmm``, not
    ``_lambda_``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def operands(kdef: KernelDef, shape: dict, dtype: str, seed: int, device):
    """``make_args`` compiled for ``device``: the operands are built there,
    so the kernel call that takes them runs there too."""
    key = ("args", kdef.name, shape_sig(shape, dtype), device)
    make = _program(key, lambda: jax.jit(
        _named(lambda s: kdef.make_args(shape, dtype, s), f"{kdef.name}_operands"),
        out_shardings=SingleDeviceSharding(device),
    ))
    return make(jnp.int32(seed))


def compiled(kdef: KernelDef, shape: dict, dtype: str, config: dict, device):
    """The kernel at (shape, dtype, config) compiled for ``device``.  On a
    TPU the program must hold the Mosaic kernel (``tpu_custom_call``): a
    silently interpreted or XLA-lowered kernel is an error, not a fallback."""
    interpret = interpret_default(device)
    key = ("call", kdef.name, shape_sig(shape, dtype), config_sig(config), device)

    def build():
        sharding = SingleDeviceSharding(device)
        avals = jax.eval_shape(lambda: kdef.make_args(shape, dtype, 0))
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding) for a in avals]
        call = _named(lambda *a: kdef.call(shape, a, config, interpret), kdef.name)
        prog = jax.jit(call).lower(*specs).compile()
        if not interpret and "tpu_custom_call" not in prog.as_text():
            raise RuntimeError(
                f"{kdef.name} at {shape_sig(shape, dtype)} compiled for "
                f"{device.device_kind} without a Mosaic kernel"
            )
        return prog

    return _program(key, build)


@jax.jit
def _checksum(leaves) -> jax.Array:
    return sum(jnp.sum(x.astype(jnp.float32)) for x in leaves)


def checksum(out) -> float:
    """f32 sum over every leaf of a kernel's output, reduced on its device:
    the same program on the same operands gives the same value bit for bit."""
    return float(_checksum(jax.tree_util.tree_leaves(out)))


def max_abs_err(a, b) -> float:
    """Max elementwise |a - b| across a pytree pair (parity gate metric)."""
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(leaves_a, leaves_b)
    )


__all__ = [
    "Cost",
    "KernelDef",
    "KERNELS",
    "get_kernel",
    "shape_sig",
    "config_sig",
    "interpret_default",
    "published_shapes",
    "operands",
    "compiled",
    "checksum",
    "max_abs_err",
]
