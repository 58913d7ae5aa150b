"""GQA attention: blockwise (online-softmax) XLA path + decode-step path.

The blockwise formulation never materializes the full (Lq, Lk) score matrix:
it scans over KV chunks carrying the running (max, denom, acc) triple.  This
is the same algorithm the Pallas flash kernel (kernels/flash_attention.py)
implements with explicit VMEM tiling on TPU; here it serves as the XLA
lowering used by the dry-run and as a memory-safe default on any backend.

Causal note: the scan visits every KV chunk for every query (masked), so HLO
FLOPs are ~2x the causal ideal; the TPU kernel skips fully-masked blocks.
This is accounted for in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard_x

_NEG = -1e30


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, L, KV, hd) -> (B, L, H, hd).  Under TP the repeat is local: each
    chip materializes only its own query heads' K/V copies (tiny)."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    k = jnp.repeat(k, n_heads // n_kv, axis=2)
    return shard_x(k, "batch", "seq", "heads_act", None)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Blockwise attention.  q (B,Lq,H,hd); k,v (B,Lk,KV,hd) -> (B,Lq,H,hd)."""
    b, lq, h, hd = q.shape
    lk = k.shape[1]
    ck = min(kv_chunk, lk)
    while lk % ck:  # fall back to the largest divisor of lk (odd test lengths)
        ck -= 1
    n_chunks = lk // ck

    k, v = repeat_kv(k, h), repeat_kv(v, h)  # per-head layout, head-sharded
    scale = 1.0 / (hd**0.5)
    q_pos = q_offset + jnp.arange(lq)

    kc = k.reshape(b, n_chunks, ck, h, hd).swapaxes(0, 1)  # (n, B, ck, H, hd)
    vc = v.reshape(b, n_chunks, ck, h, hd).swapaxes(0, 1)

    acc0 = jnp.zeros((b, lq, h, hd), jnp.float32)
    m0 = jnp.full((b, lq, h), _NEG, jnp.float32)
    l0 = jnp.zeros((b, lq, h), jnp.float32)

    def step(carry, xs):
        acc, m, l, idx = carry
        k_i, v_i = xs
        s = jnp.einsum("blhd,bchd->blhc", q, k_i, preferred_element_type=jnp.float32)
        s = s * scale  # (B, Lq, H, ck)
        k_pos = idx * ck + jnp.arange(ck)
        mask = jnp.ones((lq, ck), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask_b = mask[None, :, None, :]
        s = jnp.where(mask_b, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask_b  # zero out masked cols
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # §Perf: p cast to the value dtype for the PV matmul - halves the
        # score-chain HBM traffic; the accumulator stays fp32 (flash-standard)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "blhc,bchd->blhd", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new, idx + 1), None

    from repro.models.layers import scan_unroll

    (acc, m, l, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, 0), (kc, vc), unroll=scan_unroll()
    )
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)
    return shard_x(out, "batch", "seq", "heads_act", None)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    cache_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """One-token attention against a cache.

    q (B, 1, H, hd); k_cache/v_cache (B, Lc, KV, hd); pos (B,) current position.
    ``cache_positions`` (B, Lc): absolute position stored at each cache slot
    (ring buffers for windowed attention); defaults to arange for linear caches.

    When the active strategy enables flash_decode and the cache is
    sequence-sharded over "model", dispatches to the distributed flash-decode
    path (each shard attends to its local cache slice; partial softmax states
    combine with an LSE-rescaled psum - no cache all-gather).
    """
    from repro.parallel.sharding import flash_decode_enabled

    if flash_decode_enabled():
        return _decode_attention_distributed(
            q, k_cache, v_cache, pos, cache_positions=cache_positions, window=window
        )
    b, _, h, hd = q.shape
    lc = k_cache.shape[1]
    kr = repeat_kv(k_cache, h)  # (B, Lc, H, hd); local repeat per shard
    vr = repeat_kv(v_cache, h)
    scale = 1.0 / (hd**0.5)

    s = jnp.einsum("bhd,blhd->bhl", q[:, 0], kr, preferred_element_type=jnp.float32)
    s = s * scale  # (B, H, Lc)
    if cache_positions is None:
        cache_positions = jnp.broadcast_to(jnp.arange(lc)[None, :], (b, lc))
    valid = cache_positions <= pos[:, None]
    if window is not None:
        valid &= cache_positions > (pos[:, None] - window)
    valid &= cache_positions >= 0
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", p, vr, preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)


def _decode_attention_distributed(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    cache_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Distributed flash-decode (§Perf): the KV cache stays sequence-sharded
    over "model"; each shard computes partial (m, l, acc) over its slice and
    the full softmax is reconstructed with an LSE-rescaled psum.  Wire cost
    per layer: O(B*H*hd) instead of O(B*Lc*KV*hd) (the cache all-gather GSPMD
    otherwise inserts - measured 2.1 GB/layer for llama3-405b decode_32k)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import _CTX, dp_axes

    mesh = _CTX.mesh
    b, _, h, hd = q.shape
    lc = k_cache.shape[1]
    if cache_positions is None:
        cache_positions = jnp.broadcast_to(jnp.arange(lc)[None, :], (b, lc))
    dp = dp_axes(mesh.axis_names)
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)

    # shard_map needs even shards: pad the cache seq dim; padded slots carry
    # cache_position = -1 and are masked out by the validity test below
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    pad = (-lc) % n_model
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, padw)
        v_cache = jnp.pad(v_cache, padw)
        cache_positions = jnp.pad(cache_positions, ((0, 0), (0, pad)), constant_values=-1)

    def local(q, k, v, cpos, pos):
        # q (b', 1, H, hd) replicated over model; k/v (b', lc', KV, hd) local slice
        hh, dd = q.shape[2], q.shape[3]
        kr = jnp.repeat(k, hh // k.shape[2], axis=2)
        vr = jnp.repeat(v, hh // v.shape[2], axis=2)
        s = jnp.einsum("bhd,blhd->bhl", q[:, 0].astype(jnp.float32), kr.astype(jnp.float32))
        s = s / (dd**0.5)
        valid = cpos <= pos[:, None]
        if window is not None:
            valid &= cpos > (pos[:, None] - window)
        valid &= cpos >= 0
        s = jnp.where(valid[:, None, :], s, _NEG)
        m = jnp.max(s, axis=-1)  # (b', H)
        p = jnp.exp(s - m[..., None]) * valid[:, None, :]
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhl,blhd->bhd", p, vr.astype(jnp.float32))
        # combine partial softmax states across cache shards
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(acc * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g, 1e-37)[..., None]
        return out[:, None].astype(q.dtype)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),  # q: heads gathered (tiny)
            P(bspec, "model", None, None),  # cache slices stay put
            P(bspec, "model", None, None),
            P(bspec, "model"),
            P(bspec),
        ),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, cache_positions, pos)


# ---------------------------------------------------------------------------
# Projections (shared by all attention layers)
# ---------------------------------------------------------------------------


def qkv_proj(x: jax.Array, p: dict) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B,L,D) -> q (B,L,H,hd), k/v (B,L,KV,hd) using 3D weights."""
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"])
    q = shard_x(q, "batch", "seq", "heads_act", None)
    k = shard_x(k, "batch", "seq", "kv_heads_act", None)
    v = shard_x(v, "batch", "seq", "kv_heads_act", None)
    return q, k, v


def out_proj(attn_out: jax.Array, wo: jax.Array) -> jax.Array:
    return jnp.einsum("blhk,hkd->bld", attn_out, wo)
