"""Pure-jnp oracles for every Pallas kernel (exact, unblocked math)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(
    q: jax.Array,  # (B, H, Lq, hd)
    k: jax.Array,  # (B, KV, Lk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    b, h, lq, hd = q.shape
    n_kv, lk = k.shape[1], k.shape[2]
    rep = h // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (hd**0.5)
    q_pos = jnp.arange(lq)[:, None]
    k_pos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def selective_scan_chunk_ref(x, dt, b, c, a, h0):
    """Sequential reference of the SSM chunk recurrence (fp32)."""
    B, chunk, di = x.shape

    def step(h, t):
        dt_t = dt[:, t, :].astype(jnp.float32)  # (B, di)
        x_t = x[:, t, :].astype(jnp.float32)
        b_t = b[:, t, :].astype(jnp.float32)  # (B, N)
        c_t = c[:, t, :].astype(jnp.float32)
        da = jnp.exp(dt_t[..., None] * a[None])  # (B, di, N)
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y_t = jnp.sum(h * c_t[:, None, :], axis=-1)  # (B, di)
        return h, y_t

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(chunk))
    return ys.swapaxes(0, 1), h  # (B, chunk, di), (B, di, N)


def rglru_ref(log_a, gx, h0=None):
    B, L, dr = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, dr), jnp.float32)

    def step(h, t):
        h = jnp.exp(log_a[:, t, :].astype(jnp.float32)) * h + gx[:, t, :].astype(jnp.float32)
        return h, h

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(L))
    return ys.swapaxes(0, 1), h


def moe_gmm_ref(x, w):
    return jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    ).astype(x.dtype)


def mla_ref(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, *, scale: float):
    """Standard (non-absorbed) multi-head latent attention in float32 at the
    highest matmul precision, one head at a time.

    q_nope (B, H, Lq, dn), q_rope (B, H, Lq, dr); c_kv (B, Lk, dc), k_rope
    (B, Lk, dr); w_uk (dc, H, dn), w_uv (dc, H, dv).  Head h's keys
    ``[c_kv W_UK_h, k_rope]`` and values ``c_kv W_UV_h`` are decompressed,
    then exact softmax attention with ``scale``, causal, the queries at key
    positions ``Lk - Lq ... Lk - 1`` -> (B, H, Lq, dv) in q's dtype."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    lq, lk = q_nope.shape[2], c_kv.shape[1]
    c, k_rope = f32(c_kv), f32(k_rope)
    mask = (lk - lq + jnp.arange(lq))[:, None] >= jnp.arange(lk)[None, :]

    def head(operands):
        qn, qr, wk, wv = (f32(a) for a in operands)  # (B, Lq, dn), (B, Lq, dr), (dc, dn), (dc, dv)
        k = jnp.concatenate([c @ wk, k_rope], axis=-1)  # (B, Lk, dn + dr)
        s = jnp.einsum("bqd,bkd->bqk", jnp.concatenate([qn, qr], axis=-1), k) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, c @ wv)

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_rope, 1, 0),
                                 jnp.moveaxis(w_uk, 1, 0), jnp.moveaxis(w_uv, 1, 0)))
    return jnp.moveaxis(out, 0, 1).astype(q_nope.dtype)
