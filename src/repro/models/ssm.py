"""Mamba1 selective-SSM stack (falcon-mamba-7b) — attention-free.

Training uses a chunked linear-recurrence: an outer ``lax.scan`` over sequence
chunks carries the (B, d_inner, N) state; within a chunk the diagonal
recurrence h_t = a_t * h_{t-1} + b_t is evaluated with
``lax.associative_scan``.  The (B, chunk, d_inner, N) discretized tensors only
ever exist per-chunk (never for the full sequence).  Decode is a single-token
recurrence with O(1) state — this is why falcon-mamba runs the long_500k cell.

The TPU hot-spot (per-chunk scan) has a Pallas kernel in
kernels/selective_scan.py; this module is the XLA lowering / oracle path.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import causal_conv1d, conv1d_step, embed_tokens, rms_norm, scan_layers, scan_layers_carry
from repro.models.spec import ParamSpec, dense, stacked
from repro.models.transformer import _head
from repro.parallel.sharding import shard_x


def block_specs(cfg: ArchConfig, dt: str) -> dict:
    D, di, N, R, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    return {
        "ln": ParamSpec((D,), ("norm",), dt, "zeros"),
        "w_in_x": dense((D, di), ("embed", "ssm_inner"), dt),
        "w_in_z": dense((D, di), ("embed", "ssm_inner"), dt),
        "conv_w": dense((di, K), ("ssm_inner", "conv"), dt, scale=0.5),
        "conv_b": ParamSpec((di,), ("ssm_inner",), dt, "zeros"),
        "w_x_dt": dense((di, R), ("ssm_inner", "dt_rank"), dt),
        "w_x_b": dense((di, N), ("ssm_inner", "ssm_state"), dt),
        "w_x_c": dense((di, N), ("ssm_inner", "ssm_state"), dt),
        "w_dt": dense((R, di), ("dt_rank", "ssm_inner"), dt),
        "b_dt": ParamSpec((di,), ("ssm_inner",), "float32", "ssm_dt_bias"),
        "a_log": ParamSpec((di, N), ("ssm_inner", "ssm_state"), "float32", "ssm_a_log"),
        "d_skip": ParamSpec((di,), ("ssm_inner",), "float32", "ones"),
        "w_out": dense((di, D), ("ssm_inner", "embed"), dt),
    }


def specs(cfg: ArchConfig) -> dict:
    dt = cfg.param_dtype
    tree: dict[str, Any] = {
        "embed": dense((cfg.vocab_size, cfg.d_model), ("vocab", "embed_table"), dt, scale=0.02),
        "blocks": stacked(cfg.n_layers, block_specs(cfg, dt)),
        "ln_f": ParamSpec((cfg.d_model,), ("norm",), dt, "zeros"),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = dense((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt)
    return tree


# ---------------------------------------------------------------------------
# Selective scan (chunked)
# ---------------------------------------------------------------------------


def _ssm_inputs(cfg: ArchConfig, p: dict, xb: jax.Array):
    """xb (B, L, di) post-conv -> dt (B,L,di) f32, Bm/Cm (B,L,N) f32."""
    dt_low = jnp.einsum("bld,dr->blr", xb, p["w_x_dt"])
    dt = jnp.einsum("blr,rd->bld", dt_low, p["w_dt"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["b_dt"].astype(jnp.float32))
    bm = jnp.einsum("bld,dn->bln", xb, p["w_x_b"]).astype(jnp.float32)
    cm = jnp.einsum("bld,dn->bln", xb, p["w_x_c"]).astype(jnp.float32)
    return dt, bm, cm


def selective_scan_chunked(cfg: ArchConfig, p, xb, dt, bm, cm, h0=None, use_pallas: bool = False):
    """Evaluate the selective scan over the full sequence in chunks.

    xb (B, L, di); dt (B, L, di); bm, cm (B, L, N).
    Returns (y (B, L, di), h_last (B, di, N) float32).
    """
    B, L, di = xb.shape
    N = bm.shape[-1]
    ck = min(cfg.ssm_chunk, L)
    while L % ck:  # fall back to the largest divisor of L (odd test lengths)
        ck -= 1
    n_chunks = L // ck
    a = -jnp.exp(p["a_log"].astype(jnp.float32))  # (di, N)

    def to_chunks(t):
        return t.reshape(B, n_chunks, ck, *t.shape[2:]).swapaxes(0, 1)

    xs = (to_chunks(xb), to_chunks(dt), to_chunks(bm), to_chunks(cm))
    h_init = jnp.zeros((B, di, N), jnp.float32) if h0 is None else h0

    if use_pallas:
        from repro.kernels import ops as kops

        shape = {"B": B, "chunk": ck, "di": di, "N": N}

        def chunk_body(h, chunk):
            y, h_new = kops.call("selective_scan", shape, *chunk, a, h)
            return h_new, y
    elif cfg.ssm_scan == "seq":

        def chunk_body(h, chunk):
            # §Perf strip-mined path: walk the chunk sequentially (unroll=16)
            # so the (B, ck, di, N) discretized tensors NEVER materialize in
            # HBM - only the (B, di, N) state is carried.  ~10x less traffic
            # than the associative-scan tree at the cost of serial latency
            # the VPU hides (the recurrence is elementwise).
            xc, dtc, bc, cc = chunk

            def step(h, xs):
                x_t, dt_t, b_t, c_t = xs  # (B, di), (B, di), (B, N), (B, N)
                da = jnp.exp(dt_t[..., None] * a)
                h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
                y_t = jnp.einsum("bdn,bn->bd", h, c_t)
                return h, y_t

            from repro.models.layers import scan_unroll

            ts = jax.tree.map(lambda t: t.swapaxes(0, 1), (xc, dtc, bc, cc))
            h, ys = jax.lax.scan(step, h, ts, unroll=True if scan_unroll() else 16)
            return h, ys.swapaxes(0, 1)
    else:

        def chunk_body(h, chunk):
            xc, dtc, bc, cc = chunk  # (B, ck, di), (B, ck, di), (B, ck, N) x2
            da = jnp.exp(dtc[..., None] * a)  # (B, ck, di, N)
            db = (dtc * xc.astype(jnp.float32))[..., None] * bc[:, :, None, :]

            def combine(u, v):
                a1, b1 = u
                a2, b2 = v
                return a2 * a1, a2 * b1 + b2

            cum_a, cum_b = jax.lax.associative_scan(combine, (da, db), axis=1)
            hs = cum_b + cum_a * h[:, None]  # (B, ck, di, N)
            y = jnp.einsum("bldn,bln->bld", hs, cc)
            return hs[:, -1], y

    from repro.models.layers import scan_unroll

    h_last, ys = jax.lax.scan(chunk_body, h_init, xs, unroll=scan_unroll())
    y = ys.swapaxes(0, 1).reshape(B, L, di)
    return y, h_last


def mamba_block(cfg: ArchConfig, x, p, *, use_pallas: bool = False):
    """One Mamba block (full-sequence). x (B, L, D)."""
    h_in = rms_norm(x, p["ln"], cfg.norm_eps)
    xb = jnp.einsum("bld,de->ble", h_in, p["w_in_x"])
    z = jnp.einsum("bld,de->ble", h_in, p["w_in_z"])
    xb = shard_x(xb, "batch", "seq", "ssm_inner_act")
    xb = jax.nn.silu(causal_conv1d(xb, p["conv_w"], p["conv_b"]))
    dt, bm, cm = _ssm_inputs(cfg, p, xb)
    y, _ = selective_scan_chunked(cfg, p, xb, dt, bm, cm, use_pallas=use_pallas)
    y = (y + p["d_skip"].astype(jnp.float32) * xb.astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("ble,ed->bld", y, p["w_out"])
    return shard_x(x + out, "batch", "seq", "embed_act")


def backbone(cfg: ArchConfig, params, tokens, extras=None):
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    return scan_layers(
        lambda c, p: mamba_block(cfg, c, p), x, params["blocks"], remat=cfg.remat
    )


def forward(cfg: ArchConfig, params, tokens, extras=None):
    return _head(cfg, params, backbone(cfg, params, tokens, extras))


# ---------------------------------------------------------------------------
# Decode (recurrent state; O(1) in sequence length)
# ---------------------------------------------------------------------------


def cache_specs(cfg: ArchConfig, batch: int, cache_len: int) -> dict:
    """Recurrent state: SSM state + conv window per layer.  cache_len unused."""
    di, N, K, L = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv, cfg.n_layers
    return {
        "layers": {
            "h": ParamSpec((L, batch, di, N), ("layers", "cache_batch", "ssm_inner_act", None), "float32", "zeros"),
            "conv": ParamSpec(
                (L, batch, K - 1, di), ("layers", "cache_batch", None, "ssm_inner_act"), cfg.compute_dtype, "zeros"
            ),
        }
    }


def mamba_decode_block(cfg: ArchConfig, x, p, layer_cache):
    """x (B, 1, D) one token."""
    h_in = rms_norm(x[:, 0], p["ln"], cfg.norm_eps)  # (B, D)
    xb = h_in @ p["w_in_x"]
    z = h_in @ p["w_in_z"]
    xb, conv_state = conv1d_step(xb, layer_cache["conv"], p["conv_w"], p["conv_b"])
    xb = jax.nn.silu(xb)
    dt = jax.nn.softplus(
        ((xb @ p["w_x_dt"]) @ p["w_dt"]).astype(jnp.float32) + p["b_dt"].astype(jnp.float32)
    )  # (B, di)
    bm = (xb @ p["w_x_b"]).astype(jnp.float32)  # (B, N)
    cm = (xb @ p["w_x_c"]).astype(jnp.float32)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))  # (di, N)
    da = jnp.exp(dt[..., None] * a)  # (B, di, N)
    db = (dt * xb.astype(jnp.float32))[..., None] * bm[:, None, :]
    h = da * layer_cache["h"] + db  # (B, di, N)
    y = jnp.einsum("bdn,bn->bd", h, cm)
    y = y + p["d_skip"].astype(jnp.float32) * xb.astype(jnp.float32)
    y = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["w_out"]
    return x + y[:, None, :], {"h": h, "conv": conv_state}


def prefill(cfg: ArchConfig, params, tokens, extras=None, cache_len=None):
    """Full forward, returning the recurrent state after the last token."""
    B, L = tokens.shape
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)

    def body(c, p):
        h_in = rms_norm(c, p["ln"], cfg.norm_eps)
        xb_pre = jnp.einsum("bld,de->ble", h_in, p["w_in_x"])
        z = jnp.einsum("bld,de->ble", h_in, p["w_in_z"])
        xb = jax.nn.silu(causal_conv1d(xb_pre, p["conv_w"], p["conv_b"]))
        dt, bm, cm = _ssm_inputs(cfg, p, xb)
        y, h_last = selective_scan_chunked(cfg, p, xb, dt, bm, cm)
        y = (y + p["d_skip"].astype(jnp.float32) * xb.astype(jnp.float32)).astype(c.dtype)
        y = y * jax.nn.silu(z)
        out = jnp.einsum("ble,ed->bld", y, p["w_out"])
        conv_tail = xb_pre[:, -(cfg.ssm_conv - 1):, :]  # last K-1 *pre-conv* inputs
        return c + out, (h_last, conv_tail)

    x, (h, conv) = scan_layers_carry(body, x, params["blocks"], remat=cfg.remat)
    logits = _head(cfg, params, x[:, -1:, :])
    return logits, {"layers": {"h": h, "conv": conv}}


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, extras=None):
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    x, new_cache = scan_layers_carry(
        lambda c, scanned: mamba_decode_block(cfg, c, scanned[0], scanned[1]),
        x,
        (params["blocks"], cache["layers"]),
        remat="none",
    )
    return _head(cfg, params, x), {"layers": new_cache}
