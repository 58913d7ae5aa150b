"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local (sliding
window) MQA attention in a repeating (rec, rec, attn) pattern.

The RG-LRU gate matrices are block-diagonal (Griffin §2.3) with one block per
tensor-parallel shard, so gate matmuls are fully local under TP.  The
recurrence is diagonal, evaluated with ``lax.associative_scan`` over the full
sequence at train time (O(L) memory in (B, L, d_rnn)) and as an O(1)-state
step at decode time — which is why recurrentgemma runs the long_500k cell
with a bounded (window-sized) attention cache.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.layers import (
    apply_rope,
    causal_conv1d,
    conv1d_step,
    embed_tokens,
    geglu,
    rms_norm,
    scan_layers,
    scan_layers_carry,
)
from repro.models.spec import ParamSpec, dense, stacked
from repro.models.transformer import _head, attn_specs, write_cache
from repro.parallel.sharding import shard_x

N_GATE_BLOCKS = 16  # block-diagonal gate blocks == model-axis size
LRU_C = 8.0


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _gate_blocks(cfg: ArchConfig) -> int:
    nb = N_GATE_BLOCKS
    while cfg.rnn_dim % nb:
        nb //= 2
    return max(nb, 1)


def rec_specs(cfg: ArchConfig, dt: str) -> dict:
    D, dr, K = cfg.d_model, cfg.rnn_dim, 4
    nb = _gate_blocks(cfg)
    bd = dr // nb
    return {
        "ln": ParamSpec((D,), ("norm",), dt, "zeros"),
        "w_x": dense((D, dr), ("embed", "rnn"), dt),
        "w_gate": dense((D, dr), ("embed", "rnn"), dt),
        "conv_w": dense((dr, K), ("rnn", "conv"), dt, scale=0.5),
        "conv_b": ParamSpec((dr,), ("rnn",), dt, "zeros"),
        "w_rec_gate": dense((nb, bd, bd), ("rnn", None, None), dt),
        "b_rec_gate": ParamSpec((dr,), ("rnn",), dt, "zeros"),
        "w_in_gate": dense((nb, bd, bd), ("rnn", None, None), dt),
        "b_in_gate": ParamSpec((dr,), ("rnn",), dt, "zeros"),
        "lam": ParamSpec((dr,), ("rnn",), "float32", "rglru_lambda"),
        "w_out": dense((dr, D), ("rnn", "embed"), dt),
        "ln_mlp": ParamSpec((D,), ("norm",), dt, "zeros"),
        "mlp": {
            "w_gate": dense((D, cfg.d_ff), ("embed", "mlp"), dt),
            "w_up": dense((D, cfg.d_ff), ("embed", "mlp"), dt),
            "w_down": dense((cfg.d_ff, D), ("mlp", "embed"), dt),
        },
    }


def attn_block_specs(cfg: ArchConfig, dt: str) -> dict:
    return {
        "ln": ParamSpec((cfg.d_model,), ("norm",), dt, "zeros"),
        "attn": attn_specs(cfg, dt),
        "ln_mlp": ParamSpec((cfg.d_model,), ("norm",), dt, "zeros"),
        "mlp": {
            "w_gate": dense((cfg.d_model, cfg.d_ff), ("embed", "mlp"), dt),
            "w_up": dense((cfg.d_model, cfg.d_ff), ("embed", "mlp"), dt),
            "w_down": dense((cfg.d_ff, cfg.d_model), ("mlp", "embed"), dt),
        },
    }


def _layout(cfg: ArchConfig) -> tuple[int, int]:
    """(n_superblocks, n_tail_rec_layers)."""
    p = len(cfg.block_pattern or ("rec", "rec", "attn"))
    return cfg.n_layers // p, cfg.n_layers % p


def specs(cfg: ArchConfig) -> dict:
    dt = cfg.param_dtype
    n_super, n_tail = _layout(cfg)
    tree: dict[str, Any] = {
        "embed": dense((cfg.vocab_size, cfg.d_model), ("vocab", "embed_table"), dt, scale=0.02),
        "superblocks": stacked(
            n_super,
            {
                "rec1": rec_specs(cfg, dt),
                "rec2": rec_specs(cfg, dt),
                "attn": attn_block_specs(cfg, dt),
            },
        ),
        "ln_f": ParamSpec((cfg.d_model,), ("norm",), dt, "zeros"),
        "lm_head": dense((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt),
    }
    if n_tail:
        tree["tail"] = stacked(n_tail, rec_specs(cfg, dt))
    return tree


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


def _block_diag(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """u (..., dr) @ block-diagonal w (nb, bd, bd) + b."""
    nb, bd, _ = w.shape
    ub = u.reshape(u.shape[:-1] + (nb, bd))
    out = jnp.einsum("...kd,kde->...ke", ub, w)
    return out.reshape(u.shape) + b


def _lru_gates(p: dict, u: jax.Array):
    """Returns (log_a (..., dr) f32, gated_input (..., dr) f32)."""
    r = jax.nn.sigmoid(_block_diag(u, p["w_rec_gate"], p["b_rec_gate"]).astype(jnp.float32))
    i = jax.nn.sigmoid(_block_diag(u, p["w_in_gate"], p["b_in_gate"]).astype(jnp.float32))
    log_a = -LRU_C * r * jax.nn.softplus(p["lam"].astype(jnp.float32))
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))  # sqrt(1 - a^2), stable
    return log_a, beta * i * u.astype(jnp.float32)


def rglru_seq(p: dict, u: jax.Array, h0=None, use_pallas: bool = False):
    """RG-LRU over a full sequence.  u (B, L, dr) -> (y, h_last (B, dr) f32)."""
    log_a, gx = _lru_gates(p, u)
    if use_pallas:
        from repro.kernels import ops as kops

        B, L, dr = log_a.shape
        y, h_last = kops.call("rglru_scan", {"B": B, "L": L, "dr": dr}, log_a, gx, h0)
        return y.astype(u.dtype), h_last
    a = jnp.exp(log_a)
    if h0 is not None:
        gx = gx.at[:, 0, :].add(a[:, 0, :] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gx), axis=1)
    return h.astype(u.dtype), h[:, -1]


def rglru_step(p: dict, u_t: jax.Array, h: jax.Array):
    """One decode step.  u_t (B, dr); h (B, dr) f32."""
    log_a, gx = _lru_gates(p, u_t)
    h_new = jnp.exp(log_a) * h + gx
    return h_new.astype(u_t.dtype), h_new


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def rec_block(cfg: ArchConfig, x, p, h0=None):
    """Full-seq recurrent block.  Returns (x, (h_last, conv_tail))."""
    h_in = rms_norm(x, p["ln"], cfg.norm_eps)
    u_pre = jnp.einsum("bld,de->ble", h_in, p["w_x"])
    g = jax.nn.gelu(jnp.einsum("bld,de->ble", h_in, p["w_gate"]))
    u_pre = shard_x(u_pre, "batch", "seq", "rnn_act")
    u = causal_conv1d(u_pre, p["conv_w"], p["conv_b"])
    y, h_last = rglru_seq(p, u, h0)
    out = jnp.einsum("ble,ed->bld", y * g, p["w_out"])
    x = x + out
    h2 = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    x = x + geglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    conv_tail = u_pre[:, -3:, :]
    return shard_x(x, "batch", "seq", "embed_act"), (h_last, conv_tail)


def attn_block(cfg: ArchConfig, x, p, pos):
    """Local-window MQA block.  Returns (x, (k, v))."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = attn.qkv_proj(h, p["attn"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    a = attn.attention(q, k, v, causal=True, window=cfg.local_window)
    x = x + attn.out_proj(a, p["attn"]["wo"])
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    x = x + geglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return shard_x(x, "batch", "seq", "embed_act"), (k, v)


# ---------------------------------------------------------------------------
# Model passes
# ---------------------------------------------------------------------------


def backbone(cfg: ArchConfig, params, tokens, extras=None):
    B, L = tokens.shape
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = jnp.arange(L)[None, :]

    def super_body(c, p):
        c, _ = rec_block(cfg, c, p["rec1"])
        c, _ = rec_block(cfg, c, p["rec2"])
        c, _ = attn_block(cfg, c, p["attn"], pos)
        return c

    x = scan_layers(super_body, x, params["superblocks"], remat=cfg.remat)
    if "tail" in params:
        x = scan_layers(
            lambda c, p: rec_block(cfg, c, p)[0], x, params["tail"], remat=cfg.remat
        )
    return x


def forward(cfg: ArchConfig, params, tokens, extras=None):
    return _head(cfg, params, backbone(cfg, params, tokens, extras))


def cache_specs(cfg: ArchConfig, batch: int, cache_len: int) -> dict:
    """LRU states + conv windows + ring-buffer attention caches."""
    n_super, n_tail = _layout(cfg)
    W = min(cfg.local_window, cache_len)
    dr, KV, hd = cfg.rnn_dim, cfg.n_kv_heads, cfg.hd
    ct = cfg.compute_dtype
    sb = {
        "rec1_h": ParamSpec((n_super, batch, dr), ("layers", "cache_batch", "rnn_act"), "float32", "zeros"),
        "rec1_conv": ParamSpec((n_super, batch, 3, dr), ("layers", "cache_batch", None, "rnn_act"), ct, "zeros"),
        "rec2_h": ParamSpec((n_super, batch, dr), ("layers", "cache_batch", "rnn_act"), "float32", "zeros"),
        "rec2_conv": ParamSpec((n_super, batch, 3, dr), ("layers", "cache_batch", None, "rnn_act"), ct, "zeros"),
        "k": ParamSpec(
            (n_super, batch, W, KV, hd), ("layers", "cache_batch", "cache_seq", "kv_heads_act", None), ct, "zeros"
        ),
        "v": ParamSpec(
            (n_super, batch, W, KV, hd), ("layers", "cache_batch", "cache_seq", "kv_heads_act", None), ct, "zeros"
        ),
    }
    tree = {"superblocks": sb}
    if n_tail:
        tree["tail"] = {
            "h": ParamSpec((n_tail, batch, dr), ("layers", "cache_batch", "rnn_act"), "float32", "zeros"),
            "conv": ParamSpec((n_tail, batch, 3, dr), ("layers", "cache_batch", None, "rnn_act"), ct, "zeros"),
        }
    return tree


def ring_positions(pos: jax.Array, window: int) -> jax.Array:
    """Absolute position stored at each ring-buffer slot given current pos (B,).

    Slot j holds the largest p <= pos with p % W == j (negative => empty).
    """
    j = jnp.arange(window)[None, :]
    p = pos[:, None] - ((pos[:, None] - j) % window)
    return p


def _rec_step(cfg, x, p, h, conv_state):
    """x (B, 1, D) decode step of a recurrent block."""
    h_in = rms_norm(x[:, 0], p["ln"], cfg.norm_eps)
    u_pre = h_in @ p["w_x"]
    g = jax.nn.gelu(h_in @ p["w_gate"])
    u, conv_state = conv1d_step(u_pre, conv_state, p["conv_w"], p["conv_b"])
    y, h_new = rglru_step(p, u, h)
    out = (y * g) @ p["w_out"]
    x = x + out[:, None, :]
    h2 = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    x = x + geglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x, h_new, conv_state


def _attn_step(cfg, x, p, k_cache, v_cache, pos):
    W = k_cache.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k_t, v_t = attn.qkv_proj(h, p["attn"])
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k_t = apply_rope(k_t, pos[:, None], cfg.rope_theta)
    ck, cv = write_cache(k_cache, v_cache, k_t, v_t, pos % W)
    cpos = ring_positions(pos, W)
    a = attn.decode_attention(q, ck, cv, pos, cache_positions=cpos, window=cfg.local_window)
    x = x + attn.out_proj(a, p["attn"]["wo"])
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    x = x + geglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x, ck, cv


def prefill(cfg: ArchConfig, params, tokens, extras=None, cache_len=None):
    B, L = tokens.shape
    cache_len = cache_len or L
    W = min(cfg.local_window, cache_len)
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = jnp.arange(L)[None, :]

    def ring_from_seq(k):  # (B, L, KV, hd) -> ring (B, W, KV, hd)
        if L >= W:
            tail = k[:, -W:]
            # place token t at slot t % W
            slots = (jnp.arange(L - W, L)) % W
            ring = jnp.zeros((B, W) + k.shape[2:], k.dtype)
            return ring.at[:, slots].set(tail)
        pad = ((0, 0), (0, W - L), (0, 0), (0, 0))
        return jnp.pad(k, pad)

    def super_body(c, p):
        c, (h1, cv1) = rec_block(cfg, c, p["rec1"])
        c, (h2, cv2) = rec_block(cfg, c, p["rec2"])
        c, (k, v) = attn_block(cfg, c, p["attn"], pos)
        cache = {
            "rec1_h": h1, "rec1_conv": cv1,
            "rec2_h": h2, "rec2_conv": cv2,
            "k": ring_from_seq(k), "v": ring_from_seq(v),
        }
        return c, cache

    x, sb_cache = scan_layers_carry(super_body, x, params["superblocks"], remat=cfg.remat)
    cache = {"superblocks": sb_cache}
    if "tail" in params:
        def tail_body(c, p):
            c, (h, cv) = rec_block(cfg, c, p)
            return c, {"h": h, "conv": cv}

        x, tail_cache = scan_layers_carry(tail_body, x, params["tail"], remat=cfg.remat)
        cache["tail"] = tail_cache
    return _head(cfg, params, x[:, -1:, :]), cache


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, extras=None):
    x = embed_tokens(tokens, params["embed"], cfg.compute_dtype)

    def super_body(c, scanned):
        p, lc = scanned
        c, h1, cv1 = _rec_step(cfg, c, p["rec1"], lc["rec1_h"], lc["rec1_conv"])
        c, h2, cv2 = _rec_step(cfg, c, p["rec2"], lc["rec2_h"], lc["rec2_conv"])
        c, ck, cvv = _attn_step(cfg, c, p["attn"], lc["k"], lc["v"], pos)
        return c, {
            "rec1_h": h1, "rec1_conv": cv1,
            "rec2_h": h2, "rec2_conv": cv2,
            "k": ck, "v": cvv,
        }

    x, sb_cache = scan_layers_carry(
        super_body, x, (params["superblocks"], cache["superblocks"]), remat="none"
    )
    new_cache = {"superblocks": sb_cache}
    if "tail" in params:
        def tail_body(c, scanned):
            p, lc = scanned
            c, h, cv = _rec_step(cfg, c, p, lc["h"], lc["conv"])
            return c, {"h": h, "conv": cv}

        x, tail_cache = scan_layers_carry(
            tail_body, x, (params["tail"], cache["tail"]), remat="none"
        )
        new_cache["tail"] = tail_cache
    return _head(cfg, params, x), new_cache
