"""Plain reference of DeepSeek-V3 latent attention (MLA) for a prefill chunk,
and the operands the program makes for a task from its seed.

``operands`` repeats the program's operand recipe for this kernel (the same
``jax.random`` calls on the same keys), so the reference computes on the
task's own inputs without taking anything the program made.  ``outputs``
is the standard, non-absorbed definition: each head's keys
``[c_kv W_UK_h, k_rope]`` and values ``c_kv W_UV_h`` decompressed in
float32, then exact softmax attention with the shape's scale, causal with
the queries at key positions ``Lk - Lq ... Lk - 1``, at the highest matmul
precision, cast to the payload's dtype.  It runs a block of heads at a
time, so that a 16k context's scores fit beside the operands.
"""
import functools

import jax
import jax.numpy as jnp

HEADS_PER_BLOCK = 8


def operands(shape: dict, dtype: str, seed) -> tuple:
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


@functools.partial(jax.jit, static_argnums=(6, 7))
def attend(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, scale, offset):
    """q_nope (B, G, Lq, dn), q_rope (B, G, Lq, dr) of G heads; c_kv (B, Lk,
    dc), k_rope (B, Lk, dr); w_uk (dc, G, dn), w_uv (dc, G, dv); the
    queries at key positions ``offset ...`` -> (B, G, Lq, dv)."""
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    lq, lk = q_nope.shape[2], c_kv.shape[1]
    k_nope = jnp.einsum("blc,chd->bhld", f(c_kv), f(w_uk))
    v = jnp.einsum("blc,chd->bhld", f(c_kv), f(w_uv))
    rope = jnp.broadcast_to(f(k_rope)[:, None], k_nope.shape[:3] + k_rope.shape[-1:])
    k = jnp.concatenate([k_nope, rope], axis=-1)
    q = jnp.concatenate([f(q_nope), f(q_rope)], axis=-1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = (offset + jnp.arange(lq))[:, None] >= jnp.arange(lk)[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    return out.astype(q_nope.dtype)


def blocks(shape: dict, args: tuple, offset: int):
    """Yields the output a block of heads at a time."""
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    g = min(HEADS_PER_BLOCK, shape["H"])
    for h in range(0, shape["H"], g):
        with jax.default_matmul_precision("highest"):
            yield attend(q_nope[:, h:h + g], q_rope[:, h:h + g], c_kv, k_rope[:, 0],
                         w_uk[:, h:h + g], w_uv[:, h:h + g], shape["scale"], offset)


def outputs(shape: dict, args: tuple):
    return blocks(shape, args, shape["Lk"] - shape["Lq"])
