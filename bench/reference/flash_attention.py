"""Plain reference of causal GQA attention, and the operands the program
makes for a task from its seed.

``operands`` repeats the program's operand recipe for this kernel (the same
``jax.random`` calls on the same keys), so the reference computes on the
task's own inputs without taking anything the program made.  ``outputs``
is exact, unblocked softmax attention in float32 at the highest matmul
precision, cast to the kernel's output dtype, one KV head's group of query
heads at a time so that an 8k context fits beside the operands.
"""
import functools

import jax
import jax.numpy as jnp


def operands(shape: dict, dtype: str, seed) -> tuple:
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, H, KV, L, hd = shape["B"], shape["H"], shape["KV"], shape["L"], shape["hd"]
    q = jax.random.normal(kq, (B, H, L, hd), jnp.dtype(dtype))
    k = jax.random.normal(kk, (B, KV, L, hd), jnp.dtype(dtype))
    v = jax.random.normal(kv, (B, KV, L, hd), jnp.dtype(dtype))
    return q, k, v


@functools.partial(jax.jit, static_argnums=(3, 4))
def _group(q, k, v, causal, window):
    # q: (B, rep, L, hd) query heads that share the one KV head k, v: (B, 1, L, hd)
    hd, L = q.shape[-1], q.shape[-2]
    s = jnp.einsum("bhqd,bkd->bhqk", q.astype(jnp.float32), k[:, 0].astype(jnp.float32))
    s = s / (hd**0.5)
    pos_q = jnp.arange(L)[:, None]
    pos_k = jnp.arange(L)[None, :]
    mask = jnp.ones((L, L), bool)
    if causal:
        mask &= pos_q >= pos_k
    if window is not None:
        mask &= (pos_q - pos_k) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkd->bhqd", p, v[:, 0].astype(jnp.float32))
    return out.astype(q.dtype)


def outputs(shape: dict, args: tuple):
    """Yields the output in blocks: the query heads of one KV head each."""
    q, k, v = args
    H, KV = shape["H"], shape["KV"]
    rep = H // KV
    causal, window = shape.get("causal", True), shape.get("window")
    for g in range(KV):
        with jax.default_matmul_precision("highest"):
            out = _group(q[:, g * rep:(g + 1) * rep], k[:, g:g + 1], v[:, g:g + 1], causal, window)
        yield out
