"""Plain reference of one Mamba selective-scan chunk (step by step, float32),
and the operands the program makes for a task from its seed (the same
``jax.random`` calls on the same keys).

    h_t = exp(dt_t a) h_{t-1} + (dt_t x_t) b_t,    y_t = sum_n h_t c_t

Outputs y (B, chunk, di) and the last state h (B, di, N), both float32.
"""
import jax
import jax.numpy as jnp


def operands(shape: dict, dtype: str, seed) -> tuple:
    kx, kdt, kb, kc, ka = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, ck, di, N = shape["B"], shape["chunk"], shape["di"], shape["N"]
    x = jax.random.normal(kx, (B, ck, di), jnp.dtype(dtype))
    dt = jax.random.uniform(kdt, (B, ck, di), jnp.float32, 0.001, 0.1)
    b = jax.random.normal(kb, (B, ck, N), jnp.float32)
    c = jax.random.normal(kc, (B, ck, N), jnp.float32)
    a = -jax.random.uniform(ka, (di, N), jnp.float32, 0.5, 2.0)
    h0 = jnp.zeros((B, di, N), jnp.float32)
    return x, dt, b, c, a, h0


@jax.jit
def _scan(x, dt, b, c, a, h0):
    def step(h, t):
        dt_t = dt[:, t, :].astype(jnp.float32)
        x_t = x[:, t, :].astype(jnp.float32)
        b_t = b[:, t, :].astype(jnp.float32)
        c_t = c[:, t, :].astype(jnp.float32)
        da = jnp.exp(dt_t[..., None] * a.astype(jnp.float32)[None])
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(x.shape[1]))
    return ys.swapaxes(0, 1), h


def outputs(shape: dict, args: tuple):
    with jax.default_matmul_precision("highest"):
        y, h = _scan(*args)
    yield y
    yield h
