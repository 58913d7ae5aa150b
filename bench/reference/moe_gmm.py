"""Plain reference of the expert matmul y[e] = x[e] @ w[e], and the
operands the program makes for a task from its seed (the same
``jax.random`` calls on the same keys).  float32 at the highest matmul
precision, cast to the kernel's output dtype."""
import jax
import jax.numpy as jnp


def operands(shape: dict, dtype: str, seed) -> tuple:
    kx, kw = jax.random.split(jax.random.PRNGKey(seed), 2)
    E, C, D, F = shape["E"], shape["C"], shape["D"], shape["F"]
    x = jax.random.normal(kx, (E, C, D), jnp.dtype(dtype))
    w = (jax.random.normal(kw, (E, D, F), jnp.float32) * (1.0 / D**0.5)).astype(jnp.dtype(dtype))
    return x, w


@jax.jit
def _gmm(x, w):
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)).astype(x.dtype)


def outputs(shape: dict, args: tuple):
    with jax.default_matmul_precision("highest"):
        out = _gmm(*args)
    yield out
