"""Plain reference of one DeepSeek-V3 MLA decode step, and the operands the
program makes for a task from its seed.

The program absorbs the query through W_UK and reads the latent cache
itself; this reference does not.  It is the same standard, non-absorbed
definition as ``mla_prefill``'s (``attend``: K and V decompressed per head
in float32, exact softmax), with one query per head at the cache's last
position, so that it sees the whole cache.  So the absorption is itself
under test.  It runs one sequence and a block of heads at a time, so that
the decompressed cache fits beside the operands.
"""
import jax

from bench import spec

HEADS_PER_BLOCK = 32

_prefill = spec.plugin("reference", "mla_prefill")


def operands(shape: dict, dtype: str, seed) -> tuple:
    return _prefill.operands(shape, dtype, seed)


def outputs(shape: dict, args: tuple):
    """Yields the output one sequence and a block of heads at a time."""
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    g = min(HEADS_PER_BLOCK, shape["H"])
    for b in range(shape["B"]):
        for h in range(0, shape["H"], g):
            with jax.default_matmul_precision("highest"):
                out = _prefill.attend(
                    q_nope[b:b + 1, h:h + g, None], q_rope[b:b + 1, h:h + g, None],
                    c_kv[b:b + 1], k_rope[b:b + 1], w_uk[:, h:h + g], w_uv[:, h:h + g],
                    shape["scale"], shape["Lk"] - 1,
                )
            yield out[:, :, 0]
