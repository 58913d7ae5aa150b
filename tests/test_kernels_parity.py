"""Registry-driven Pallas parity (kernels/registry.py).

Every registered kernel must match its pure-jnp oracle at tight fp32
tolerance — across its ENTIRE block sweep space at the task-payload (tiny)
shape, at the CI-bench (smoke) shape under defaults, and across the
attention variants (causal / non-causal / windowed / GQA / MQA) the bench
rows don't sweep.  Interpret mode on CPU; the same calls lower to Mosaic on
a real TPU.

This is the test-side twin of the check_bench HARD allclose gate (1e-3):
the gate catches drift in CI artifacts, this suite pins the much tighter
tolerance the kernels actually achieve, so a config point that silently
degrades (a masked-out block, an off-by-one window) fails here first."""
from __future__ import annotations

import numpy as np
import pytest

from repro.kernels import registry as kreg

# fp32 interpret-mode kernels track the jnp oracle to ~1e-6; 2e-5 leaves
# headroom for accumulation-order differences without hiding real bugs
TOL = 2e-5

INTERPRET = kreg.interpret_default()


def _parity_err(name: str, shape: dict, config: dict, seed: int = 0) -> float:
    kdef = kreg.get_kernel(name)
    args = kdef.make_args(shape, "float32", seed)
    return kreg.max_abs_err(
        kdef.call(shape, args, config, INTERPRET), kdef.ref(shape, args)
    )


@pytest.mark.parametrize("name", sorted(kreg.KERNELS))
@pytest.mark.parametrize("tier", ["tiny", "smoke"])
def test_parity_at_payload_and_bench_shapes(name, tier):
    """Defaults config at the two shapes the system actually dispatches:
    tiny (the kind="kernel" payload default) and smoke (BENCH_smoke rows)."""
    kdef = kreg.get_kernel(name)
    shape = dict(kdef.tiny_shape if tier == "tiny" else kdef.smoke_shape)
    assert _parity_err(name, shape, kdef.defaults(shape, "float32")) <= TOL


@pytest.mark.parametrize("name", sorted(kreg.KERNELS))
def test_parity_across_entire_sweep_space(name):
    """Every config the autotuner could ever pick computes the same answer:
    the sweep space at the tiny shape is small enough to cover exhaustively
    (a pruned-away config is still a *legal* config)."""
    kdef = kreg.get_kernel(name)
    shape = dict(kdef.tiny_shape)
    space = kdef.space(shape)
    assert len(space) >= 2, "sweep space degenerate: the autotuner has no choice"
    for config in space:
        err = _parity_err(name, shape, config)
        assert err <= TOL, f"{name} diverges at {kreg.config_sig(config)}: {err:g}"


# ---------------------------------------------------------------------------
# attention variants: masking interacts with the block grid, so causal,
# windowed, and grouped-KV paths each get their own parity point
# ---------------------------------------------------------------------------

_VARIANTS = {
    "mha_causal": {"H": 4, "KV": 4, "causal": True, "window": None},
    "mha_full": {"H": 4, "KV": 4, "causal": False, "window": None},
    "gqa_causal": {"H": 4, "KV": 2, "causal": True, "window": None},
    "mqa_causal": {"H": 4, "KV": 1, "causal": True, "window": None},
    "windowed": {"H": 4, "KV": 4, "causal": True, "window": 32},
    "gqa_windowed": {"H": 4, "KV": 2, "causal": True, "window": 64},
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_flash_attention_variants(variant):
    shape = {"B": 1, "L": 128, "hd": 32, **_VARIANTS[variant]}
    kdef = kreg.get_kernel("flash_attention")
    for config in ({"block_q": 32, "block_k": 32}, {"block_q": 64, "block_k": 32}):
        err = _parity_err("flash_attention", shape, config)
        assert err <= TOL, f"{variant} @ {kreg.config_sig(config)}: {err:g}"


def test_make_args_is_seed_deterministic():
    """Same (shape, dtype, seed) => bit-identical operands on every host —
    the property the autotuner's byte-identical payload cache rests on."""
    for name, kdef in kreg.KERNELS.items():
        shape = dict(kdef.tiny_shape)
        a = kdef.make_args(shape, "float32", 3)
        b = kdef.make_args(shape, "float32", 3)
        c = kdef.make_args(shape, "float32", 4)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert any(
            not np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, c)
        ), f"{name}: seed does not reach the operands"
