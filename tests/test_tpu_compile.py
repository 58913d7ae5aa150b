"""The kernels of the main path compile with Mosaic for a TPU v5e.

Each kernel is compiled through the same function the kernel-task runtime
uses (``registry.compiled``) for one chip of a described ``v5e:2x2``
topology: at the widths of the published model that carries it and at the
default ``kind="kernel"`` payload shape.  Nothing runs, so this says
nothing about results or times; it catches what the TPU compiler refuses
(unaligned slices, VMEM overflow, layouts Mosaic cannot build) without a
chip.  The topology is described inside a fixture, never at import."""
from __future__ import annotations

import os

import jax
import pytest

from repro.kernels import registry as kreg

PUBLISHED = [
    (name, dtype)
    for name, (_, _, dtypes) in kreg.published_shapes().items()
    for dtype in dtypes
]


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(name: str, shape: dict, dtype: str, device):
    kdef = kreg.get_kernel(name)
    assert not kreg.interpret_default(device)
    return kreg.compiled(kdef, shape, dtype, kreg.block_config(kdef, shape, dtype), device)


@pytest.mark.parametrize("name,dtype", PUBLISHED)
def test_compiles_at_published_widths(name, dtype, chip, no_persistent_cache):
    _, shape, _ = kreg.published_shapes()[name]
    program = _compile(name, shape, dtype, chip)
    assert "tpu_custom_call" in program.as_text()


@pytest.mark.parametrize("name", sorted(kreg.KERNELS))
def test_compiles_at_payload_default_shape(name, chip, no_persistent_cache):
    program = _compile(name, dict(kreg.get_kernel(name).tiny_shape), "float32", chip)
    assert "tpu_custom_call" in program.as_text()
