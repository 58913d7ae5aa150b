"""Int8 error-feedback gradient compression (multi-device via subprocess)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

import jax
import jax.numpy as jnp

from repro.optim import compression as C


def test_quantize_dequantize_bounded_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(33, 17)), jnp.float32)
    q, scale = C._quant(C._to_blocks(x, 1))
    deq = C._dequant(q, scale).reshape(-1)[: x.size].reshape(x.shape)
    # int8 block quantization: error < scale/2 per element
    per_block_bound = np.repeat(np.asarray(scale), C.BLOCK)[: x.size].reshape(x.shape)
    assert np.all(np.abs(np.asarray(deq - x)) <= per_block_bound * 0.51 + 1e-7)


def test_compression_state_shapes():
    st = C.compression_state(jax.ShapeDtypeStruct((37, 53), jnp.float32), 8)
    assert st["worker_err"].shape == (37, 53)
    assert st["owner_err"].shape[1] == C.BLOCK


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.optim import compression as C

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    shape = (37, 53)
    xs = rng.normal(size=(8,) + shape).astype(np.float32)
    true_mean = xs.mean(0)
    state = C.compression_state(jax.ShapeDtypeStruct(shape, jnp.float32), 8)

    def f(x_local, st):
        return C.compressed_mean(x_local[0], st, "data")

    fm = jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"), P()), out_specs=P(), check_vma=False
    )
    got, st = jax.jit(fm)(jnp.asarray(xs), state)
    one_shot = float(np.max(np.abs(np.asarray(got) - true_mean)) / np.max(np.abs(true_mean)))
    assert one_shot < 0.05, one_shot

    accum = np.zeros(shape); errs = []
    for i in range(20):
        got, st = jax.jit(fm)(jnp.asarray(xs), st)
        accum += np.asarray(got)
        errs.append(np.max(np.abs(accum / (i + 1) - true_mean)))
    assert errs[-1] < errs[0] / 5, (errs[0], errs[-1])  # EF kills the bias
    print("COMPRESSION_OK", one_shot, errs[-1])
""")


def test_compressed_allreduce_with_error_feedback_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True, timeout=300
    )
    assert "COMPRESSION_OK" in out.stdout, out.stdout + out.stderr
