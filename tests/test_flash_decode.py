"""Distributed flash-decode == single-device decode (multi-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.models.model import Model
    from repro.parallel import sharding as sh
    from repro.train import step as step_lib

    for arch_name, kv in (("llama3-8b", 1), ("recurrentgemma-2b", 1)):
        cfg = get_arch(arch_name).reduced().replace(n_kv_heads=kv)
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        B, L = 4, 16
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L + 1)), jnp.int32)
        _, cache = model.prefill(params, {"tokens": toks[:, :L]}, cache_len=L + 1)
        lg_ref, _ = model.decode_step(params, cache, toks[:, L:L+1], jnp.full((B,), L, jnp.int32))
        ref = np.asarray(lg_ref[:, 0])

        strat = dataclasses.replace(sh.STRATEGIES["tp"], name="tp_fd", flash_decode=True)
        fn = step_lib.make_decode_step(model, strat, mesh)
        shardings = step_lib.make_shardings(
            model, strat, mesh,
            {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32)},
            model.cache_specs(B, L + 1))
        named = lambda t: jax.tree.map(lambda ps: NamedSharding(mesh, ps), t)
        jfn = jax.jit(fn, in_shardings=(named(shardings.params), named(shardings.cache), named(shardings.batch)))
        cache_sh = jax.tree.map(lambda x, s: jax.device_put(x, s), cache, named(shardings.cache))
        lg, _ = jfn(params, cache_sh, {"tokens": toks[:, L:L+1], "pos": jnp.full((B,), L, jnp.int32)})
        err = np.max(np.abs(ref - np.asarray(lg[:, 0]))) / (np.max(np.abs(ref)) + 1e-9)
        assert err < 2e-3, (arch_name, err)
        print("FLASH_DECODE_OK", arch_name, float(err))
""")


@pytest.mark.slow
def test_flash_decode_matches_reference_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.stdout.count("FLASH_DECODE_OK") == 2, out.stdout + out.stderr
