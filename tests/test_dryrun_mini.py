"""Mini dry-run: lower+compile reduced configs on an 8-device host mesh in a
subprocess (the full 512-device sweep runs via launch/dryrun.py)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, get_shape, token_batch_spec
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.parallel.sharding import STRATEGIES
    from repro.train import step as step_lib

    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

    for arch_name in ("llama3-8b", "falcon-mamba-7b", "grok-1-314b"):
        arch = get_arch(arch_name).reduced().replace(
            d_model=128, d_ff=256, n_heads=8, head_dim=16, vocab_size=512)
        model = Model(arch)
        strategy = STRATEGIES["tp"]
        if arch.family == "moe":
            strategy = strategy.with_overrides(experts=None)
        named = lambda t: jax.tree.map(lambda ps: NamedSharding(mesh, ps), t)
        import jax.numpy as jnp
        batch_specs = {
            "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
        }
        sh = step_lib.make_shardings(model, strategy, mesh, batch_specs)
        fn = step_lib.make_train_step(model, strategy, mesh, adamw.AdamWConfig())
        params, opt = step_lib.abstract_train_state(model)
        metrics_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                  step_lib.metrics_struct(model))
        metrics_sh["grad_norm"] = NamedSharding(mesh, P())
        metrics_sh["lr"] = NamedSharding(mesh, P())
        jfn = jax.jit(fn,
            in_shardings=(named(sh.params), named(sh.opt), named(sh.batch)),
            out_shardings=(named(sh.params), named(sh.opt), metrics_sh),
            donate_argnums=(0, 1))
        compiled = jfn.lower(params, opt, batch_specs).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        assert cost["flops"] > 0
        print("MINI_DRYRUN_OK", arch_name, int(cost["flops"]))
""")


@pytest.mark.slow
def test_mini_dryrun_8dev_compiles():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.stdout.count("MINI_DRYRUN_OK") == 3, out.stdout + out.stderr
