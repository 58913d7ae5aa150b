"""``correct`` comes out true for a sound run and false for the control and
for each fault a cell can have, through the whole harness at tiny shapes
on the CPU (kernels interpreted).

The faults are planted underneath the timed path, in the program's own
functions: the reference in lower precision in the kernel's place (the
control), operands made from the wrong seed (an answer altered where it is
produced), half of each output left out, attention without its causal mask
or with the wrong softmax scale, a task that fails, and -- with
the providers on three virtual devices -- every task placed on the first
(the placement across chips left out).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import calibrate, oracle, spec
from bench.conftest import SEED, run_tiny

ROOT = Path(__file__).resolve().parent.parent


def failed_checks(out: dict) -> set:
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    out, rec = run_tiny("xplat1.mixed.steady")
    assert out["correct"], out["checks"]
    assert out["attempted"] == len(rec.tasks) == 8
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"task_p50_ms", "task_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert {"not_done", "retries", "wrong_device", "execs_off"} <= set(out["checks"])


def test_control_is_not_correct(monkeypatch):
    """The reference, every floating operand a precision lower, in the
    kernel's place: the gap of at least one payload passes its limit."""
    from repro.kernels import registry as kreg

    def control(kdef, shape, dtype, config, device):
        ref = spec.plugin("reference", kdef.name)
        return lambda *args: list(ref.outputs(shape, tuple(oracle._round_down(a) for a in args)))

    monkeypatch.setattr(kreg, "compiled", control)
    out, _ = run_tiny("xplat1.mixed.steady", rate=6.0)
    assert not out["correct"]
    assert any(k.startswith("gap.") for k in failed_checks(out)), out["checks"]


def test_control_fails_the_float32_scan(monkeypatch):
    """The float32 payload alone: bfloat16 operands are caught too."""
    from repro.kernels import registry as kreg

    def control(kdef, shape, dtype, config, device):
        ref = spec.plugin("reference", kdef.name)
        return lambda *args: list(ref.outputs(shape, tuple(oracle._round_down(a) for a in args)))

    monkeypatch.setattr(kreg, "compiled", control)
    out, _ = run_tiny("xplat1.scan.flood", rate=4.0)
    assert "gap.mamba_scan_f32" in failed_checks(out), out["checks"]


def test_wrong_operands_are_caught(monkeypatch):
    from repro.kernels import registry as kreg

    real = kreg.operands
    monkeypatch.setattr(kreg, "operands", lambda kdef, shape, dtype, seed, device: real(kdef, shape, dtype, seed + 1, device))
    out, _ = run_tiny("xplat1.mixed.steady")
    assert not out["correct"]
    assert any(k.startswith("gap.") for k in failed_checks(out)), out["checks"]


def test_half_of_each_output_left_out_is_caught(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.kernels import registry as kreg

    real = kreg.compiled

    def half(kdef, shape, dtype, config, device):
        program = real(kdef, shape, dtype, config, device)

        def call(*args):
            def cut(x):
                n = x.shape[-2] // 2
                return jnp.concatenate([x[..., :n, :], jnp.zeros_like(x[..., n:, :])], axis=-2)

            return jax.tree_util.tree_map(cut, program(*args))

        return call

    monkeypatch.setattr(kreg, "compiled", half)
    out, _ = run_tiny("xplat1.mixed.steady")
    assert not out["correct"]
    assert any(k.startswith("gap.") for k in failed_checks(out)), out["checks"]


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS["flash_attention"]))
def test_attention_faults_are_caught(monkeypatch, fault):
    """The attention reference with a fault planted, in the kernel's place:
    weights that move the output but hardly its mean are still caught."""
    from repro.kernels import registry as kreg

    real = kreg.compiled

    def planted(kdef, shape, dtype, config, device):
        if kdef.name != "flash_attention":
            return real(kdef, shape, dtype, config, device)
        return lambda *args: list(calibrate.faulty(kdef.name, fault, shape, args))

    monkeypatch.setattr(kreg, "compiled", planted)
    out, _ = run_tiny("xplat1.mixed.steady", rate=6.0)
    assert not out["correct"]
    assert failed_checks(out) == {"gap.grok1_attention"}, out["checks"]


def test_failing_task_is_caught(monkeypatch):
    """Expert tasks raise once the warm-up (one per provider) is over: the
    broker's retries and, once they run out, the tasks themselves count
    against ``correct``."""
    from repro.core.managers import compute

    real = compute.KernelRuntime.run
    seen = []

    def flaky(self, task, device):
        if task.payload["kernel"] == "moe_gmm":
            seen.append(task)
            if len(seen) > 3:
                raise RuntimeError("planted fault")
        return real(self, task, device)

    monkeypatch.setattr(compute.KernelRuntime, "run", flaky)
    out, _ = run_tiny("xplat1.mixed.steady", rate=6.0)
    assert not out["correct"]
    assert {"not_done", "retries"} <= failed_checks(out), out["checks"]
    assert out["failed"] > 0


SPREAD = r"""
import json, sys
sys.path[:0] = [{root!r}]
import jax
from bench import run, spec
from bench.conftest import SEED, tiny_config
from repro.core.provider import ProviderHandle
bench = spec.benchmark()
cell = spec.cell(bench, "xplat1.mixed.steady")
config = tiny_config(bench, cell["config"])
for i, p in enumerate(config["providers"]):
    p["device_offset"] = i
traffic = dict(spec.traffic(cell["traffic"]), rate_per_s=8.0)
go = lambda: run.run_cell(bench, cell, config, traffic, SEED, 2.0, False, jax.devices(), log=lambda s: None)[0]
sound = go()
ProviderHandle.next_device = lambda self: jax.devices()[0]
print(json.dumps({{"sound": sound, "all_on_chip0": go()}}))
"""


@pytest.fixture(scope="module")
def spread_over_chips():
    """The one-chip deployment with its three providers on three virtual
    devices, in one process with one thread per device: a sound run, then
    one that places every task on the first device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               XLA_FLAGS="--xla_force_host_platform_device_count=3 --xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run([sys.executable, "-c", SPREAD.format(root=str(ROOT))],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_providers_on_their_own_chips_are_correct(spread_over_chips):
    out = spread_over_chips["sound"]
    assert out["device"]["count"] == 3
    assert out["correct"], out["checks"]


def test_tasks_off_their_providers_chips_are_caught(spread_over_chips):
    """Every task placed on the first chip, whatever its provider."""
    out = spread_over_chips["all_on_chip0"]
    assert not out["correct"]
    assert "wrong_device" in failed_checks(out), out["checks"]


def test_no_tpu_gives_no_result(tmp_path):
    """On a machine without a TPU the command exits non-zero and prints no
    result line; so it does in a directory that holds only BENCHMARK.json
    and the benchmark's own files."""
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    import shutil

    shutil.copytree(ROOT / "bench", bare / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for where in (ROOT, bare):
        proc = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "xplat1.mixed.steady", "--seed", str(SEED),
             "--seconds", "1", "--trace", "0"],
            cwd=where, capture_output=True, text=True, env=env, timeout=300,
        )
        assert proc.returncode != 0
        assert '"correct"' not in proc.stdout
        assert "no TPU" in proc.stderr
