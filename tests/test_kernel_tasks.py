"""kind="kernel" tasks: real Pallas compute on the wire.

Covers the whole payload path — the KernelRuntime's rep-granular resume
contract (managers/compute.py), the checkpointer's kernel branch (progress
IS the checkpoint: lost_s == 0), a live broker executing one task per
registered kernel with ``kernel.exec`` accounting reconciling under
HYDRA_EVENTS_CHECK=1, tuned-config consultation under HYDRA_AUTOTUNE=1,
and the acceptance scenario: a searise run whose serve lane dispatches
kernel payloads completes with ZERO failed tasks under the PR-6 correlated
fault schedule."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import Hydra, ProviderSpec, Task, TaskState
from repro.core.events import EventBus
from repro.core.provider import ValidationError
from repro.core.managers.compute import KERNEL_RUNTIME
from repro.core.staging import DatasetRegistry
from repro.ckpt.checkpoint import TaskCheckpointer
from repro.kernels import registry as kreg
from repro.scenarios import presets
from repro.scenarios.runner import check_invariants, run_scenario

from conftest import wait_until


# ---------------------------------------------------------------------------
# KernelRuntime: rep-granular execution + resume
# ---------------------------------------------------------------------------


def test_kernel_runtime_executes_and_advances_progress():
    task = Task(kind="kernel", payload={"kernel": "moe_gmm", "reps": 2, "seed": 1})
    result = KERNEL_RUNTIME.run(task, jax.devices()[0])
    assert result["kernel"] == "moe_gmm"
    assert result["reps"] == 2 and result["skipped_reps"] == 0
    assert result["kernel_s"] > 0
    assert task.progress_frac == 1.0
    assert task.kernel_stats["reps"] == 2
    assert task.kernel_stats["config"] == kreg.config_sig(
        kreg.get_kernel("moe_gmm").defaults(kreg.get_kernel("moe_gmm").tiny_shape, "float32")
    )


def test_kernel_runtime_resume_skips_completed_reps():
    """A resumed task re-enters with the progress_frac the checkpointer
    captured: only the unfinished reps run again."""
    task = Task(kind="kernel", payload={"kernel": "rglru_scan", "reps": 4})
    task.progress_frac = 0.5  # two of four reps completed before the kill
    task.kernel_done_s = 0.125
    result = KERNEL_RUNTIME.run(task, jax.devices()[0])
    assert result["skipped_reps"] == 2
    assert result["reps"] == 4
    assert task.progress_frac == 1.0
    # lifetime totals: kernel_s includes the pre-kill work, so broker
    # reps/seconds accounting reconciles across preempt/resume cycles
    assert result["kernel_s"] > 0.125
    assert task.kernel_stats["kernel_s"] == result["kernel_s"]


def test_kernel_runtime_honors_explicit_payload_config():
    shape = {"B": 1, "L": 64, "dr": 128}
    task = Task(
        kind="kernel",
        payload={
            "kernel": "rglru_scan",
            "shape": shape,
            "config": {"block_d": 32},
        },
    )
    result = KERNEL_RUNTIME.run(task, jax.devices()[0])
    assert result["config"] == "block_d=32"
    assert result["sig"] == kreg.shape_sig(shape, "float32")


# ---------------------------------------------------------------------------
# checkpointer kernel branch: completed reps ARE the checkpoint
# ---------------------------------------------------------------------------


def test_checkpointer_kernel_branch_loses_nothing():
    ck = TaskCheckpointer(DatasetRegistry(), EventBus(strict=False), interval_s=2.0)
    kernel = Task(kind="kernel", payload={"kernel": "rglru_scan", "reps": 4})
    assert ck.eligible(kernel)  # resumable from rep 0: never charge a retry
    assert not ck.eligible(Task(kind="noop"))
    kernel.progress_frac = 0.75
    kernel.kernel_done_s = 1.5
    ck.on_preempt(kernel)
    # the runtime's per-rep advance IS the durable boundary: unlike the
    # sleep path there is no interval rounding and no re-executed tail
    assert kernel.progress_frac == 0.75
    assert kernel.resumes == 1 and kernel.retries == 0
    assert kernel.ckpt_dataset == f"ckpt:{kernel.uid}"
    assert kernel.ckpt_dataset in kernel.inputs
    assert ck.registry.known(kernel.ckpt_dataset)
    stats = ck.stats()
    assert stats["preempted_work_s"] == pytest.approx(1.5)
    assert stats["reexecuted_s"] == 0.0


# ---------------------------------------------------------------------------
# broker execution + kernel.exec accounting (HYDRA_EVENTS_CHECK strict)
# ---------------------------------------------------------------------------


def _kernel_broker(tmp_path) -> Hydra:
    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, workdir=str(tmp_path))
    h.register_provider(ProviderSpec(name="a", concurrency=2))
    return h


def test_broker_executes_one_task_per_registered_kernel(tmp_path):
    h = _kernel_broker(tmp_path)
    tasks = [
        Task(kind="kernel", payload={"kernel": name, "reps": 1, "seed": i})
        for i, name in enumerate(sorted(kreg.KERNELS))
    ]
    h.dispatch(tasks)
    assert wait_until(lambda: all(t.done() for t in tasks), timeout=120.0)
    for t in tasks:
        assert t.tstate == TaskState.DONE and t.exception() is None
        assert t.result()["skipped_reps"] == 0
    # one kernel.exec per completed task, keyed metrics reconcile with the
    # legacy accumulators (the shutdown below re-runs the strict cross-check)
    assert h.kernel_execs == len(tasks)
    assert h.kernel_execs_by == {name: 1 for name in kreg.KERNELS}
    assert h.kernel_reps == len(tasks)
    assert h.kernel_seconds > 0
    view = h.events.view
    assert view.get("hydra.kernel.execs") == len(tasks)
    assert view.keyed_get("hydra.kernel.execs") == {name: 1 for name in kreg.KERNELS}
    exec_events = [e for e in h.events.events() if e.name == "kernel.exec"]
    assert len(exec_events) == len(tasks)
    h.shutdown(wait=True)


@pytest.mark.parametrize("name", sorted(kreg.KERNELS))
@pytest.mark.parametrize("connector", ["caas", "pilot"])
def test_kernel_task_names_its_device_and_checksums_its_output(tmp_path, name, connector):
    """The result says where the kernel ran (a device of the provider's
    slice) and carries a checksum of its output that agrees with the
    pure-jnp reference on the same seeded operands."""
    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, workdir=str(tmp_path))
    handle = h.register_provider(ProviderSpec(name="p", connector=connector, concurrency=1))
    kdef = kreg.get_kernel(name)
    task = Task(kind="kernel", payload={"kernel": name, "seed": 5})
    h.dispatch([task])
    assert wait_until(task.done, timeout=60.0)
    h.shutdown(wait=True)
    result = task.result()
    assert result["device"] in [d.id for d in handle.devices]
    assert result["platform"] == handle.devices[0].platform
    shape = dict(kdef.tiny_shape)
    want = kreg.checksum(kdef.ref(shape, kdef.make_args(shape, "float32", 5)))
    assert result["checksum"] == pytest.approx(want, rel=1e-5, abs=1e-4)


@pytest.mark.parametrize(
    "offset,n_devices", [(1, 1), (0, 2), (-1, 1), (len(jax.devices()), 1)]
)
def test_missing_device_slice_is_refused(tmp_path, offset, n_devices):
    """A provider whose slice is not among the visible devices is an
    error, not a silent wrap onto an existing device."""
    h = Hydra(pod_store="memory", workdir=str(tmp_path))
    with pytest.raises(ValidationError):
        h.register_provider(ProviderSpec(name="p", device_offset=offset, n_devices=n_devices))
    h.register_provider(ProviderSpec(name="q"))  # offset 0: the shared device 0
    h.shutdown(wait=True)


_FOUR_DEVICES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import concurrent.futures
    from repro.core import Hydra, ProviderSpec, Task
    from repro.kernels import registry as kreg

    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0)
    for i in range(4):
        connector = "pilot" if i == 3 else "caas"
        h.register_provider(ProviderSpec(
            name=f"chip{i}", connector=connector, device_offset=i, concurrency=2))
    tasks = [
        Task(kind="kernel", payload={"kernel": "rglru_scan", "seed": i % 2})
        for i in range(16)
    ]
    h.dispatch(tasks)
    _, pending = concurrent.futures.wait(tasks, timeout=120)
    h.shutdown(wait=True)
    assert not pending
    seen = set()
    for t in tasks:
        r = t.result()
        assert r["device"] == int(t.provider[-1]), (t.provider, r["device"])
        seen.add(r["device"])
    assert seen == {0, 1, 2, 3}, seen
    by_seed = {}
    for t in tasks:
        by_seed.setdefault(t.payload["seed"], set()).add(t.result()["checksum"])
    assert all(len(v) == 1 for v in by_seed.values()), by_seed
    print("FOUR_DEVICES_OK", sorted(seen))
""")


def test_four_providers_run_on_four_devices():
    """Four one-device providers on four (virtual CPU) devices: each task
    runs on its provider's device, every device does work, and a seed's
    checksum is the same on every device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES], env=env, capture_output=True, text=True, timeout=300
    )
    assert "FOUR_DEVICES_OK" in out.stdout, out.stderr[-3000:]


def test_broker_kernel_tasks_consult_tuned_cache_under_gate(tmp_path, monkeypatch):
    h = _kernel_broker(tmp_path)
    tuner = h.enable_kernel_autotune(timer="model")
    kdef = kreg.get_kernel("rglru_scan")
    tuned = tuner.tune("rglru_scan", dict(kdef.tiny_shape), "float32")
    default_sig = kreg.config_sig(kdef.defaults(kdef.tiny_shape, "float32"))
    assert kreg.config_sig(tuned.config) != default_sig  # a real contrast

    monkeypatch.setenv("HYDRA_AUTOTUNE", "1")
    gated = Task(kind="kernel", payload={"kernel": "rglru_scan"})
    h.dispatch([gated])
    assert wait_until(gated.done, timeout=60.0)
    assert gated.result()["config"] == kreg.config_sig(tuned.config)

    monkeypatch.delenv("HYDRA_AUTOTUNE")
    ungated = Task(kind="kernel", payload={"kernel": "rglru_scan"})
    h.dispatch([ungated])
    assert wait_until(ungated.done, timeout=60.0)
    assert ungated.result()["config"] == default_sig

    assert len([e for e in h.events.events() if e.name == "kernel.tune"]) == 1
    assert h.events.view.get("hydra.kernel.tunes") == 1
    h.shutdown(wait=True)
    # shutdown released the process-global tuner installation
    from repro.kernels import autotune

    assert autotune._GLOBAL is not tuner


def test_enable_kernel_autotune_refuses_double_attach(tmp_path):
    h = _kernel_broker(tmp_path)
    h.enable_kernel_autotune(timer="model")
    with pytest.raises(RuntimeError):
        h.enable_kernel_autotune(timer="model")
    h.shutdown(wait=True)


# ---------------------------------------------------------------------------
# the acceptance scenario: kernel payloads under correlated chaos
# ---------------------------------------------------------------------------


def _shrunken_kernels_spec(seed: int = 0):
    """searise_kernels at tier-1 size: same fleet, same four-event fault
    schedule, one serve wave of four single-rep kernel tasks (one per
    registered kernel) so real compute stays a few wall seconds."""
    spec = presets.searise_kernels(seed)
    spec.traffic.facts_members = 6
    spec.traffic.train_jobs = 1
    spec.traffic.serve_waves = 1
    spec.traffic.serve_tasks_per_wave = 4
    spec.traffic.serve_kernel_reps = 1
    return spec


def test_kernel_scenario_zero_failed_under_chaos():
    spec = _shrunken_kernels_spec()
    chaos = run_scenario(spec, chaos=True)
    base = run_scenario(spec, chaos=False)
    assert check_invariants(chaos, base, spec) == []
    assert chaos.failed_tasks == 0 and base.failed_tasks == 0
    for report in (chaos, base):
        k = report.kernel
        # at-least-once execution, exactly-once completion: a speculative
        # duplicate may add an exec, never lose one
        assert k["execs"] >= spec.traffic.serve_tasks_per_wave
        assert set(k["execs_by"]) == set(spec.traffic.serve_kernels)
        assert k["reps"] >= spec.traffic.serve_tasks_per_wave
        assert k["seconds"] > 0
        assert k["tunes"] == len(spec.traffic.serve_kernels)  # pre-tuned once each


@pytest.mark.chaos
def test_kernel_preset_full_smoke_scale_preempts_and_recovers():
    """The unshrunken preset (nightly): enough serve waves that the
    preempt-kill wave actually lands on kernel work mid-flight."""
    spec = presets.searise_kernels()
    chaos = run_scenario(spec, chaos=True)
    base = run_scenario(spec, chaos=False)
    assert check_invariants(chaos, base, spec) == []
    assert chaos.failed_tasks == 0
    assert chaos.preempted_tasks > 0
    want = spec.traffic.serve_waves * spec.traffic.serve_tasks_per_wave
    assert chaos.kernel["execs"] >= want
