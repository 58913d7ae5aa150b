"""Bring-up check: the broker's kernel-task path end to end on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four one-chip providers vs one chip

One process drives everything; operands are built from seeds on the device.

(a) Device: versions, devices, cache directory.  No TPU -> exit 1, no result.
(b) Kernels direct: each Pallas kernel compiled with Mosaic at the widths of
    the published model that carries it (kernels/registry.py
    ``published_shapes``), checked against its pure-jnp reference.
(c) Brokered: ``Hydra.dispatch`` of >= 1,024 kernel tasks plus a train and a
    prefill compute task over the paper's provider kinds (two CaaS clouds,
    one pilot HPC pool).  Every task must finish DONE without a retry, on a
    TPU of its own provider, with the checksum of a direct run of the same
    (kernel, shape, dtype, seed).
(d) Last line: ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the four-provider backlog (``device_offset`` 0..3)
and the same backlog on one provider pinned to chip 0, and compares them.
Wall times printed here are information, not a benchmark.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import sys
import time
import traceback
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_KERNEL_TASKS = 1024
SEEDS = (0, 1, 2, 3)
# max |kernel - reference| by output dtype: f32 outputs differ only in
# summation order and transcendental rounding; bf16 outputs may differ by
# two bf16 ulps at magnitude 4-8
TOL = {"float32": 1e-3, "bfloat16": 2.0**-4}
HBM_BUDGET = 12e9  # of the v5e's 16 GB: in-flight operands must stay under it
TASK_TIMEOUT_S = 600.0


def fail(msg: str, exc: BaseException | None = None) -> None:
    print(f"FAIL: {msg}", flush=True)
    if exc is not None:
        print("".join(traceback.format_exception(exc)), flush=True)
    sys.exit(1)


def version(pkg: str) -> str:
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device():
    import jax

    from repro.runtime.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"jax {jax.__version__}, jaxlib {version('jaxlib')}, libtpu {version('libtpu')}")
    devices = jax.devices()
    print(f"devices: {devices}")
    print(f"device_kind: {devices[0].device_kind}")
    print(f"compile cache: {cache_dir}")
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        sys.exit(1)
    return devices


class CacheCounter:
    """Persistent compile-cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# (b) kernels direct
# ---------------------------------------------------------------------------


def payloads() -> list[dict]:
    """One kind="kernel" payload per (kernel, dtype) at published widths."""
    from repro.kernels import registry as kreg

    return [
        {"kernel": name, "shape": shape, "dtype": dtype, "arch": arch}
        for name, (arch, shape, dtypes) in kreg.published_shapes().items()
        for dtype in dtypes
    ]


def _program(p: dict, device):
    from repro.kernels import registry as kreg

    kdef = kreg.get_kernel(p["kernel"])
    return kdef, kreg.compiled(kdef, p["shape"], p["dtype"], kdef.defaults(p["shape"], p["dtype"]), device)


def phase_kernels(device) -> None:
    import jax

    from repro.kernels import registry as kreg

    if kreg.interpret_default(device):
        fail(f"kernels would be interpreted on {device}")
    for p in payloads():
        t0 = time.perf_counter()
        kdef, program = _program(p, device)
        compile_s = time.perf_counter() - t0
        args = kreg.operands(kdef, p["shape"], p["dtype"], 0, device)
        out = program(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a, s=p["shape"]: kdef.ref(s, a))(*args)
        err = kreg.max_abs_err(out, want)
        out_dtype = str(jax.tree_util.tree_leaves(out)[0].dtype)
        tol = TOL[out_dtype]
        print(
            f"kernel {p['kernel']} ({p['arch']}) {kreg.shape_sig(p['shape'], p['dtype'])}: "
            f"mosaic=yes compile_s={compile_s:.3f} max_abs_err={err!r} "
            f"tol={tol!r} ({out_dtype} out)",
            flush=True,
        )
        if not err <= tol:
            fail(f"{p['kernel']} {p['dtype']} max_abs_err {err!r} > {tol!r}")


# ---------------------------------------------------------------------------
# (c) the brokered path
# ---------------------------------------------------------------------------


def operand_bytes(p: dict) -> int:
    """Device bytes one in-flight task holds: operands, the f32 temporaries
    make_args may build them from, and the output."""
    import jax
    import numpy as np

    from repro.kernels import registry as kreg

    kdef = kreg.get_kernel(p["kernel"])
    args = jax.eval_shape(lambda: kdef.make_args(p["shape"], p["dtype"], 0))
    out = jax.eval_shape(lambda *a: kdef.ref(p["shape"], a), *args)
    size = lambda a: int(np.prod(a.shape))  # noqa: E731
    held = sum(size(a) * a.dtype.itemsize for a in args)
    temps = sum(size(a) * 4 for a in args if a.dtype.itemsize < 4)
    outs = sum(size(a) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(out))
    return held + temps + outs


def backlog() -> list:
    """N_KERNEL_TASKS kernel tasks cycling through the payloads and seeds,
    then one train and one prefill compute task."""
    from repro.core import Task

    kinds = payloads()
    tasks = []
    for i in range(N_KERNEL_TASKS):
        p = kinds[i % len(kinds)]
        seed = SEEDS[(i // len(kinds)) % len(SEEDS)]
        tasks.append(Task(kind="kernel", payload={
            "kernel": p["kernel"], "shape": p["shape"], "dtype": p["dtype"],
            "seed": seed, "reps": 1,
        }))
    for step_kind in ("train", "prefill"):
        tasks.append(Task(kind="compute", arch="llama3-8b", step_kind=step_kind))
    return tasks


def reference_checksums(device) -> dict:
    """Checksum of a direct run of every (kernel, shape, dtype, seed)."""
    from repro.kernels import registry as kreg

    ref = {}
    for p in payloads():
        kdef, program = _program(p, device)
        for seed in SEEDS:
            args = kreg.operands(kdef, p["shape"], p["dtype"], seed, device)
            key = (p["kernel"], kreg.shape_sig(p["shape"], p["dtype"]), seed)
            ref[key] = kreg.checksum(program(*args))
    return ref


def task_key(t) -> tuple:
    from repro.kernels import registry as kreg

    p = t.payload
    return (p["kernel"], kreg.shape_sig(p["shape"], p["dtype"]), p["seed"])


def run_backlog(providers: list, label: str) -> tuple:
    """Dispatch the backlog through a fresh broker; return (hydra, tasks)
    once every task has finished.  Fails on any failed or retried task."""
    from repro.core import Hydra, TaskState

    h = Hydra(streaming=True, pod_store="memory")
    for spec in providers:
        h.register_provider(spec)
    tasks = backlog()
    t0 = time.perf_counter()
    h.dispatch(tasks)
    _, pending = concurrent.futures.wait(tasks, timeout=TASK_TIMEOUT_S)
    wall = time.perf_counter() - t0
    h.shutdown(wait=True)
    errors = [t for t in tasks if getattr(t, "last_error", None) is not None]
    if errors:
        fail(f"{label}: {len(errors)} task(s) failed at least once", errors[0].last_error)
    not_done = [t for t in tasks if t.tstate != TaskState.DONE]
    if pending or not_done:
        fail(f"{label}: {len(pending)} pending, {len(not_done)} not DONE after {TASK_TIMEOUT_S} s")
    retries = sum(t.retries for t in tasks)
    if retries:
        fail(f"{label}: {retries} retries")
    n_kernel = sum(t.kind == "kernel" for t in tasks)
    if h.kernel_execs != n_kernel:
        fail(f"{label}: hydra.kernel_execs {h.kernel_execs} != {n_kernel} kernel tasks")
    print(
        f"{label}: {len(tasks)} tasks ({n_kernel} kernel, {len(tasks) - n_kernel} compute) "
        f"DONE, failed=0 retries=0 kernel_execs={h.kernel_execs} "
        f"wall_s={wall:.3f} tasks_per_s={len(tasks) / wall:.1f} (information only)",
        flush=True,
    )
    return h, tasks


def check_placement(h, tasks, label: str) -> dict:
    """Every task ran on a TPU of its own provider; returns tasks per device."""
    per_device: dict = {}
    for t in tasks:
        r = t.result()
        own = [d.id for d in h.proxy.get(t.provider).devices]
        if r["platform"] != "tpu" or r["device"] not in own:
            fail(f"{label}: task {t.uid} on {t.provider} ran on {r['platform']}:{r['device']}, "
                 f"provider devices {own}")
        per_device[r["device"]] = per_device.get(r["device"], 0) + 1
    return dict(sorted(per_device.items()))


def size_concurrency() -> int:
    """In-flight slots such that slots x the largest task's bytes stay under
    HBM_BUDGET; prints the reckoning."""
    worst = max(payloads(), key=operand_bytes)
    per_task = operand_bytes(worst)
    slots = int(HBM_BUDGET // per_task)
    for p in payloads():
        print(f"  bytes per in-flight {p['kernel']} {p['dtype']} task: {operand_bytes(p)}")
    print(f"  slots = {HBM_BUDGET:.0f} B // {per_task} B ({worst['kernel']}) = {slots}")
    return slots


def phase_brokered(device) -> None:
    from benchmarks.common import cloud_provider, hpc_provider

    slots = size_concurrency()
    # the paper's provider kinds: two CaaS clouds and one pilot HPC pool
    providers = [
        cloud_provider("jet2", vcpus=slots * 3 // 8),
        cloud_provider("aws", vcpus=slots * 3 // 8),
        hpc_provider("bridges2", cores=slots - 2 * (slots * 3 // 8)),
    ]
    print("providers: " + ", ".join(f"{p.name}({p.connector}, {p.concurrency} slots)" for p in providers))
    ref = reference_checksums(device)
    h, tasks = run_backlog(providers, "brokered")
    per_device = check_placement(h, tasks, "brokered")
    bad = [t for t in tasks if t.kind == "kernel" and t.result()["checksum"] != ref[task_key(t)]]
    if bad:
        t = bad[0]
        fail(f"{len(bad)} checksum mismatches; first {task_key(t)}: "
             f"{t.result()['checksum']!r} != {ref[task_key(t)]!r}")
    print(f"brokered: every checksum matches its direct run ({len(ref)} references); "
          f"tasks per device {per_device}")
    print(f"peak_bytes_in_use: {(device.memory_stats() or {}).get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# --chips 4: four one-chip providers vs one chip
# ---------------------------------------------------------------------------


def phase_four_chips(devices) -> None:
    from repro.core import ProviderSpec

    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, found {len(devices)}")
    slots = size_concurrency()  # per provider: each has a chip of its own
    four = [
        ProviderSpec(name=f"chip{i}", connector="caas", device_offset=i, concurrency=slots)
        for i in range(4)
    ]
    h4, tasks4 = run_backlog(four, "four providers")
    per_device = check_placement(h4, tasks4, "four providers")
    print(f"four providers: tasks per device {per_device}")
    if sorted(per_device) != [d.id for d in devices[:4]]:
        fail(f"not every chip ran tasks: {per_device}")
    one = [ProviderSpec(name="chip0", connector="caas", device_offset=0, concurrency=slots)]
    h1, tasks1 = run_backlog(one, "one provider on chip 0")
    check_placement(h1, tasks1, "one provider on chip 0")
    mismatched = [
        (a, b) for a, b in zip(tasks4, tasks1)
        if a.kind == "kernel" and a.result()["checksum"] != b.result()["checksum"]
    ]
    if mismatched:
        a, b = mismatched[0]
        fail(f"{len(mismatched)} per-task checksums differ from the one-chip run; first "
             f"{task_key(a)} on device {a.result()['device']}: "
             f"{a.result()['checksum']!r} != {b.result()['checksum']!r}")
    print(f"four providers: all {sum(t.kind == 'kernel' for t in tasks4)} kernel checksums "
          f"match the one-chip run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    sys.path.insert(0, str(ROOT / "src"))

    devices = phase_device()
    cache = CacheCounter()
    if chips == 4:
        phase_four_chips(devices)
    else:
        phase_kernels(devices[0])
        phase_brokered(devices[0])
    print(f"persistent compile cache: hits={cache.hits} misses={cache.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
