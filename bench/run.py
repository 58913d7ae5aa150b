"""Runs one cell of BENCHMARK.json once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: load the cell's files by name; fail without a result unless JAX
finds a TPU and the cell's number of chips; build the deployment; warm up
every (kernel, shape, dtype, device) program the traffic uses by a burst
through ``Hydra.dispatch``; send the seed's open-loop schedule for
``--seconds``; wait for every task due in the window; compare each answer
with the reference (``oracle.py``); print the cell's metrics.  The last line
of standard output is one JSON object; the numbers compared, each with its
limit, are the last lines of standard error and the last key of that object.

With ``--trace 1`` a steady sub-window of a few seconds is profiled, and the
cell's per-layer metrics are printed in place of its end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the package, not its modules, is importable: ``bench.spec``, never ``spec``
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import deployment, generator, oracle, spec, trace_reduce  # noqa: E402

WAIT_AFTER_CLOSE_S = 120.0  # how long answers due in the window are waited for
WARM_TIMEOUT_S = 900.0


def start_jax(root: Path = ROOT):
    """JAX with the persistent compile cache at a fixed path (the one the
    environment gives, else ``.jax_cache/`` in the checkout) and the TPU
    runtime's logs off, so nothing is written outside the checkout."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def accelerator(chips: int) -> list:
    """The cell's chips, or exit with no result."""
    jax = start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        sys.exit(1)
    if len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX finds {len(devices)}", file=sys.stderr)
        sys.exit(1)
    return devices


class CompileCounter:
    """Programs built or loaded from the persistent cache, from JAX's own
    monitoring events (one per backend compile request)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


@dataclass
class TaskRecord:
    due: float  # perf_counter seconds
    done: float  # when the future resolved, or when the run gave up on it
    payload: str
    ok: bool  # DONE with an answer inside its limit
    events: list  # the task's own trace: (event, perf_counter seconds)


@dataclass
class Record:
    """What a metric reader reads (``metrics/<name>.py`` ``read``)."""

    seconds: float
    setup_s: float
    window_start: float  # perf_counter seconds
    tasks: list  # [TaskRecord], every task due in the window
    phase_s: dict  # broker phase seconds spent in the window (bind, partition, ...)
    chips: int
    work: dict  # payload -> (flops, bytes) of one call
    kernel_of: dict  # payload -> kernel
    peaks: dict
    device: dict | None = None  # from the trace: window_s, busy_s [per chip], calls {payload: (n, s)}


class Profiler(threading.Thread):
    """Profiles [t0 + a, t0 + b) of the window from a thread of its own, so
    that the generator keeps its schedule while the profiler starts."""

    def __init__(self, log_dir: str, t_start: float, t_stop: float):
        super().__init__(name="bench-profiler", daemon=True)
        self.log_dir, self.t_start, self.t_stop = log_dir, t_start, t_stop
        self.window = (0.0, 0.0)
        self.error: BaseException | None = None

    def run(self):
        import jax

        try:
            time.sleep(max(0.0, self.t_start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            began = time.perf_counter()
            time.sleep(max(0.0, self.t_stop - time.perf_counter()))
            ended = time.perf_counter()
            jax.profiler.stop_trace()
            self.window = (began, ended)
        except BaseException as e:  # noqa: BLE001 - reported by the caller
            self.error = e


def trace_window(seconds: float) -> tuple[float, float]:
    """The profiled part of the window: about four seconds in its middle."""
    a = max(0.3 * seconds, seconds / 2 - 2)
    return a, min(a + 4, 0.9 * seconds)


def send(h, window: list, t0: float) -> list[float]:
    """The open loop: dispatch each task when due, whatever has finished.
    Returns how late each dispatch ran behind its first task's due time."""
    late = []
    i, n = 0, len(window)
    while i < n:
        now = time.perf_counter()
        due = t0 + window[i][0].due_s
        if due > now:
            time.sleep(due - now)
            continue
        j = i
        while j < n and t0 + window[j][0].due_s <= now:
            j += 1
        h.dispatch([t for _, t in window[i:j]])
        late.append(now - due)
        i = j
    return late


def device_reading(tr, devices: list, lo_ns: float, hi_ns: float, types: dict) -> dict:
    busy, calls = [], {}
    for d in devices:
        ops = [e for e in tr.ops.get(d.id, []) if e.end_ns > lo_ns and e.start_ns < hi_ns]
        busy.append(trace_reduce.busy_ns(trace_reduce.clip(trace_reduce.union(ops), lo_ns, hi_ns)) / 1e9)
        for k, (n, ns) in trace_reduce.kernel_calls(ops, types).items():
            c = calls.get(k, (0, 0.0))
            calls[k] = (c[0] + n, c[1] + ns / 1e9)
    return {"window_s": (hi_ns - lo_ns) / 1e9, "busy_s": busy, "calls": calls}


def breakdown(tr, devices: list, lo_ns: float, hi_ns: float) -> dict:
    ops = [e for d in devices for e in tr.ops.get(d.id, []) if e.end_ns > lo_ns and e.start_ns < hi_ns]
    gs = []
    for d in devices:
        merged = trace_reduce.clip(trace_reduce.union(tr.ops.get(d.id, [])), lo_ns, hi_ns)
        gs.extend(trace_reduce.gaps(merged, lo_ns, hi_ns))
    return {
        "device_ops": [[k, v] for k, v in trace_reduce.top_ops(ops)],
        "idle_gaps": [[k, v] for k, v in trace_reduce.gap_labels(gs, tr.host)],
    }


def trace_bounds(tr, devices: list, window_s: float) -> tuple[float, float]:
    """The traced window on the trace's clock: it ends at the last event on
    any of the cell's devices or host threads and lasts as long as the
    host measured between starting and stopping the profiler."""
    ends = [e.end_ns for d in devices for e in tr.ops.get(d.id, [])] + [e.end_ns for e in tr.host]
    hi = max(ends) if ends else window_s * 1e9
    return hi - window_s * 1e9, hi


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, devices: list, t_process: float = T_PROCESS, keep_trace: str | None = None,
             log=print) -> tuple[dict, Record]:
    compiles = CompileCounter()
    cat = deployment.payloads(config)
    arrivals = generator.schedule(traffic, seed, seconds)
    used = sorted({a.payload for a in arrivals})
    providers = {p["name"]: p for p in config["providers"]}
    chips = sorted({i for p in providers.values() for i in range(p["device_offset"], p["device_offset"] + p.get("n_devices", 1))})
    cell_devices = [devices[i] for i in chips]
    provider_devices = {
        name: {devices[i].id for i in range(p["device_offset"], p["device_offset"] + p.get("n_devices", 1))}
        for name, p in providers.items()
    }

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        h = deployment.build(config, os.path.join(tmp, "broker"))
        try:
            n_warm = deployment.warm(h, config, used, generator.operand_seeds(seed, 1)[0], WARM_TIMEOUT_S)
            window = [(a, deployment.task(cat[a.payload], a.operand_seed)) for a in arrivals]
            done_at: dict = {}
            for i, (_, t) in enumerate(window):
                t.add_done_callback(lambda _f, i=i: done_at.__setitem__(i, time.perf_counter()))
            phase0 = h.phase_totals()
            execs0 = h.kernel_execs
            compiles0 = compiles.n
            t0 = time.perf_counter() + 0.05
            setup_s = t0 - t_process
            prof = None
            if trace:
                a, b = trace_window(seconds)
                prof = Profiler(os.path.join(tmp, "trace"), t0 + a, t0 + b)
                prof.start()
            late = send(h, window, t0)
            close = t0 + seconds
            _, pending = concurrent.futures.wait([t for _, t in window], timeout=max(0.0, close + WAIT_AFTER_CLOSE_S - time.perf_counter()))
            gave_up = time.perf_counter()
            if prof is not None:
                prof.join()
            compiled_in_window = compiles.n - compiles0
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in cell_devices)
        finally:
            h.shutdown(wait=True)
        # read once every executor thread has ended: the broker counts an
        # execution just after the task's future resolves
        phase1 = h.phase_totals()
        execs = h.kernel_execs - execs0
        log(f"warm-up: {n_warm} tasks; set-up {setup_s:.3f} s; {len(window)} tasks due in {seconds} s")
        log(f"generator: {len(late)} dispatches, late by max {max(late, default=0) * 1e3:.3f} ms, "
            f"mean {sum(late) / max(1, len(late)) * 1e3:.3f} ms")
        log(f"programs compiled or loaded inside the window: {compiled_in_window}")
        log(f"unfinished {WAIT_AFTER_CLOSE_S:.0f} s after the window closed: {len(pending)}")

        device = None
        bd = None
        if trace:
            if prof.error is not None:
                raise prof.error
            files = glob.glob(os.path.join(tmp, "trace", "**", "*.xplane.pb"), recursive=True)
            if keep_trace and files:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(files[0], os.path.join(keep_trace, "run.xplane.pb"))
            tr = trace_reduce.load(files[0])
            win = prof.window[1] - prof.window[0]
            lo, hi = trace_bounds(tr, cell_devices, win)
            types = {name: spec.plugin("work", cat[name]["kernel"]).hlo_types(cat[name]["shape"], cat[name]["dtype"])
                     for name in used}
            if any(tr.ops.get(d.id) for d in cell_devices):
                device = device_reading(tr, cell_devices, lo, hi, types)
                bd = breakdown(tr, cell_devices, lo, hi)

    refs = oracle.references(config, [(a.payload, a.operand_seed) for a in arrivals], devices[chips[0]])
    checks, oks = oracle.judge(config, window, provider_devices, devices[0].platform, execs, refs)
    correct = oracle.passed(checks)

    records = []
    for i, ((a, t), ok) in enumerate(zip(window, oks)):
        records.append(TaskRecord(
            due=t0 + a.due_s, done=done_at.get(i, gave_up) if ok else gave_up,
            payload=a.payload, ok=ok, events=list(t.trace.events),
        ))
    rec = Record(
        seconds=seconds, setup_s=setup_s, window_start=t0, tasks=records,
        phase_s={k: phase1.get(k, 0.0) - phase0.get(k, 0.0) for k in phase1},
        chips=len(cell_devices),
        work={n: spec.plugin("work", cat[n]["kernel"]).work(cat[n]["shape"], cat[n]["dtype"]) for n in used},
        kernel_of={n: cat[n]["kernel"] for n in used},
        peaks=spec.peaks(devices[0].device_kind) if devices[0].platform == "tpu" else {},
        device=device,
    )
    metrics = {}
    for m in spec.metrics_for(bench, cell["name"], trace):
        value = spec.plugin("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(cell_devices), "memory_peak_bytes": peak,
    }
    if device is not None:
        dev["busy_s"] = sum(device["busy_s"]) / len(device["busy_s"])
        dev["window_s"] = device["window_s"]
    out = {
        "correct": correct,
        "attempted": len(window),
        "failed": sum(not r.ok for r in records),
        "metrics": metrics,
        "device": dev,
    }
    if bd is not None:
        out["breakdown"] = bd
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out, rec


def print_checks(out: dict) -> None:
    for k, c in out["checks"].items():
        flag = "" if c["value"] <= c["limit"] else "  FAILED"
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}){flag}", file=sys.stderr)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    devices = accelerator(cell["chips"])
    out, _ = run_cell(bench, cell, config, traffic, args.seed, args.seconds, bool(args.trace), devices,
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    print_checks(out)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
