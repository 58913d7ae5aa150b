"""Profiler spans and task stamps along the task path (runtime/tracing.py
``span``): the stamps every layer boundary adds to a task's ``Trace``, the
``hydra.*`` annotations a profiler records beside them, and the one offset
(a span's start minus its ``t0``) that maps stamps onto the profiler's
clock."""
from __future__ import annotations

import gc
import glob

import jax
import pytest

from repro.core import Hydra, ProviderSpec, Task
from repro.runtime.clock import virtual_time
from repro.runtime.tracing import Trace, span

from conftest import wait_until

ORDER = ["queued", "batched", "state:SUBMITTED", "slot", "exec_start", "launched", "synced", "exec_done"]
TASK_SPANS = ["hydra.exec.task", "hydra.kernel.operands", "hydra.kernel.launch", "hydra.kernel.sync",
              "hydra.kernel.checksum"]
# spans over a batch or pod, which carry no task uid
SHARED_SPAN = {"queued": "hydra.dispatch.enqueue", "batched": "hydra.dispatch.batch", "slot": "hydra.exec.pod"}
# which span each stamp opens or closes
STAMP_SPAN = {**SHARED_SPAN, "exec_start": "hydra.exec.task", "launched": "hydra.kernel.launch",
              "synced": "hydra.kernel.sync"}
SLACK_NS = 5e6  # a stamp lands inside its span within 5 ms on the CPU


def _broker(tmp_path, connector):
    h = Hydra(pod_store="memory", streaming=True, workdir=str(tmp_path))
    h.register_provider(ProviderSpec(name="p", connector=connector, concurrency=1))
    return h


def _run(h, seed):
    task = Task(kind="kernel", payload={"kernel": "rglru_scan", "seed": seed})
    h.dispatch([task])
    assert wait_until(task.done, timeout=60.0)
    assert task.exception() is None
    return task


def _hydra_events(log_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``hydra.*`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hydra."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("connector", ["caas", "pilot"])
def test_stamps_follow_the_task_path_in_order(tmp_path, connector):
    h = _broker(tmp_path, connector)
    try:
        task = _run(h, 3)
    finally:
        h.shutdown(wait=True)
    at = [task.trace.first(s) for s in ORDER]
    assert None not in at, dict(zip(ORDER, at))
    assert at == sorted(at), dict(zip(ORDER, at))


def test_profiler_spans_carry_the_task_and_map_its_stamps(tmp_path):
    h = _broker(tmp_path / "broker", "caas")
    try:
        _run(h, 1)  # compiles outside the trace
        with jax.profiler.trace(str(tmp_path / "trace")):
            task = _run(h, 2)
            gc.collect()
    finally:
        h.shutdown(wait=True)
    events = _hydra_events(tmp_path / "trace")
    mine = {name: (a, b, st) for name, a, b, st in events if st.get("uid") == task.uid}
    for name in TASK_SPANS:
        assert name in mine, sorted(mine)
    # a span over several tasks is the one whose t0 is the task's stamp
    spans = dict(mine)
    for stamp, name in SHARED_SPAN.items():
        (spans[name],) = [(a, b, st) for n, a, b, st in events
                          if n == name and st["t0"] == pytest.approx(task.trace.first(stamp), abs=1e-6)]
    assert spans["hydra.dispatch.batch"][2]["n"] == 1 and spans["hydra.dispatch.batch"][2]["pods"] == 1

    a, _, st = mine["hydra.exec.task"]
    offset = a - st["t0"] * 1e9  # the profiler's clock minus the task's, in ns
    for stamp, name in STAMP_SPAN.items():
        at = task.trace.first(stamp) * 1e9 + offset
        lo, hi, _ = spans[name]
        assert lo - SLACK_NS <= at <= hi + SLACK_NS, (stamp, name, at - lo, hi - at)
    assert mine["hydra.exec.task"][2]["provider"] == "p"
    assert mine["hydra.exec.task"][2]["kernel"] == "rglru_scan"
    assert any(name == "hydra.gc" for name, *_ in events)
    # the stamp maps onto its own span's start exactly (one clock read)
    assert task.trace.first("exec_start") * 1e9 + offset == pytest.approx(a, abs=1e3)


def test_dispatcher_trace_does_not_grow_per_batch(tmp_path):
    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, workdir=str(tmp_path))
    h.register_provider(ProviderSpec(name="p", concurrency=2))
    try:
        first = Task(kind="noop")
        h.dispatch([first])
        assert wait_until(first.done)
        before = len(h.dispatcher().trace.events)
        for _ in range(5):
            t = Task(kind="noop")
            h.dispatch([t])
            assert wait_until(t.done)
        assert h.dispatcher().batches >= 6
        assert len(h.dispatcher().trace.events) == before
    finally:
        h.shutdown(wait=True)


def test_a_batch_stamp_is_one_clock_read_on_every_trace():
    traces = [Trace() for _ in range(3)]
    with span("dispatch.batch", traces, "batched", n=3) as s:
        s.set_metadata(pods=1)  # no profiler: a no-op
    (at,) = {tr.first("batched") for tr in traces}
    assert at is not None


def test_stamps_stay_virtual_under_a_virtual_clock():
    tr = Trace()
    with virtual_time(start=1000.0, auto_advance=False):
        with span("exec.task", tr, "exec_start", uid="t"):
            pass
    assert tr.events == [("exec_start", 1000.0)]


def test_without_a_profiler_a_span_records_nothing_but_its_stamp():
    tr = Trace()
    with span("kernel.sync", uid="t") as a, span("exec.task", tr, "exec_start", uid="t") as b:
        pass
    assert a is b  # the shared no-op
    assert [e for e, _ in tr.events] == ["exec_start"]
