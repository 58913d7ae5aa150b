"""The per-layer readers of the task path's own stamps (``queued``,
``batched``, ``slot``, ``launched``, ``synced``) on records made by hand,
and on the tiny harness run."""
from __future__ import annotations

import pytest

from bench import spec
from bench.conftest import run_tiny
from bench.run import Record, TaskRecord

STEADY = ["queue_ms.steady", "slot_wait_ms.steady", "launch_ms.steady", "sync_ms.steady", "checksum_ms.steady"]
FLOOD = ["launch_ms.flood", "sync_ms.flood", "checksum_ms.flood"]


def task(due, ms: dict, ok=True):
    """A task due at ``due`` with its stamps ``ms`` milliseconds after it."""
    events = [("created", due)] + [(name, due + t / 1e3) for name, t in ms.items()]
    return TaskRecord(due=due, done=due + max(ms.values()) / 1e3, payload="a", ok=ok, events=events)


# task i: queued at 0.1 ms, batched 2.1 + i, submitted 3 + i, slot 4 + 2i,
# exec_start 6 + 2i, launched 7 + 2i, synced 17 + 3i, exec_done 18 + 3i
STAMPS = ["queued", "batched", "state:SUBMITTED", "slot", "exec_start", "launched", "synced", "exec_done"]


def stamps(i: int) -> dict:
    return dict(zip(STAMPS, [0.1, 2.1 + i, 3 + i, 4 + 2 * i, 6 + 2 * i, 7 + 2 * i, 17 + 3 * i, 18 + 3 * i]))


@pytest.fixture
def rec():
    tasks = [task(100 + i / 100, stamps(i)) for i in range(4)]
    return Record(seconds=0.1, setup_s=1.0, window_start=100.0, tasks=tasks, phase_s={}, chips=1,
                  work={}, kernel_of={}, peaks={})


def read(name, rec):
    return spec.plugin("metrics", name).read(rec)


@pytest.mark.parametrize("name,want", [
    ("queue_ms.steady", 2.0 + 1.5),  # 2 + i over i = 0..3
    ("slot_wait_ms.steady", 1.0 + 1.5),  # 1 + i
    ("launch_ms.steady", 1.0),
    ("launch_ms.flood", 1.0),
    ("sync_ms.steady", 10.0 + 1.5),  # 10 + i
    ("sync_ms.flood", 10.0 + 1.5),
    ("checksum_ms.steady", 1.0),
    ("checksum_ms.flood", 1.0),
])
def test_reader_value(rec, name, want):
    assert read(name, rec) == pytest.approx(want)


@pytest.mark.parametrize("cell", ["steady", "flood"])
def test_the_kernel_runtime_splits_exec_ms(rec, cell):
    parts = sum(read(f"{m}_ms.{cell}", rec) for m in ("launch", "sync", "checksum"))
    assert parts == pytest.approx(read(f"exec_ms.{cell}", rec))


def test_inner_waits_lie_inside_the_outer_ones(rec):
    assert read("queue_ms.steady", rec) <= read("dispatch_ms.steady", rec)
    assert read("slot_wait_ms.steady", rec) <= read("pool_wait_ms.steady", rec)


def test_reps_take_the_first_launch_and_sync(rec):
    t = rec.tasks[0]
    t.events += [("launched", t.due + 0.020), ("synced", t.due + 0.030)]  # a second rep
    assert read("launch_ms.flood", rec) == pytest.approx(1.0)
    # the first task's checksum interval now holds its second rep
    assert read("checksum_ms.flood", rec) > 1.0
    parts = sum(read(f"{m}_ms.flood", rec) for m in ("launch", "sync", "checksum"))
    assert parts == pytest.approx(read("exec_ms.flood", rec))


@pytest.mark.parametrize("name", STEADY + FLOOD)
def test_silent_without_the_stamps(rec, name):
    # a program that stamps only state:SUBMITTED, exec_start and exec_done
    for t in rec.tasks:
        t.events = [(e, at) for e, at in t.events if e in ("created", "state:SUBMITTED", "exec_start", "exec_done")]
    assert read(name, rec) is None
    assert read("exec_ms.steady", rec) is not None


@pytest.mark.parametrize("cell,names", [("xplat1.mixed.steady", STEADY), ("xplat1.scan.flood", FLOOD)])
def test_a_traced_run_reports_the_split(cell, names):
    out, rec = run_tiny(cell, trace=True)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for name in names:
        assert got[name]["value"] >= 0, name
    kind = cell.rsplit(".", 1)[1]
    parts = sum(got[f"{m}_ms.{kind}"]["value"] for m in ("launch", "sync", "checksum"))
    assert parts == pytest.approx(got[f"exec_ms.{kind}"]["value"], rel=0.01)
