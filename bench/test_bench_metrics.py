"""Every metric reader of BENCHMARK.json on a record made by hand."""
from __future__ import annotations

import pytest

from bench import spec
from bench.run import Record, TaskRecord

PEAKS = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def task(due, done, ok=True, submitted=None, start=None, end=None, payload="a"):
    events = [("created", due)]
    if submitted is not None:
        events.append(("state:SUBMITTED", submitted))
    if start is not None:
        events.append(("exec_start", start))
    if end is not None:
        events += [("state:DONE", end), ("exec_done", end)]
    return TaskRecord(due=due, done=done, payload=payload, ok=ok, events=events)


@pytest.fixture
def rec():
    # 20 tasks due 0.0 .. 0.19 s after the window opens at 100 s; task i
    # takes (i + 1) ms from due to done; the last one failed
    tasks = [
        task(100 + i / 100, 100 + i / 100 + (i + 1) / 1000, ok=i < 19,
             submitted=100 + i / 100 + 0.0005, start=100 + i / 100 + 0.0007, end=100 + i / 100 + (i + 1) / 1000)
        for i in range(20)
    ]
    device = {"window_s": 2.0, "busy_s": [1.5, 0.5],
              "calls": {"a": (10, 1.0), "b": (4, 0.5)}}
    return Record(
        seconds=0.2, setup_s=12.5, window_start=100.0, tasks=tasks,
        phase_s={"bind": 0.001, "submit": 0.003}, chips=2,
        work={"a": (4.0, 20.0), "b": (50.0, 1.0)}, kernel_of={"a": "k1", "b": "k2"},
        peaks=PEAKS, device=device,
    )


def read(name, rec):
    return spec.plugin("metrics", name).read(rec)


def test_every_metric_has_a_reader(rec):
    for m in spec.benchmark()["end_to_end"] + spec.benchmark()["per_layer"]:
        value = read(m["name"], rec)
        assert value is None or value >= 0, m["name"]


def test_latency_percentiles_nearest_rank(rec):
    assert read("task_p50_ms", rec) == pytest.approx(10.0)  # 10th of 20
    assert read("task_p95_ms", rec) == pytest.approx(19.0)  # 19th of 20
    rec.tasks[0].done = rec.tasks[0].due + 1.0  # a task that never finished counts beyond the rest
    assert read("task_p95_ms", rec) == pytest.approx(20.0)


def test_throughput_counts_correct_tasks_done_inside_the_window(rec):
    # tasks 0..18 are correct; all finish by 100.209 s, the window closes at 100.2
    inside = sum(t.done <= 100.2 for t in rec.tasks[:19])
    assert read("tasks_per_s", rec) == pytest.approx(inside / 0.2)


def test_host_spans(rec):
    assert read("setup_s", rec) == 12.5
    assert read("dispatch_ms.steady", rec) == pytest.approx(0.5)
    assert read("pool_wait_ms.steady", rec) == pytest.approx(0.2)
    assert read("exec_ms.steady", rec) == pytest.approx(sum((i + 1) / 1000 - 0.0007 for i in range(20)) / 20 * 1e3)
    assert read("broker_ovh_us.flood", rec) == pytest.approx(1e6 * 0.004 / 20)


def test_device_shares(rec):
    assert read("idle_share.steady", rec) == pytest.approx(50.0)
    # (10 x 4 + 4 x 50) flops over 100 flop/s x 2 chips x 2 s
    assert read("mfu.steady", rec) == pytest.approx(100.0 * 240 / 400)


def test_roofline_takes_the_larger_bound(rec):
    rec.kernel_of = {"a": "flash_attention", "b": "moe_gmm"}
    # a: 20 bytes at 10 B/s = 2 s per call beats 0.04 s of flops: 10 calls, 20 s least, 1 s spent
    # that is the trace reading more work than the chip can do: the reader does not clip it
    assert read("flash_attention_roofline", rec) == pytest.approx(2000.0)
    # b: 50 flops at 100 flop/s = 0.5 s per call: 4 calls, 2 s least, 0.5 s spent
    assert read("moe_gmm_roofline", rec) == pytest.approx(400.0)
    assert read("selective_scan_roofline", rec) is None


def test_device_metrics_are_silent_without_a_trace(rec):
    rec.device = None
    for name in ("idle_share.steady", "mfu.flood", "moe_gmm_roofline"):
        assert read(name, rec) is None


def test_busy_skew_over_chips(rec):
    # busy 1.5 s and 0.5 s: the busier chip over their mean of 1 s
    assert read("chip_busy_skew.x4", rec) == pytest.approx(1.5)
    rec.device["busy_s"] = [0.7]
    assert read("chip_busy_skew.x4", rec) is None
