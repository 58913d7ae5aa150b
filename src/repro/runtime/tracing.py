"""Event tracing + the paper's four metrics (OVH, TH, TPT, TTX).

Definitions (Hydra paper §5):
  OVH - time Hydra spends preparing the workload for execution and
        communicating with the platform middleware to initiate execution
        (bind + partition + serialize + submit phases).
  TH  - broker throughput: tasks *processed* per second (not executed).
  TPT - task total processing time on the platform: execute the tasks AND
        prepare/shut down the task execution environments.
  TTX - total time the platform takes to execute all submitted tasks.

Every Task/Pod/Provider carries a trace: a list of (event, t) stamped by the
*active clock* (runtime/clock.py) — ``time.perf_counter`` under the default
WallClock, exact virtual instants under a VirtualClock.  Metrics are derived
purely from traces, so they are platform- and workload-agnostic, exactly as
in the paper, and scheduler tests can replay 10k-task scenarios in virtual
time without distorting a single metric formula.

``span`` puts the same stamps on the profiler's timeline: each layer
boundary of the task path opens a ``jax.profiler.TraceAnnotation`` named
``hydra.<stage>`` that carries ``t0``, the clock read its stamp took, so
one offset (the span's start in ns minus ``t0`` x 1e9) maps every task
stamp onto the trace beside the device ops (docs/OBSERVABILITY.md).  The
annotations record only while a profiler runs; otherwise a span costs a
``TraceMe`` check and its stamp.
"""
from __future__ import annotations

import gc
import threading
from dataclasses import dataclass, field
from typing import Iterable, Optional, Union

from jax.profiler import TraceAnnotation

from repro.runtime.clock import get_clock, now


@dataclass
class Trace:
    events: list[tuple[str, float]] = field(default_factory=list)

    def add(self, event: str, t: Optional[float] = None) -> float:
        t = now() if t is None else t
        self.events.append((event, t))
        return t

    def first(self, event: str) -> Optional[float]:
        for e, t in self.events:
            if e == event:
                return t
        return None

    def last(self, event: str) -> Optional[float]:
        out = None
        for e, t in self.events:
            if e == event:
                out = t
        return out

    def span(self, start: str, end: str) -> Optional[float]:
        t0, t1 = self.first(start), self.last(end)
        if t0 is None or t1 is None:
            return None
        return t1 - t0


class _NoSpan:
    """What ``span`` returns while no profiler runs: a shared no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()
_profiling = TraceAnnotation.is_enabled


def span(name: str, trace: Union[Trace, Iterable[Trace], None] = None,
         stamp: Optional[str] = None, **args):
    """``with span("kernel.sync", uid=task.uid): ...`` marks a stage of the
    task path as ``hydra.kernel.sync`` on the profiler's timeline, with
    ``args`` and ``t0`` (the active clock as the span opens, read lock-free:
    ``Clock.stamp``) as its stats.

    With ``trace`` (a ``Trace``, or several: one per task of a batch) and
    ``stamp``, the span's start is also stamped on each, with the one clock
    read that is ``t0``: a stamp and its span share an instant exactly, and
    stay virtual under a ``VirtualClock``.  ``set_metadata(**args)`` on the
    span adds stats known only at its end (a batch's pod count).  Call it in
    the ``with`` statement: the stamp is taken here, not on entry."""
    t0 = None
    if stamp is not None:
        t0 = get_clock().stamp()
        for tr in (trace,) if isinstance(trace, Trace) else trace:
            tr.events.append((stamp, t0))
    if _profiling():
        return TraceAnnotation(f"hydra.{name}", t0=get_clock().stamp() if t0 is None else t0, **args)
    return _NO_SPAN


_gc_annotation: Optional[TraceAnnotation] = None


def _on_gc(phase: str, info: dict) -> None:
    # a collection starts and stops on one thread and never overlaps
    # another, so one slot holds the open annotation
    global _gc_annotation
    if phase == "start":
        if _profiling():
            _gc_annotation = TraceAnnotation(
                "hydra.gc", t0=get_clock().stamp(), generation=info["generation"]
            )
            _gc_annotation.__enter__()
    elif _gc_annotation is not None:
        annotation, _gc_annotation = _gc_annotation, None
        annotation.__exit__(None, None, None)


def trace_gc() -> None:
    """Marks every garbage collection of this process as a ``hydra.gc``
    span (idempotent: one callback per process)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# Metric aggregation
# ---------------------------------------------------------------------------

# Broker-side (OVH) phases, in order.
OVH_PHASES = [
    ("bind_start", "bind_done"),
    ("partition_start", "partition_done"),
    ("serialize_start", "serialize_done"),
    ("submit_start", "submit_done"),
]


@dataclass
class Metrics:
    ovh: float  # broker overhead (s)
    th: float  # broker throughput (tasks/s)
    tpt: float  # platform processing time (s), incl. env setup/teardown
    ttx: float  # platform execution time (s)
    n_tasks: int
    n_pods: int
    phases: dict[str, float] = field(default_factory=dict)

    def row(self) -> dict:
        return {
            "ovh_s": round(self.ovh, 6),
            "th_tasks_per_s": round(self.th, 2),
            "tpt_s": round(self.tpt, 6),
            "ttx_s": round(self.ttx, 6),
            "n_tasks": self.n_tasks,
            "n_pods": self.n_pods,
            **{f"phase_{k}_s": round(v, 6) for k, v in self.phases.items()},
        }


def compute_metrics(run_trace: Trace, tasks: Iterable, pods: Iterable) -> Metrics:
    """Derive the paper's metrics from the broker run trace + task traces."""
    tasks, pods = list(tasks), list(pods)
    phases = {}
    ovh = 0.0
    for start, end in OVH_PHASES:
        d = run_trace.span(start, end)
        if d is not None:
            phases[start.rsplit("_", 1)[0]] = d
            ovh += d

    # TH: tasks processed by the broker / broker processing window
    t0 = run_trace.first("bind_start")
    t1 = run_trace.last("submit_done")
    th = len(tasks) / (t1 - t0) if (t0 is not None and t1 is not None and t1 > t0) else 0.0

    # TPT: platform window incl. env setup/teardown (pod env_up .. env_down)
    env_up = [t for p in pods if (t := p.trace.first("env_setup_start")) is not None]
    env_dn = [t for p in pods if (t := p.trace.last("env_teardown_done")) is not None]
    tpt = (max(env_dn) - min(env_up)) if env_up and env_dn else 0.0

    # TTX: first task exec start .. last task exec done
    starts = [t for task in tasks if (t := task.trace.first("exec_start")) is not None]
    ends = [t for task in tasks if (t := task.trace.last("exec_done")) is not None]
    ttx = (max(ends) - min(starts)) if starts and ends else 0.0

    return Metrics(ovh, th, tpt, ttx, len(tasks), len(pods), phases)


class Counter:
    """Thread-safe monotonically increasing id generator."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._n = 0
        self._lock = threading.Lock()

    def next(self) -> str:
        with self._lock:
            self._n += 1
            return f"{self.prefix}.{self._n:06d}"
