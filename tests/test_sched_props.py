"""Property-based scheduler invariants under the VirtualClock.

For random DAGs (<= 200 nodes) run through BOTH dispatch modes:

  * a task is dispatched only after every dependency is DONE (virtual
    trace ordering: first ``submitted`` >= each dep's last ``exec_done``),
  * no task is ever dispatched twice (exactly one ``submitted`` event when
    no faults are injected),
  * streaming is never slower than frontier mode beyond one wave of
    virtual-time skew, and never produces more pods.

Virtual time is what makes this suite feasible: each example schedules
hundreds of multi-second sleep tasks in real milliseconds.
"""
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Hydra, ProviderSpec, Task, TaskState, Workflow, WorkflowManager
from repro.runtime.clock import virtual_time

pytestmark = pytest.mark.slow  # deselectable on PR CI runs (-m "not slow")

# one wave of sleep: the unit of virtual-time skew for makespan comparison
# (the auto-advancer may tick while a readiness event is still in flight
# between threads, costing a task-duration wave; streaming crosses two more
# thread handoffs than frontier mode, so allow two waves of skew)
WAVE = 1.0
SKEW = 2 * WAVE


def random_dag(seed: int, duration: float = WAVE) -> Workflow:
    """A random DAG of sleep tasks: <= 200 nodes, <= 3 deps per node drawn
    from recent predecessors (bounded depth, realistic workflow shape)."""
    rng = random.Random(seed)
    n = 20 + (seed * 37) % 180
    wf = Workflow(name=f"prop.{seed}.{n}")
    nodes: list[Task] = []
    for i in range(n):
        k = rng.randint(0, min(3, len(nodes)))
        window = nodes[-10:]  # recent predecessors only: keeps depth sane
        deps = rng.sample(window, min(k, len(window))) if window else []
        nodes.append(wf.add(Task(kind="sleep", duration=duration), deps=deps))
    return wf


def run_mode(seed: int, streaming: bool) -> dict:
    # a generous stability window (~10ms of quiet) lets readiness events
    # finish their thread handoffs before the advancer ticks a wave.
    # SCPP (one task per pod) in BOTH modes: co-scheduled MCPP pod tasks
    # execute sequentially by design, which would make makespan measure pod
    # packing rather than scheduling order — the invariant under test here.
    with virtual_time(stability_polls=20) as clock:
        h = Hydra(
            pod_store="memory",
            streaming=streaming,
            batch_window=0.0,
            max_batch=512,
            partitioning="scpp",
        )
        h.register_provider(ProviderSpec(name="p1", concurrency=64))
        h.register_provider(ProviderSpec(name="p2", concurrency=64))
        wf = random_dag(seed)
        WorkflowManager(h, partitioning="scpp").run([wf], timeout=3600)
        ok = wf.done and not wf.failed
        stats = h.stream_stats()
        h.shutdown(wait=True)
        starts = [t.trace.first("exec_start") for t in wf.tasks]
        ends = [t.trace.last("exec_done") for t in wf.tasks]
        makespan = (
            max(e for e in ends if e is not None) - min(s for s in starts if s is not None)
            if all(e is not None for e in ends)
            else float("inf")
        )
        return {"wf": wf, "ok": ok, "makespan": makespan, "pods": stats["n_pods"]}


def check_dispatch_invariants(wf: Workflow) -> None:
    by_uid = {t.uid: t for t in wf.tasks}
    for t in wf.tasks:
        assert t.tstate == TaskState.DONE, f"{t.uid} ended {t.tstate}"
        submitted = [ts for ev, ts in t.trace.events if ev == "submitted"]
        assert len(submitted) == 1, f"{t.uid} dispatched {len(submitted)} times"
        for dep_uid in wf.deps[t.uid]:
            dep = by_uid[dep_uid]
            dep_done = dep.trace.last("exec_done")
            assert dep_done is not None
            assert submitted[0] >= dep_done, (
                f"{t.uid} dispatched at {submitted[0]} before dep "
                f"{dep_uid} finished at {dep_done}"
            )


@given(st.integers(0, 6))
@settings(max_examples=7, deadline=None)
def test_random_dag_scheduler_invariants(seed):
    frontier = run_mode(seed, streaming=False)
    streaming = run_mode(seed, streaming=True)
    assert frontier["ok"] and streaming["ok"]
    check_dispatch_invariants(frontier["wf"])
    check_dispatch_invariants(streaming["wf"])
    # streaming never beaten by frontier beyond the bounded virtual skew
    assert streaming["makespan"] <= frontier["makespan"] + SKEW + 1e-6, (
        f"seed {seed}: streaming {streaming['makespan']} vs "
        f"frontier {frontier['makespan']}"
    )
    # and it never fragments the workload into more pods
    assert streaming["pods"] <= frontier["pods"]
