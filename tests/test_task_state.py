"""Task state machine: legal transitions, idempotent completion, tracing."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.task import (
    FINAL_STATES,
    LEGAL,
    IllegalTransition,
    Resources,
    Task,
    TaskState,
)

ALL_STATES = list(TaskState)


def test_legal_path_to_done():
    t = Task(kind="noop")
    for s in (TaskState.BOUND, TaskState.PARTITIONED, TaskState.SUBMITTED, TaskState.RUNNING):
        t.advance(s)
    t.mark_done(42)
    assert t.tstate == TaskState.DONE
    assert t.result() == 42


def test_illegal_transition_raises():
    t = Task(kind="noop")
    with pytest.raises(IllegalTransition):
        t.advance(TaskState.RUNNING)  # NEW -> RUNNING is illegal


def test_mark_done_is_idempotent_and_authoritative():
    t = Task(kind="noop")
    t.advance(TaskState.BOUND)
    t.mark_done("first")
    t.mark_done("second")  # duplicate/speculative completion: no-op
    assert t.result() == "first"
    assert t.tstate == TaskState.DONE


def test_mark_failed_ignored_when_not_inflight():
    t = Task(kind="noop")
    t.advance(TaskState.BOUND)
    assert t.mark_failed(RuntimeError("stale")) is False
    assert t.tstate == TaskState.BOUND


def test_retry_cycle():
    t = Task(kind="noop", max_retries=2)
    for s in (TaskState.BOUND, TaskState.PARTITIONED, TaskState.SUBMITTED, TaskState.RUNNING):
        t.advance(s)
    assert t.mark_failed(RuntimeError("boom")) is True
    assert not t.done()  # retries remain: no exception surfaced yet
    t.reset_for_retry()
    assert t.tstate == TaskState.BOUND and t.retries == 1


def test_exhausted_retries_surface_exception():
    t = Task(kind="noop", max_retries=0)
    for s in (TaskState.BOUND, TaskState.PARTITIONED, TaskState.SUBMITTED, TaskState.RUNNING):
        t.advance(s)
    t.mark_failed(RuntimeError("boom"))
    with pytest.raises(RuntimeError):
        t.result(timeout=0.1)


@given(st.lists(st.sampled_from(ALL_STATES), min_size=1, max_size=12))
@settings(max_examples=200, deadline=None)
def test_state_machine_never_leaves_final_states(path):
    """Property: whatever transition sequence is attempted via try_advance,
    a final-state task only changes via the explicit retry path."""
    t = Task(kind="noop")
    for target in path:
        before = t.tstate
        moved = t.try_advance(target)
        if moved:
            assert target in LEGAL[before]
        else:
            assert t.tstate == before
        if before in FINAL_STATES and before != TaskState.FAILED:
            assert t.tstate == before


def test_resources_fits():
    small = Resources(cpus=1, accels=0, memory_mb=100)
    big = Resources(cpus=8, accels=2, memory_mb=1024)
    assert small.fits(big) and not big.fits(small)


# ---------------------------------------------------------------------------
# Property suite: random legal/illegal op sequences (transitions + completion
# calls) must never corrupt the machine — final states stay final (modulo the
# explicit FAILED -> BOUND retry), done callbacks fire exactly once, and every
# trace is monotonically timestamped.
# ---------------------------------------------------------------------------

# ops: attempted transitions (legal or not) interleaved with completion calls
OPS = ALL_STATES + ["mark_done", "mark_failed", "mark_canceled", "reset_for_retry"]


def _apply(task, op):
    if isinstance(op, TaskState):
        task.try_advance(op)
    elif op == "mark_done":
        task.mark_done("r")
    elif op == "mark_failed":
        task.mark_failed(RuntimeError("boom"))
    elif op == "mark_canceled":
        task.mark_canceled()
    elif op == "reset_for_retry":
        if task.tstate == TaskState.FAILED and task.retries < task.max_retries:
            task.reset_for_retry()


@given(st.lists(st.sampled_from(OPS), min_size=1, max_size=16))
@settings(max_examples=300, deadline=None)
def test_random_ops_never_corrupt_final_states(ops):
    t = Task(kind="noop", max_retries=1)
    for op in ops:
        before = t.tstate
        _apply(t, op)
        after = t.tstate
        assert after in set(TaskState)
        if before in FINAL_STATES and before != TaskState.FAILED:
            # DONE/CANCELED are absorbing, whatever is thrown at them
            assert after == before
        if before == TaskState.FAILED:
            # FAILED may only leave via the explicit retry path
            assert after in (TaskState.FAILED, TaskState.BOUND)


@given(st.lists(st.sampled_from(OPS), min_size=1, max_size=16))
@settings(max_examples=300, deadline=None)
def test_done_callbacks_never_double_fire(ops):
    t = Task(kind="noop", max_retries=0)
    fired = []
    t.add_done_callback(lambda fut: fired.append(fut))
    for op in ops:
        _apply(t, op)
    assert len(fired) <= 1
    if t.done():  # resolved future <=> exactly one callback fire
        assert len(fired) == 1
    # duplicate completion attempts are no-ops: a resolved (or resolvable)
    # future fires exactly once; a tstate-only CANCELED (future never
    # resolved) stays silent rather than firing late
    t.mark_done("again")
    t.mark_done("again")
    assert len(fired) == (1 if t.done() else 0)


@given(st.lists(st.sampled_from(OPS), min_size=1, max_size=16))
@settings(max_examples=300, deadline=None)
def test_trace_events_monotonically_timestamped(ops):
    t = Task(kind="noop", max_retries=1)
    for op in ops:
        _apply(t, op)
    ts = [stamp for _, stamp in t.trace.events]
    assert ts == sorted(ts)
    assert t.trace.events[0][0] == "created"
