"""CapacityLedger invariants (core/ledger.py).

The ledger's whole value is that its O(1) counters NEVER drift from what a
from-scratch scan would compute.  Two attack angles:

  * property test: drive a random interleaving of the real broker events —
    bind/dispatch, completion, provider registration/removal/blacklist,
    group member churn, breaker trips/recoveries, acquisition begin/
    complete/abort — through the REAL broker API and assert, after every
    settled step, that the ledger equals ``Hydra._ledger_recompute()``;
  * concurrency regression: ``queue_pressure()`` read under concurrent
    enqueue/dispatch/completion traffic stays finite, non-negative, and the
    ledger still reconciles when the dust settles.

The whole tier-1 suite additionally runs with HYDRA_LEDGER_CHECK=1
(conftest.py), so every broker test doubles as a ledger cross-check; these
tests target the event sources end-on.
"""
import random
import threading

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Hydra, ProviderSpec, Task
from repro.core.ledger import CapacityLedger, LedgerDivergence
from repro.runtime.clock import virtual_time


def reconciled(h: Hydra, tries: int = 200) -> dict:
    """Assert the ledger matches the recompute once in-flight events land."""
    h.ledger.check(retries=tries, retry_sleep_s=0.005)
    return h.ledger.snapshot()


# ---------------------------------------------------------------------------
# unit-level: the counter algebra
# ---------------------------------------------------------------------------


def test_ledger_counter_algebra():
    led = CapacityLedger()
    led.upsert_direct("a", 4)
    led.upsert_direct("b", 2)
    assert led.total_slots() == 6 and led.idle_slots() == 6
    led.load_delta("a", 3)
    assert led.idle_slots() == 3
    led.load_delta("a", 2)  # over capacity: idle clamps at 0, not negative
    assert led.idle_slots() == 2 and led.total_slots() == 6
    led.load_delta("a", -5)
    assert led.idle_slots() == 6
    led.deactivate("a")
    assert led.total_slots() == 2 and led.idle_slots() == 2
    led.set_counted("a", True)
    assert led.total_slots() == 6
    led.remove("a")
    led.remove("a")  # idempotent
    assert led.total_slots() == 2
    led.begin_incoming("x", 4)
    led.begin_incoming("x", 4)  # re-begin replaces, not accumulates
    assert led.incoming_slots() == 4
    led.end_incoming("x")
    led.end_incoming("x")
    assert led.incoming_slots() == 0
    led.task_entered(5)
    led.task_resolved(2)
    assert led.backlog() == 3


def test_ledger_capacity_gain_callback_fires_outside_lock():
    led = CapacityLedger()
    gains = []

    def on_gain():
        gains.append(led.idle_slots())  # re-entering a read must not deadlock

    led.attach(on_capacity_gain=on_gain)
    led.upsert_direct("a", 2)
    led.load_delta("a", 2)
    led.load_delta("a", -1)  # idle 0 -> 1: a gain
    assert gains and gains[-1] == 1


def test_strict_divergence_raises():
    led = CapacityLedger(strict=True)
    led.attach(recompute=lambda: {"idle_slots": 99, "total_slots": 99, "incoming_slots": 0, "backlog": 0})
    with pytest.raises(LedgerDivergence):
        led.check(retries=2, retry_sleep_s=0.0)
    assert led.divergences == 1


# ---------------------------------------------------------------------------
# property test: random REAL broker event sequences
# ---------------------------------------------------------------------------


@given(st.integers(0, 9))
@settings(max_examples=10, deadline=None)
def test_random_event_sequences_never_diverge(seed):
    rng = random.Random(seed)
    with virtual_time():
        h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, max_batch=64)
        # a standing fleet plus a group whose members we can churn
        for i in range(3):
            h.register_provider(ProviderSpec(name=f"s{seed}p{i}", concurrency=2))
        group = h.register_group(
            f"s{seed}g",
            [ProviderSpec(name=f"s{seed}m{i}", concurrency=2) for i in range(2)],
            failure_threshold=1,
            reset_timeout_s=0.01,
        )
        alive = [f"s{seed}p{i}" for i in range(3)]
        elastic_n = 0
        outstanding_tasks: list[Task] = []

        for step in range(30):
            op = rng.randrange(7)
            if op in (0, 1):  # dispatch a burst
                burst = [Task(kind="noop") for _ in range(rng.randint(1, 8))]
                outstanding_tasks.extend(burst)
                h.dispatch(burst)
            elif op == 2 and alive:  # blacklist-style outage
                victim = rng.choice(alive)
                alive.remove(victim)
                h.manager(victim).fail()
                h._handle_provider_down(victim)
            elif op == 3:  # scale-out: register a fresh provider
                elastic_n += 1
                name = f"s{seed}e{elastic_n}"
                h.register_provider(ProviderSpec(name=name, concurrency=2))
                alive.append(name)
            elif op == 4 and len(alive) > 1:  # scale-in: drain + deregister
                victim = alive.pop()
                h.remove_provider(victim, drain=True, deregister=True)
            elif op == 5:  # breaker trip on a group member
                member = rng.choice(group.member_names)
                group.mark_down(member)
            else:  # acquisition lifecycle
                elastic_n += 1
                spec = ProviderSpec(name=f"s{seed}a{elastic_n}", concurrency=2)
                h.begin_acquisition(spec, eta_s=100.0)
                if rng.random() < 0.5:
                    h.abort_acquisition(spec.name)
                else:
                    h.complete_acquisition(spec)
                    alive.append(spec.name)
            reconciled(h)

        # let the work finish and re-check the settled state
        h._dispatcher.drain(timeout=30)
        snap = reconciled(h)
        assert snap["idle_slots"] >= 0 and snap["total_slots"] >= 0
        h.shutdown(wait=True)


# ---------------------------------------------------------------------------
# regression: queue_pressure under concurrent enqueue/dispatch
# ---------------------------------------------------------------------------


def test_queue_pressure_consistent_under_concurrent_traffic():
    with virtual_time():
        h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, max_batch=64)
        for i in range(4):
            h.register_provider(ProviderSpec(name=f"qp{i}", concurrency=4))
        d = h.dispatcher()
        stop = threading.Event()
        bad: list = []

        def reader():
            while not stop.is_set():
                p = d.queue_pressure()
                if not (0.0 <= p < 1e9):
                    bad.append(p)

        readers = [threading.Thread(target=reader, daemon=True) for _ in range(3)]
        for r in readers:
            r.start()
        all_tasks = []
        for _ in range(20):
            burst = [Task(kind="noop") for _ in range(25)]
            all_tasks.extend(burst)
            h.dispatch(burst)
        assert d.drain(timeout=30)
        stop.set()
        for r in readers:
            r.join(timeout=5)
        assert not bad, f"queue_pressure out of range: {bad[:5]}"
        for t in all_tasks:
            assert t.result(timeout=10) is None
        snap = reconciled(h)
        assert snap["backlog"] == 0  # every resolved task left the backlog
        assert snap["idle_slots"] == snap["total_slots"] == 16
        h.shutdown(wait=True)


def test_tripped_fleet_at_pool_max_recovers_via_probe():
    """Livelock regression: with an autoscaler attached (throttled budget)
    and EVERY slot behind an OPEN breaker, the event-driven ledger reads 0
    idle forever — the OPEN -> HALF_OPEN transition only happens inside a
    dispatch.  The stall path must fall back to the time-aware probe peek
    (broker.probe_slots) once the reset windows elapse, or a fully-tripped
    fleet at pool max never receives the probe that recovers it.  Wall
    clock: breaker windows must elapse by real time while no task moves the
    virtual clock."""
    from repro.core.autoscaler import LaunchSpec, ProviderPool, cloud_startup

    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0)
    h.register_group(
        "pg",
        [ProviderSpec(name=f"pm{i}", concurrency=2) for i in range(2)],
        failure_threshold=1,
        reset_timeout_s=0.15,
    )
    pool = ProviderPool(
        [
            LaunchSpec(
                template=ProviderSpec(name="nope", platform="cloud"),
                min_instances=0,
                max_instances=0,  # pool exhausted: no replacement capacity
                latency=cloud_startup(1.0),
            )
        ]
    )
    h.autoscale(pool, tick_s=0.05)
    group = h.group("pg")
    group.mark_down("pm0")
    group.mark_down("pm1")
    assert h.idle_slots() == 0 and h.total_slots() == 0
    tasks = [Task(kind="noop") for _ in range(8)]
    h.dispatch(tasks)
    for t in tasks:
        assert t.result(timeout=20) is None  # recovered via half-open probe
    reconciled(h)
    h.shutdown(wait=True)


def test_backlog_counts_distinct_unresolved_submitted_tasks():
    with virtual_time():
        h = Hydra(pod_store="memory", streaming=True, batch_window=0.0)
        h.register_provider(ProviderSpec(name="bl0", concurrency=4))
        tasks = [Task(kind="noop") for _ in range(10)]
        h.dispatch(tasks)
        for t in tasks:
            t.result(timeout=10)
        snap = reconciled(h)
        assert snap["backlog"] == 0
        h.shutdown(wait=True)


def test_prune_retires_metrics_and_bounds_submissions():
    with virtual_time():
        h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, max_batch=16)
        h.register_provider(ProviderSpec(name="pr0", concurrency=4))
        tasks = [Task(kind="noop") for _ in range(400)]
        h.dispatch(tasks)
        for t in tasks:
            t.result(timeout=30)
        h._dispatcher.drain(timeout=10)
        h._prune_finished_submissions()
        with h._lock:
            live = len(h._submissions)
        assert live == 0  # everything resolved: nothing retained
        totals = h.phase_totals()  # retired totals survive the prune
        assert totals.get("bind", 0) >= 0 and "submit" in totals
        with h._lock:
            assert h._retired["n_tasks"] == 400
        h.shutdown(wait=True)
