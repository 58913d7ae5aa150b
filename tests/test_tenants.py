"""Multi-tenant front door: token buckets, bounded queues, typed
backpressure, weighted-fair lane drain, and interactive SLO preemption.

Everything timed runs under a VirtualClock, so bucket refills and flood
latencies are exact and the whole file costs real seconds.
"""
import threading

import pytest

from repro.core import Hydra, ProviderSpec, Task
from repro.core.admission import (
    AdmissionController,
    AdmissionError,
    TenantSpec,
    TokenBucket,
)
from repro.core.policy import apportion_budget
from repro.runtime.clock import virtual_time

from hypothesis import given, settings, strategies as st
from conftest import wait_until


# ---------------------------------------------------------------------------
# TokenBucket
# ---------------------------------------------------------------------------


def test_token_bucket_refill_and_reject():
    with virtual_time(auto_advance=False) as clock:
        b = TokenBucket(rate=10.0, burst=5.0)
        assert b.take(5)  # drain the burst
        assert not b.take(1)  # empty: reject, no partial charge
        assert b.available() == pytest.approx(0.0)
        clock.advance(0.3)  # 10/s * 0.3s = 3 tokens back
        assert b.available() == pytest.approx(3.0)
        assert b.take(3)
        assert not b.take(1)
        clock.advance(10.0)  # refill caps at burst, not rate * elapsed
        assert b.available() == pytest.approx(5.0)


def test_token_bucket_wait_hint_and_refund():
    with virtual_time(auto_advance=False) as clock:
        b = TokenBucket(rate=2.0, burst=4.0)
        assert b.take(4)
        # 3 tokens at 2/s: ready in 1.5 virtual seconds
        assert b.wait_hint_s(3) == pytest.approx(1.5)
        b.put(2)  # rollback refund
        assert b.available() == pytest.approx(2.0)
        b.put(100)  # refund never exceeds burst
        assert b.available() == pytest.approx(4.0)
        clock.advance(1.0)
        assert b.available() == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# AdmissionController
# ---------------------------------------------------------------------------


def test_admission_rate_limit_rejects_with_typed_error():
    with virtual_time(auto_advance=False) as clock:
        ctl = AdmissionController([TenantSpec(name="t", rate=5.0, burst=5.0)])
        ctl.admit([Task(tenant="t") for _ in range(5)])
        with pytest.raises(AdmissionError) as ei:
            ctl.admit([Task(tenant="t")])
        assert ei.value.tenant == "t"
        assert ei.value.reason == "rate_limited"
        assert ei.value.retry_after_s == pytest.approx(0.2)
        clock.advance(1.0)  # 5 tokens back
        ctl.admit([Task(tenant="t") for _ in range(5)])
        assert ctl.stats()["rejected"] == {"t:rate_limited": 1}


def test_admission_queue_bound_and_release_on_resolution():
    with virtual_time(auto_advance=False):
        ctl = AdmissionController([TenantSpec(name="t", max_queued=3)])
        tasks = [Task(tenant="t") for _ in range(3)]
        ctl.admit(tasks)
        assert ctl.held("t") == 3
        with pytest.raises(AdmissionError) as ei:
            ctl.admit([Task(tenant="t")])
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_s is None  # wait for completions, not a timer
        # resolution frees the slot, whatever the resolution path
        tasks[0].mark_done()
        tasks[1].mark_canceled()
        assert ctl.held("t") == 1
        ctl.admit([Task(tenant="t"), Task(tenant="t")])
        assert ctl.held("t") == 3
        # release is idempotent: an explicit release after the callback is a no-op
        ctl.release(tasks[0])
        assert ctl.held("t") == 3


def test_admission_is_all_or_nothing_across_tenants():
    """A rejection for one tenant's group must refund every other group the
    same call already charged — a partial admit would strand held slots (and
    tokens) on tasks that will never enter the system."""
    with virtual_time(auto_advance=False):
        ctl = AdmissionController(
            [
                TenantSpec(name="a", rate=100.0, burst=100.0, max_queued=10),
                TenantSpec(name="b", max_queued=2),
            ]
        )
        mixed = [Task(tenant="a") for _ in range(4)] + [Task(tenant="b") for _ in range(3)]
        with pytest.raises(AdmissionError) as ei:
            ctl.admit(mixed)
        assert ei.value.tenant == "b" and ei.value.reason == "queue_full"
        assert ctl.held("a") == 0 and ctl.held("b") == 0
        bucket = ctl._buckets["a"]
        assert bucket.available() == pytest.approx(100.0)  # tokens refunded
        assert all(not t.admitted for t in mixed)  # nothing committed


def test_admission_exempts_already_admitted_requeues():
    with virtual_time(auto_advance=False):
        ctl = AdmissionController([TenantSpec(name="t", rate=1.0, burst=1.0)])
        (t,) = [Task(tenant="t")]
        ctl.admit([t])
        # an internal requeue (retry / failover / staging re-gate) re-enters
        # without being re-charged: the bucket is empty and this must pass
        ctl.admit([t])
        assert ctl.held("t") == 1


def test_unconfigured_tenant_is_unlimited():
    with virtual_time(auto_advance=False):
        ctl = AdmissionController()
        ctl.admit([Task() for _ in range(10_000)])
        assert ctl.weight("anyone") == 1.0


def test_broker_dispatch_raises_typed_backpressure():
    with virtual_time(auto_advance=False):
        h = Hydra(
            pod_store="memory",
            streaming=True,
            batch_window=0.0,
            tenants=[TenantSpec(name="t", max_queued=8)],
        )
        h.register_provider(ProviderSpec(name="p", concurrency=2))
        h.dispatch([Task(kind="noop", tenant="t") for _ in range(8)])
        with pytest.raises(AdmissionError):
            h.dispatch([Task(kind="noop", tenant="t")])
        assert h.tenant_stats()["rejected"] == {"t:queue_full": 1}
        h.shutdown(wait=True)


# ---------------------------------------------------------------------------
# apportion_budget: weighted fairness, deficits, no starvation
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(
    st.integers(1, 64),  # budget per round
    st.lists(st.integers(0, 50), min_size=1, max_size=6),  # demands
    st.integers(0, 5),  # weight pattern selector
)
def test_apportion_never_starves_a_nonzero_weight_lane(budget, demands, wsel):
    """Property: over repeated rounds with carried deficits, every lane with
    demand > 0 and weight > 0 receives at least one grant — however skewed
    the weights — and per-round invariants hold."""
    n = len(demands)
    patterns = [
        [1.0] * n,
        [float(i + 1) for i in range(n)],
        [100.0] + [0.1] * (n - 1),
        [0.5] * n,
        [1000.0 if i == n - 1 else 1.0 for i in range(n)],
        [0.0 if i % 2 else 1.0 for i in range(n)],  # zero-weight lanes exist
    ]
    weights = patterns[wsel % len(patterns)]
    left = list(demands)
    served = [0] * n
    carry = [0.0] * n
    for _ in range(200):
        if not any(left[i] for i in range(n) if weights[i] > 0):
            break
        grants, carry = apportion_budget(budget, left, weights, carry)
        assert sum(grants) <= budget
        for i, g in enumerate(grants):
            assert 0 <= g <= left[i]
            left[i] -= g
            served[i] += g
    for i in range(n):
        if demands[i] > 0 and weights[i] > 0:
            assert served[i] > 0, (budget, demands, weights, served)
            assert left[i] == 0  # bounded demand fully drains, never wedges


def test_apportion_weight_ratio_shapes_the_split():
    grants, _ = apportion_budget(30, [100, 100], [2.0, 1.0], None)
    assert sum(grants) == 30
    assert grants[0] == 20 and grants[1] == 10


def test_apportion_weightless_lanes_round_robin():
    # all weights zero: plain round-robin rather than a division by zero
    grants, carry = apportion_budget(5, [10, 10], [0.0, 0.0], None)
    assert sum(grants) == 5 and min(grants) >= 2
    assert carry == [0.0, 0.0]


# ---------------------------------------------------------------------------
# Dispatcher drain order: SLO-class preemption + weighted fairness
# ---------------------------------------------------------------------------


def _virtual_finish_times(tasks):
    return [t.trace.last("exec_done") for t in tasks]


def test_interactive_preempts_queued_batch_backfill():
    """Late-arriving interactive tasks overtake thousands of already-queued
    batch tasks: queued (never running) backfill is preempted."""
    with virtual_time():
        h = Hydra(
            pod_store="memory",
            streaming=True,
            batch_window=0.0,
            tenants=[TenantSpec(name="serve", weight=1.0)],
        )
        h.register_provider(ProviderSpec(name="p", concurrency=4))
        flood = [
            Task(kind="sleep", duration=0.1, tenant="bulk", slo_class="batch")
            for _ in range(2000)
        ]
        h.dispatch(flood)
        # the flood is queued; now the interactive requests arrive LATE
        serve = [
            Task(kind="sleep", duration=0.1, tenant="serve", slo_class="interactive")
            for _ in range(20)
        ]
        h.dispatch(serve)
        for t in flood + serve:
            assert t.result(timeout=120) is None
        makespan = max(_virtual_finish_times(flood))
        serve_done = max(_virtual_finish_times(serve))
        # 2020 * 0.1s over 4 slots ~ 50s of virtual makespan; the 20
        # interactive tasks (0.5s of work) must clear almost immediately
        assert makespan > 20.0
        assert serve_done < 5.0, (serve_done, makespan)
        h.shutdown(wait=True)


def test_weighted_fair_split_between_batch_tenants():
    """Two batch tenants at 3:1 weight: early completions skew ~3:1 while
    both lanes stay live (no starvation of the light tenant)."""
    with virtual_time():
        h = Hydra(
            pod_store="memory",
            streaming=True,
            batch_window=0.0,
            tenants=[
                TenantSpec(name="heavy", weight=3.0),
                TenantSpec(name="light", weight=1.0),
            ],
        )
        h.register_provider(ProviderSpec(name="p", concurrency=8))
        heavy = [Task(kind="sleep", duration=0.2, tenant="heavy") for _ in range(400)]
        light = [Task(kind="sleep", duration=0.2, tenant="light") for _ in range(400)]
        h.dispatch(heavy)
        h.dispatch(light)
        for t in heavy + light:
            assert t.result(timeout=120) is None
        cutoff = max(max(_virtual_finish_times(heavy)), max(_virtual_finish_times(light))) / 2
        h_early = sum(1 for ts in _virtual_finish_times(heavy) if ts <= cutoff)
        l_early = sum(1 for ts in _virtual_finish_times(light) if ts <= cutoff)
        assert l_early > 0  # the light lane is never starved
        assert h_early > l_early * 1.5, (h_early, l_early)
        h.shutdown(wait=True)


def test_interactive_p99_bounded_under_10k_flood():
    """The front-door acceptance shape at test scale: a 10k-task batch flood
    must not blow up interactive p99 — the same steady trickle of requests
    finishes in near-unloaded time because the interactive lane drains
    first every round."""
    with virtual_time():
        def run(flood_n: int) -> float:
            h = Hydra(
                pod_store="memory",
                streaming=True,
                batch_window=0.0,
                max_batch=64,
                tenants=[TenantSpec(name="serve", weight=1.0)],
            )
            h.register_provider(ProviderSpec(name="p", concurrency=16))
            if flood_n:
                h.dispatch(
                    [
                        Task(kind="sleep", duration=0.1, tenant="bulk")
                        for _ in range(flood_n)
                    ]
                )
            lat = []
            clock_tasks = []
            for _ in range(50):
                t = Task(
                    kind="sleep", duration=0.2, tenant="serve", slo_class="interactive"
                )
                from repro.runtime.clock import get_clock

                t0 = get_clock().now()
                h.dispatch([t])
                t.add_done_callback(lambda _f, t=t, t0=t0: lat.append(
                    (t.trace.last("exec_done") or t0) - t0
                ))
                clock_tasks.append(t)
            for t in clock_tasks:
                assert t.result(timeout=600) is None
            assert h.dispatcher().drain(timeout=600)
            h.shutdown(wait=True)
            assert len(lat) == 50
            lat.sort()
            return lat[int(0.99 * len(lat)) - 1]

        unloaded = run(0)
        flooded = run(10_000)
        assert flooded <= max(3.0 * unloaded, unloaded + 1.0), (unloaded, flooded)
