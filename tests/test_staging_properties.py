"""Property tests: the batched placement-pricing path must be a pure
refactor of the per-site path.

``StagingService.transfer_cost_many`` exists only as a performance device
(one registry pass prices a whole bind batch, §Perf exp9); if it ever
disagrees with per-site ``transfer_cost_s``, the gravity policy silently
places against different costs inside a ``bind_bulk`` than outside one.
Swept here over randomized (inputs, targets) sets — including unknown and
replica-less datasets — both directly and through ``Policy.data_costs``
inside and outside ``bulk_scope()``."""
from __future__ import annotations

from repro.core.policy import make_policy
from repro.core.staging import StagingService
from repro.core.task import Task

from hypothesis import given, settings, strategies as st

SITES = ("jet2", "chi", "bridges2", "frontier")
DATASETS = (
    "forcing",  # replicated: shared + one cloud site
    "pre",  # single cloud replica
    "fit",  # single hpc replica
    "proj",  # shared only
    "lost",  # known but replica-less: inf cost, must be skipped
    "undeclared",  # unknown to the registry: charges nothing
)


def _service() -> StagingService:
    svc = StagingService(seed=0)
    for name, platform in (
        ("jet2", "cloud"),
        ("chi", "cloud"),
        ("bridges2", "hpc"),
        ("frontier", "hpc"),
    ):
        svc.register_site(name, platform)
    svc.registry.add("forcing", 2048.0, sites=["shared", "jet2"], pinned=True)
    svc.registry.add("pre", 512.0, sites=["chi"])
    svc.registry.add("fit", 64.0, sites=["bridges2"])
    svc.registry.add("proj", 1024.0, sites=["shared"])
    svc.registry.add("lost", 128.0, sites=[])
    return svc


class _Target:
    """The slice of a bind target Policy.data_costs relies on."""

    def __init__(self, name: str):
        self.name = name


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.sampled_from(DATASETS), min_size=0, max_size=4),
    st.lists(st.sampled_from(SITES), min_size=1, max_size=4),
)
def test_transfer_cost_many_matches_per_site(names, sites):
    svc = _service()
    batched = svc.transfer_cost_many(names, sites)
    assert set(batched) == set(sites)
    for site in sites:
        assert batched[site] == svc.transfer_cost_s(names, site)
        assert batched[site] >= 0.0
        assert batched[site] != float("inf")  # lost datasets are skipped


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.sampled_from(DATASETS), min_size=1, max_size=4),
    st.lists(st.sampled_from(SITES), min_size=1, max_size=4),
)
def test_data_costs_agree_inside_and_outside_bulk_scope(names, sites):
    svc = _service()
    policy = make_policy("data_gravity")
    policy.attach_staging(svc)
    task = Task(kind="noop", inputs=list(names))
    targets = [_Target(s) for s in sites]
    outside = policy.data_costs(task, targets)
    with policy.bulk_scope():
        first = policy.data_costs(task, targets)
        again = policy.data_costs(task, targets)
        assert again is first  # the batch cache actually served the repeat
    assert outside == first
    for site in sites:
        assert first[site] == svc.transfer_cost_s(task.inputs, site)


def test_resident_inputs_price_zero_everywhere_they_live():
    svc = _service()
    costs = svc.transfer_cost_many(["pre"], SITES)
    assert costs["chi"] == 0.0  # replica hit
    assert costs["jet2"] > 0.0  # same platform, different site: still a pull
