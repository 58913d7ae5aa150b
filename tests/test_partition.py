"""Partitioning invariants (hypothesis): every task in exactly one pod,
capacity respected, SCPP/MCPP pod counts correct."""
from hypothesis import given, settings, strategies as st

from repro.core.partition import partition
from repro.core.task import Resources, Task


def _tasks(n, cpus=None):
    return [
        Task(kind="noop", resources=Resources(cpus=(cpus[i] if cpus else 1)))
        for i in range(n)
    ]


@given(st.integers(1, 300), st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_mcpp_every_task_exactly_once(n, tpp):
    tasks = _tasks(n)
    pods = partition(tasks, "p", model="mcpp", tasks_per_pod=tpp)
    seen = [t.uid for p in pods for t in p.tasks]
    assert sorted(seen) == sorted(t.uid for t in tasks)
    assert len(seen) == len(set(seen))
    assert all(p.size <= tpp for p in pods)
    assert len(pods) == -(-n // tpp)


@given(st.integers(1, 200))
@settings(max_examples=30, deadline=None)
def test_scpp_one_task_per_pod(n):
    tasks = _tasks(n)
    pods = partition(tasks, "p", model="scpp")
    assert len(pods) == n
    assert all(p.size == 1 for p in pods)


@given(st.lists(st.integers(1, 8), min_size=1, max_size=120))
@settings(max_examples=50, deadline=None)
def test_binpack_capacity_respected(cpu_list):
    cap = Resources(cpus=16, accels=8, memory_mb=1 << 20)
    tasks = _tasks(len(cpu_list), cpus=cpu_list)
    pods = partition(tasks, "p", model="binpack", pod_capacity=cap)
    seen = [t.uid for p in pods for t in p.tasks]
    assert sorted(seen) == sorted(t.uid for t in tasks)
    for p in pods:
        assert sum(t.resources.cpus for t in p.tasks) <= cap.cpus


def test_binpack_rejects_oversized_task():
    import pytest

    cap = Resources(cpus=2, accels=0, memory_mb=128)
    t = Task(kind="noop", resources=Resources(cpus=4))
    with pytest.raises(ValueError):
        partition([t], "p", model="binpack", pod_capacity=cap)
