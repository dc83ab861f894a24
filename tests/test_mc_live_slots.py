"""The kernel's ``live_slots`` counter: the task slots live (arrived and
not done) at the start of each while-loop trip, summed over the trips.

It is a cell's own count, so it reads the same whatever batch the cell
runs in and under either form of the window's commit; it is bounded by
the live tasks a trip can hold, and a cell whose every task finishes on
the trip it arrives on counts none.
"""
import numpy as np
import pytest

from conftest import commit_battery, mk_tasks
from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec


def _run(scenarios):
    from repro.mc.engine import run_scenarios
    return run_scenarios(scenarios)


@pytest.fixture(scope="module")
def forms():
    """Both commit batteries under each form of the window's commit, by
    form and ``deep``: the dense select the buckets take, and the
    scatters, forced by a zero slot limit into a program cache of their
    own."""
    from repro.mc import kernels
    out = {"dense": {deep: _run(commit_battery(deep))
                     for deep in (False, True)}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernels, "_DENSE_COMMIT_MAX_SLOTS", 0)
        mp.setattr(kernels, "_GRID_CACHE", {})
        out["scatter"] = {deep: _run(commit_battery(deep))
                          for deep in (False, True)}
    return out


@pytest.mark.parametrize("deep", [False, True], ids=["w64", "w256"])
@pytest.mark.parametrize("form", ["dense", "scatter"])
def test_live_slots_bounded_by_trips_times_tasks(forms, form, deep):
    """Each trip starts with at most every task of the cell live, and
    every battery cell has tasks waiting at some trip's start."""
    from repro.mc.engine import _bucket
    for sc, r in zip(commit_battery(deep), forms[form][deep]):
        st = r.mc_stats
        n = len(sc.workload.tasks)
        assert st["n_slots"] == _bucket(n)
        assert 0 < st["live_slots"] <= st["iters"] * n


@pytest.mark.parametrize("deep", [False, True], ids=["w64", "w256"])
def test_live_slots_same_under_both_commit_forms(forms, deep):
    dense, scatter = (forms[f][deep] for f in ("dense", "scatter"))
    for d, s in zip(dense, scatter):
        assert d.mc_stats["live_slots"] == s.mc_stats["live_slots"]
        assert d.mc_stats["iters"] == s.mc_stats["iters"]


def test_live_slots_same_alone_as_in_a_batch(forms):
    """Each cell of the (2, 256) battery run alone counts what it
    counted in the batch of three."""
    for sc, r in zip(commit_battery(True), forms["dense"][True]):
        alone, = _run([sc])
        assert alone.mc_stats["live_slots"] == r.mc_stats["live_slots"]


def test_fleet_sums_its_nodes():
    """A round-robin fleet of two nodes counts the sum of its nodes'
    cells, each run alone on its share of the tasks."""
    from repro.mc.engine import replay_assignments
    rng = np.random.default_rng(19)
    specs = sorted(zip(rng.integers(0, 600, 30).astype(float),
                       rng.integers(50, 400, 30).astype(float)))
    fleet = Scenario(workload=WorkloadSpec(kind="tasks",
                                           tasks=mk_tasks(specs)),
                     fleet=FleetSpec(n_nodes=2, cores_per_node=2,
                                     dispatcher="round_robin"),
                     policy=PolicySpec(name="cfs"))
    sel = replay_assignments(fleet, len(specs))
    nodes = [Scenario(workload=WorkloadSpec(kind="tasks", tasks=mk_tasks(
                 [s for s, i in zip(specs, sel) if i == node])),
                      fleet=FleetSpec(cores_per_node=2),
                      policy=PolicySpec(name="cfs"))
             for node in (0, 1)]
    got, = _run([fleet])
    alone = _run(nodes)
    assert got.mc_stats["live_slots"] == \
        sum(r.mc_stats["live_slots"] for r in alone) > 0
    assert got.mc_stats["iters"] == sum(r.mc_stats["iters"] for r in alone)


@pytest.mark.parametrize("policy", ["fifo", "cfs"])
def test_no_live_slot_when_tasks_finish_on_their_arrival_trip(policy):
    """Four short tasks a second apart on one core: each arrives and
    completes before the next arrives, and the trip's chain of events
    retires all eight on the first trip, so no trip starts with a task
    live."""
    tasks = mk_tasks([(1000.0 * k, 5.0) for k in range(4)])
    r, = _run([Scenario(workload=WorkloadSpec(kind="tasks", tasks=tasks),
                        fleet=FleetSpec(cores_per_node=1),
                        policy=PolicySpec(name=policy))])
    assert r.mc_stats["iters"] >= 1
    assert r.mc_stats["events"] == 8
    assert r.mc_stats["live_slots"] == 0
