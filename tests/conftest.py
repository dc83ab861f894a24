import json
import os

import numpy as np
import pytest

from repro.core.events import Task
from repro.traces import TraceSpec, generate_workload

# Per-module wall-clock accounting, written as a benchmark-style
# artifact when REPRO_TEST_TIMINGS names a path — the nightly slow
# tier exports its timings into the trend dashboard
# (benchmarks.trend_report kind "test_timings"), so a test module
# quietly doubling its runtime shows up as a trend regression instead
# of an unexplained nightly slowdown.
_TIMINGS: dict = {}


def pytest_runtest_logreport(report):
    if not os.environ.get("REPRO_TEST_TIMINGS") or report.when != "call":
        return
    module = report.nodeid.split("::")[0]
    tier = "slow" if "slow" in report.keywords else "fast"
    acc = _TIMINGS.setdefault((module, tier), [0, 0.0])
    acc[0] += 1
    acc[1] += report.duration


def pytest_sessionfinish(session, exitstatus):
    path = os.environ.get("REPRO_TEST_TIMINGS")
    if not path or not _TIMINGS:
        return
    rows = [{"module": module, "tier": tier, "n_tests": n,
             "wall_s": round(wall, 3)}
            for (module, tier), (n, wall) in sorted(_TIMINGS.items())]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"rows": rows}, f, indent=2)


def pytest_addoption(parser):
    parser.addoption(
        "--slow", action="store_true", default=False,
        help="also run tests marked slow (Pallas kernels, MC hypothesis "
             "sweeps)")


def pytest_collection_modifyitems(config, items):
    """Two-tier suite: the default tier must stay fast (a few minutes) so
    it is practical to run on every change; ``--slow`` opts into the
    Pallas kernel tests and the MC hypothesis sweeps (CI runs it nightly)."""
    if config.getoption("--slow"):
        return
    skip = pytest.mark.skip(reason="slow tier: pass --slow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def small_workload():
    """Downscaled 30s Azure-like workload (fast enough for CFS sims)."""
    spec = TraceSpec(minutes=1, invocations_per_min=1500, n_functions=80,
                     seed=7)
    w = generate_workload(spec)
    return [t for t in w.tasks if t.arrival < 30_000]


def assert_fires_bound_events(res):
    """A batched result's firing counters against its event counters:
    each (trip, core) firing of a multi-event layer retires at least one
    event and at most W (``window_len``) of them, so per layer
    ``fires <= events <= fires * W``: no firing, no event."""
    from repro.mc.spans import FIRE_LAYERS
    st = res.mc_stats
    for layer in FIRE_LAYERS:
        fires = st["fires_by_layer"][layer]
        events = st["events_by_layer"][layer]
        assert fires <= events <= fires * st["window_len"], (layer, st)


def commit_battery(deep=False):
    """fifo, cfs and hybrid on 2 cores, with fresh tasks on every call.
    By default a dense and a sparse task set: one (C=2, N=64) program,
    whose window fast-forward (W = 64) retires chunks on the dense cfs
    cell. ``deep``: 150 tasks inside a second, one (2, 256) program
    whose window holds W = 256 chunks."""
    from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
    rng = np.random.default_rng(3)
    if deep:
        sets = {"deep": (rng.integers(0, 1000, 150),
                         rng.integers(50, 400, 150))}
    else:
        sets = {"dense": (rng.integers(0, 500, 40),
                          rng.integers(50, 400, 40)),
                "sparse": (rng.integers(0, 2000, 12),
                           rng.integers(1, 400, 12))}
    kw = {"fifo": {}, "cfs": {}, "hybrid": {"n_fifo": 1}}
    return [Scenario(workload=WorkloadSpec(kind="tasks", tasks=mk_tasks(
                sorted(zip(arr.astype(float), svc.astype(float))))),
                     fleet=FleetSpec(cores_per_node=2),
                     policy=PolicySpec(name=policy, kw=kw[policy]))
            for arr, svc in sets.values() for policy in kw]


PAPER_POLICIES = {"fifo": {}, "cfs": {},
                  "hybrid": {"n_fifo": 25, "time_limit_ms": 1633.0}}


def paper_scenario(policy):
    """The paper host (50 cores, the paper's trace rates, its static
    hybrid) on the invocations of the trace's first 0.15 s: 245 tasks,
    one (50, 256) bucket, so the window holds W = 256 chunks."""
    from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
    tasks = [t for t in generate_workload(TraceSpec(seed=0)).tasks
             if t.arrival < 150.0]
    return Scenario(workload=WorkloadSpec(kind="tasks", tasks=tasks),
                    fleet=FleetSpec(cores_per_node=50),
                    policy=PolicySpec(name=policy,
                                      kw=PAPER_POLICIES[policy]))


def mk_tasks(specs):
    """specs: list of (arrival, service[, mem]) tuples."""
    out = []
    for i, s in enumerate(specs):
        arrival, service = s[0], s[1]
        mem = s[2] if len(s) > 2 else 256
        out.append(Task(tid=i, arrival=float(arrival),
                        service=float(service), mem_mb=mem,
                        deadline=arrival + 2.0 * service))
    return out
