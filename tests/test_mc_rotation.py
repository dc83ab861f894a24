"""The rotation: a 7-entry pop prefix (``kernels.rotation_prefix``),
with the full sort of every runqueue (``kernels.rotation_sorted``) only
on the trips where some cell of the batch is deeper.

The prefix must give the sort's order on every entry a reader can see,
the engine must give the scalar engine's bits, and the program must
hold its sort inside a real conditional, not under a select that runs
both sides.
"""
import re

import numpy as np
import pytest

import repro
from conftest import PAPER_POLICIES, commit_battery, mk_tasks, paper_scenario
from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec

def _digest(res):
    return sorted((t.tid, repr(t.completion), repr(t.first_run),
                   repr(t.cpu_time), t.preemptions, t.ctx_switches,
                   t.migrations) for t in res.raw.tasks)


def _random_state(rng, n_cores, n_slots):
    """A state of ``n_slots`` tasks over ``n_cores`` runqueues, most of
    them queued, core c's share falling as 1 / (c + 1)^2 so that queues
    run from empty to far past 7; vruntimes are drawn from three values
    and seqs from a narrow range, so both tie-breaks decide many pops."""
    stat = rng.choice([0, 1, 2, 3, 4], n_slots,
                      p=[0.1, 0.05, 0.7, 0.1, 0.05]).astype(np.int32)
    share = 1.0 / np.arange(1, n_cores + 1) ** 2
    qcore = rng.choice(n_cores, n_slots, p=share / share.sum())
    qcore = qcore.astype(np.int32)
    vr = (3.0 * rng.integers(1, 4, n_slots)).astype(np.float64)
    seq = rng.integers(0, 8, n_slots).astype(np.int32)
    return stat, qcore, vr.view(np.int64), seq


@pytest.mark.parametrize("n_cores,n_slots",
                         [(4, 64), (8, 128), (50, 256), (50, 1024)])
def test_prefix_matches_sort_below_rqn(n_cores, n_slots):
    """On every entry below a core's queue length the 7 pops give the
    sorted order; every entry is a valid task index."""
    import jax
    from repro.mc import kernels
    P = kernels._CYCLE_K - 1
    sort = jax.jit(kernels.rotation_sorted, static_argnums=4)
    prefix = jax.jit(kernels.rotation_prefix, static_argnums=(4, 5))
    rng = np.random.default_rng(16)
    with jax.enable_x64(True):
        for _ in range(10):
            stat, qcore, vr, seq = _random_state(rng, n_cores, n_slots)
            want = np.asarray(sort(stat, qcore, vr, seq, n_cores))
            got = np.asarray(prefix(stat, qcore, vr, seq, n_cores, P))
            assert got.shape == want.shape == (n_cores, n_slots)
            assert ((got >= 0) & (got < n_slots)).all()
            rqn = np.bincount(qcore[stat == 2], minlength=n_cores)
            assert rqn.min() < P < rqn.max()   # short and deep queues
            for c in range(n_cores):
                k = min(rqn[c], P)
                np.testing.assert_array_equal(got[c, :k], want[c, :k])


@pytest.fixture(scope="module")
def batteries():
    """The two commit batteries and the paper-host cut, each run as one
    batch of the engine."""
    from repro.mc.engine import run_scenarios
    return {"w64": run_scenarios(commit_battery(False)),
            "w256": run_scenarios(commit_battery(True)),
            "paper": run_scenarios(
                [paper_scenario(p) for p in PAPER_POLICIES])}


def _battery(name):
    if name == "paper":
        return [paper_scenario(p) for p in PAPER_POLICIES]
    return commit_battery(name == "w256")


@pytest.mark.parametrize("battery", ["w64", "w256", "paper"])
def test_rotation_is_bit_identical(batteries, battery):
    """Every cell matches the scalar engine bit for bit, cpu_time
    included."""
    for sc, got in zip(_battery(battery), batteries[battery]):
        assert _digest(got) == _digest(repro.run(sc))


def test_deep_trips_count_only_deep_cells(batteries):
    """A cell is deep on a trip where one of its runqueues holds more
    than 7 tasks: never a fifo cell; the deep battery's cfs cell on
    some trips; the paper-host cut's cfs cell, whose runqueues stay
    within 7, on none."""
    from repro.mc import kernels
    for battery, results in batteries.items():
        for sc, r in zip(_battery(battery), results):
            st = r.mc_stats
            assert 0 <= st["rotation_deep_trips"] <= st["iters"]
            assert (st["rotation_deep_trips"] > 0) == \
                (st["rq_depth_max"] > kernels._CYCLE_K - 1)
            if sc.policy.name == "fifo":
                assert st["rotation_deep_trips"] == 0
    assert batteries["w256"][1].mc_stats["rotation_deep_trips"] > 0
    assert batteries["paper"][1].mc_stats["rotation_deep_trips"] == 0


def _mixed_batch():
    """A deep cfs cell (40 tasks inside half a second on 2 cores) beside
    fifo and hybrid cells of 12 tasks over 2 s, in one (2, 64) bucket."""
    rng = np.random.default_rng(3)
    dense = (rng.integers(0, 500, 40), rng.integers(50, 400, 40))
    sparse = (rng.integers(0, 2000, 12), rng.integers(1, 400, 12))
    kw = {"cfs": {}, "fifo": {}, "hybrid": {"n_fifo": 1}}
    return [Scenario(workload=WorkloadSpec(kind="tasks", tasks=mk_tasks(
                sorted(zip(arr.astype(float), svc.astype(float))))),
                     fleet=FleetSpec(cores_per_node=2),
                     policy=PolicySpec(name=policy, kw=kw[policy]))
            for policy, (arr, svc) in (("cfs", dense), ("fifo", sparse),
                                       ("hybrid", sparse))]


def test_mixed_batch_sorts_for_its_deep_cell():
    """One deep cell makes its batch sort on its deep trips; the shallow
    cells count none of them, and every cell matches the scalar engine
    bit for bit and reads the counters it reads alone."""
    from repro.mc import kernels
    from repro.mc.engine import run_scenarios
    results = run_scenarios(_mixed_batch())
    deep, *shallow = (r.mc_stats for r in results)
    assert deep["rotation_deep_trips"] > 0
    assert deep["rq_depth_max"] > kernels._CYCLE_K
    for st in shallow:
        assert st["rotation_deep_trips"] == 0
    for sc, r in zip(_mixed_batch(), results):
        assert _digest(r) == _digest(repro.run(sc))
    alone, = run_scenarios(_mixed_batch()[:1])
    assert alone.mc_stats == deep


def test_window_trips_count_the_batchs_window(batteries):
    """A batch runs the window on a trip where some core of some cell
    retires a chunk by it: each cell counts those trips while it is
    live, at least one for each trip it fires on, at most its trips,
    and never all of them: every battery skips the window on some
    trips. The paper-host cut's cells skip it on most; a fifo cell,
    which never fires, counts the trips its batch's cfs cell fires on."""
    for battery, results in batteries.items():
        n_cores = 50 if battery == "paper" else 2
        for r in results:
            st = r.mc_stats
            fired = st["fires_by_layer"]["window_ff"]
            assert 0 <= st["window_trips"] < st["iters"]
            assert fired <= n_cores * st["window_trips"]
            assert (fired > 0) <= (st["window_trips"] > 0)
    for r in batteries["paper"]:
        assert 2 * r.mc_stats["window_trips"] < r.mc_stats["iters"]
    fifo, cfs = (r.mc_stats for r in batteries["w64"][:2])
    assert fifo["fires_by_layer"]["window_ff"] == 0
    assert 0 < fifo["window_trips"] <= cfs["window_trips"]


def test_mixed_batch_counts_window_trips_of_the_batch():
    """Each cell of the mixed batch reads alone every counter it reads
    in the batch but ``window_trips``, which counts at least the trips
    it runs the window on alone, where only it can fire, and at most
    its trips."""
    from repro.mc.engine import run_scenarios
    batch = run_scenarios(_mixed_batch())
    for sc, r in zip(_mixed_batch(), batch):
        alone, = run_scenarios([sc])
        mine, theirs = dict(alone.mc_stats), dict(r.mc_stats)
        own, got = mine.pop("window_trips"), theirs.pop("window_trips")
        assert mine == theirs
        assert own <= got <= r.mc_stats["iters"]
        assert (alone.mc_stats["fires_by_layer"]["window_ff"] > 0) \
            <= (own > 0)
        assert _digest(alone) == _digest(r)
    assert batch[0].mc_stats["fires_by_layer"]["window_ff"] > 0


def _computations(hlo_text):
    """``{name: [instruction lines]}`` of an HLO module's text."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.strip() != "}":
            comps[name].append(line)
    return comps


def _reach(comps, root):
    """The computations ``root`` calls, itself included."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += [n for n in re.findall(r"%([\w.\-]+)", line)
                     if n in comps]
    return seen


@pytest.fixture(scope="module")
def program():
    """The computations of the compiled (4, 64) program at B = 2."""
    import jax
    import jax.numpy as jnp
    from repro.mc import kernels
    f64 = jax.ShapeDtypeStruct((2, 64), jnp.float64)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    with jax.enable_x64(True):
        text = kernels.grid_kernel(4, 64).lower(
            f64, f64, i32, i32, jax.ShapeDtypeStruct((2,), jnp.float64)
        ).compile().as_text()
    return _computations(text)


def _branches(comps, scope):
    """The (false, true) branch computations of the one conditional
    under ``scope``; ``lax.cond`` lowers its true branch last."""
    cond, = [line for lines in comps.values() for line in lines
             if re.search(r"\bconditional\(", line)
             and f"/{scope}/" in line]
    return re.search(r"branch_computations=\{%([\w.\-]+), "
                     r"%([\w.\-]+)\}", cond).groups()


def test_rotation_sorts_only_inside_the_deep_branch(program):
    """The compiled program: the rotation holds one conditional, the
    sorts sit only in its second branch (the deep one) and nothing of
    the first branch sorts."""
    comps = program
    where = {c: line for c, lines in comps.items() for line in lines
             if re.search(r"\bsort\(", line)}
    assert where and all("/mc_rotation/" in line for line in where.values())
    shallow, deep = _branches(comps, "mc_rotation")
    assert set(where) <= _reach(comps, deep)
    assert not set(where) & _reach(comps, shallow)


def test_window_runs_only_inside_its_branch(program):
    """The compiled program: the window's end-time scan (a while loop
    under ``mc_window_ff``) and its dense select (every op over the
    ownership mask [B, C, W + 1, N] = [2, 4, 65, 64]) sit only in the
    second branch of the window's one conditional, and nothing of the
    first branch holds either."""
    comps = program
    scan = {c for c, lines in comps.items() for line in lines
            if re.search(r"\bwhile\(", line) and "/mc_window_ff/" in line}
    select = {c for c, lines in comps.items() for line in lines
              if "[2,4,65,64]" in line}
    assert scan and select
    skip, run = _branches(comps, "mc_window_ff")
    assert scan | select <= _reach(comps, run)
    assert not (scan | select) & _reach(comps, skip)
