"""The batched engine's own instrumentation (``repro.mc.spans``).

One small grid (C=2, N=64: one program) runs once under
``jax.profiler`` on the CPU; the tests read its per-layer event
counters, the scopes in the executed program's HLO, and the host spans
in the trace. A cut of the paper's 50-core host (C=50, N=256: the deep
window) checks the firing and runqueue counters where many cores fire.
The instrumentation must not change a single observable.
"""
import gc
import glob
import os
import re

import numpy as np
import pytest

import repro
from conftest import (PAPER_POLICIES, assert_fires_bound_events, mk_tasks,
                      paper_scenario)
from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
from repro.mc.spans import EVENT_LAYERS, FIRE_LAYERS, SCOPES, gc_spans

C = 2
POLICIES = {"fifo": {}, "cfs": {}, "hybrid": {"n_fifo": 1}}


def _tasks(kind):
    """``dense``: 40 tasks arriving inside half a second on 2 cores, so
    runqueues go deep and the fast-forwards engage (the dense case of
    ``test_mc_equivalence``); ``sparse``: 12 tasks over 2 s."""
    rng = np.random.default_rng(3)
    if kind == "dense":
        arr, svc = rng.integers(0, 500, 40), rng.integers(50, 400, 40)
    else:
        arr, svc = rng.integers(0, 2000, 12), rng.integers(1, 400, 12)
    return mk_tasks(sorted(zip(arr.astype(float), svc.astype(float))))


def _scenario(kind, policy):
    return Scenario(workload=WorkloadSpec(kind="tasks",
                                          tasks=_tasks(kind)),
                    fleet=FleetSpec(cores_per_node=C),
                    policy=PolicySpec(name=policy, kw=POLICIES[policy]))


CELLS = [(kind, p) for kind in ("dense", "sparse") for p in POLICIES]


def _scatters_by_scope(hlo_text):
    """The scatter ops of an HLO text, counted by the outermost ``mc_``
    scope of their ``op_name`` (``loop`` where none)."""
    out = {}
    for line in hlo_text.splitlines():
        if not re.search(r"\bscatter\(", line):
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        name = m.group(1) if m else ""
        found = [(name.find(f"/{s}/"), s) for s in SCOPES
                 if f"/{s}/" in name]
        scope = min(found)[1] if found else "loop"
        out[scope] = out.get(scope, 0) + 1
    return out


def _events(trace_dir):
    """``(name, stats)`` of every host event named ``mc.*``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("mc.")]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The grid's results and the ``mc.*`` events of its one traced
    ``run_scenarios`` call."""
    import jax
    from repro.mc.engine import run_scenarios
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        results = run_scenarios([_scenario(*c) for c in CELLS])
    return results, _events(d)


def test_events_by_layer_add_up_to_events(traced):
    results, _ = traced
    for r in results:
        by = r.mc_stats["events_by_layer"]
        assert list(by) == list(EVENT_LAYERS)
        assert all(v >= 0 for v in by.values())
        assert sum(by.values()) == r.mc_stats["events"]


def test_fast_forwards_retire_events_on_dense_cfs(traced):
    results, _ = traced
    stats = results[CELLS.index(("dense", "cfs"))].mc_stats
    assert stats["iters"] < stats["events"]
    assert stats["events_by_layer"]["cycle_ff"] > 0
    assert stats["events_by_layer"]["window_ff"] > 0


@pytest.mark.parametrize("cell", CELLS, ids=["-".join(c) for c in CELLS])
def test_instrumented_engine_is_bit_identical(traced, cell):
    results, _ = traced
    scalar = repro.run(_scenario(*cell))
    batched = results[CELLS.index(cell)]

    def digest(res):
        return sorted((t.tid, repr(t.completion), repr(t.first_run),
                       repr(t.cpu_time), t.preemptions, t.ctx_switches,
                       t.migrations) for t in res.raw.tasks)

    assert digest(batched) == digest(scalar)
    strip = lambda row: {k: v for k, v in row.items() if k != "backend"}
    assert strip(batched.summary()) == strip(scalar.summary())


def test_executed_program_names_every_scope(traced):
    from jax import monitoring
    from repro.mc.kernels import executables
    compiles = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(on)
    try:
        ex = executables()[(C, 64, len(CELLS))]
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert compiles == []          # the program that ran, not a new one
    text = ex.as_text()
    assert "jit_mc_grid_c2_n64" in text
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


def test_run_writes_pack_launch_rebuild_spans(traced):
    _, events = traced
    names = [n for n, _ in events]
    for name in ("mc.pack", "mc.launch"):
        assert names.count(name) == 1, names
    # one per program and one for the results
    assert names.count("mc.rebuild") == 2
    calls = {st["call"] for _, st in events if "call" in st}
    assert len(calls) == 1
    for name, st in events:
        if name in ("mc.pack", "mc.launch"):
            assert (st["C"], st["N"], st["B"]) == (C, 64, len(CELLS))
        if name == "mc.launch":
            assert st["window"] == 64


@pytest.mark.parametrize("form", ["dense", "scatter"])
def test_launch_span_names_the_commit_form(traced, tmp_path, monkeypatch,
                                           form):
    """``mc.launch`` carries the stat ``commit``, the form of the window
    commit in the program it ran: the (2, 64) bucket's own dense
    select, or the scatters, forced by a zero slot limit."""
    import jax
    from repro.mc import kernels
    from repro.mc.engine import run_scenarios
    if form == "dense":
        _, events = traced
        B = len(CELLS)
    else:
        monkeypatch.setattr(kernels, "_DENSE_COMMIT_MAX_SLOTS", 0)
        monkeypatch.setattr(kernels, "_GRID_CACHE", {})
        run_scenarios([_scenario("dense", "cfs")])   # compiles
        with jax.profiler.trace(str(tmp_path)):
            run_scenarios([_scenario("dense", "cfs")])
        events = _events(str(tmp_path))
        B = 1
    assert [st["commit"] for name, st in events
            if name == "mc.launch"] == [form]
    with jax.enable_x64(True):
        text = kernels.grid_kernel(C, 64).lower(
            *kernels._RUN_ARGS[(C, 64, B)]).compile().as_text()
    assert ("mc_window_ff" in _scatters_by_scope(text)) == \
        (form == "scatter")


@pytest.mark.parametrize("n_slots", [64, 128, 256])
def test_window_commit_holds_no_scatter(n_slots):
    """The EP3 node's buckets (4 cores, 64 and 128 slots, window
    W = 64) and a deep one (256 slots, W = 256) commit the window by
    the dense select: their compiled programs hold no scatter in
    ``mc_window_ff``, while the layers that keep theirs show them.
    Buckets of more than 2,048 slots, the paper host's one-minute trace
    (8,192) among them, keep the scatters; none is compiled here."""
    import jax
    import jax.numpy as jnp
    from repro.mc.kernels import grid_kernel, window_commit, window_len
    assert window_commit(n_slots) == "dense"
    assert window_len(n_slots) == (64 if n_slots <= 128 else 256)
    assert window_commit(2048) == "dense"
    assert window_commit(4096) == window_commit(8192) == "scatter"
    f64 = jax.ShapeDtypeStruct((2, n_slots), jnp.float64)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    with jax.enable_x64(True):
        text = grid_kernel(4, n_slots).lower(
            f64, f64, i32, i32, jax.ShapeDtypeStruct((2,), jnp.float64)
        ).compile().as_text()
    scatters = _scatters_by_scope(text)
    assert "mc_window_ff" not in scatters
    assert scatters.get("mc_cycle_ff", 0) > 0


def test_gc_spans_wrap_a_collection(tmp_path):
    import jax
    hooks = len(gc.callbacks)
    with jax.profiler.trace(str(tmp_path)):
        with gc_spans():
            assert len(gc.callbacks) == hooks + 1
            gc.collect()
        gc.collect()        # after the block: no span
    assert len(gc.callbacks) == hooks     # the hook is gone
    gen2 = [st for name, st in _events(str(tmp_path))
            if name == "mc.gc" and st["generation"] == 2]
    assert gen2 == [{"generation": 2}]


# -- the paper's host: 50 cores, a cut the CPU runs in seconds --------------

@pytest.fixture(scope="module")
def paper():
    """The three paper-host cells as one batch, by policy."""
    from repro.mc.engine import run_scenarios
    return dict(zip(PAPER_POLICIES, run_scenarios(
        [paper_scenario(p) for p in PAPER_POLICIES])))


@pytest.mark.parametrize("policy", list(PAPER_POLICIES))
def test_paper_host_fires_bound_events(paper, policy):
    stats = paper[policy].mc_stats
    assert stats["window_len"] == 256
    assert list(stats["fires_by_layer"]) == list(FIRE_LAYERS)
    assert_fires_bound_events(paper[policy])


def test_paper_host_counts_runqueues_by_policy(paper):
    """fifo keeps no runqueue and fires neither fast-forward; cfs spreads
    the burst over 50 runqueues and the cycle engine fires; the hybrid's
    25 CFS cores fill only by the FIFO cores' hand-off."""
    fifo, cfs, hybrid = (paper[p] for p in PAPER_POLICIES)
    assert fifo.mc_stats["rq_depth_max"] == 0
    assert fifo.mc_stats["fires_by_layer"] == {"cycle_ff": 0,
                                               "window_ff": 0}
    assert cfs.mc_stats["rq_depth_max"] > 0
    assert cfs.mc_stats["fires_by_layer"]["cycle_ff"] > 0
    assert hybrid.mc_stats["rq_depth_max"] > 0
    assert sum(t.migrations for t in hybrid.raw.tasks) > 0


def test_paper_host_cell_alone_matches_the_batch(paper):
    """The cfs cell run alone (B = 1) reads the counters it read in the
    batch of three, but for the batch's ``window_trips``: alone it
    counts only the trips on which it fired the window itself."""
    from repro.mc.engine import run_scenarios
    alone, = run_scenarios([paper_scenario("cfs")])
    mine, theirs = dict(alone.mc_stats), dict(paper["cfs"].mc_stats)
    assert mine.pop("window_trips") <= theirs.pop("window_trips")
    assert mine == theirs


@pytest.mark.parametrize("policy", list(PAPER_POLICIES))
def test_paper_host_is_bit_identical(paper, policy):
    scalar = repro.run(paper_scenario(policy))

    def digest(res):
        return sorted((t.tid, repr(t.completion), repr(t.first_run),
                       repr(t.cpu_time), t.preemptions, t.ctx_switches,
                       t.migrations) for t in res.raw.tasks)

    assert digest(paper[policy]) == digest(scalar)
