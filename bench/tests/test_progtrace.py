"""The reduction of the engine's own spans, scopes and counters, on a
hand-made trace with known answers."""
import time
from types import SimpleNamespace

import numpy as np
import pytest
import tiny
from benchkit import progtrace, runner, scopes, spec, tracefile

MS = 1_000_000  # ns

HLO_64 = """HloModule jit_mc_grid_c4_n64, is_scheduled=true

%fused_computation.8 (param_0: s32[]) -> s32[] {
  %param_0 = s32[] parameter(0)
  ROOT %add.9 = s32[] add(%param_0, %param_0), metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_window_ff/add"}
}

%body (p: s32[]) -> s32[] {
  %copy-start.1 = (s32[], s32[], u32[]) copy-start(%p)
  %copy-done.1 = s32[] copy-done(%copy-start.1)
  %fusion.8 = s32[] fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.8
  %fusion.1 = s32[4,64]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_rotation/jit(sort)/sort" stack_frame_id=3}
  %while.2 = (s32[]) while(%p), condition=%c, body=%b, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_cycle_ff/while"}
  %fusion.2 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_cycle_ff/while/body/add"}
  %fusion.3 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_window_ff/cummax"}
  %fusion.4 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_advance/select_n"}
  %while.5 = (s32[]) while(%p), condition=%c, body=%b, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_micro/while"}
  %fusion.6 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_micro/while/body/closed_call/mc_advance/select_n"}
  ROOT %fusion.7 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while/body/mc_micro/while/body/argmax"}
}

ENTRY %main (a: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  ROOT %while.1 = (s32[], /*index=1*/s32[]) while(%a), condition=%c, body=%body, metadata={op_name="jit(mc_grid_c4_n64)/vmap()/while"}
}
"""
# Another bucket's program: the same instruction name in another scope.
HLO_128 = """HloModule jit_mc_grid_c4_n128, is_scheduled=true
  %fusion.3 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(mc_grid_c4_n128)/vmap()/while/body/mc_rotation/sort"}
"""

# One program's trip: the outer while, with the layers nested in it.
TRIP = [(0, 44, "while.1"), (0, 6, "fusion.1"), (6, 10, "while.2"),
        (7, 8, "fusion.2"), (16, 4, "fusion.3"), (20, 4, "fusion.4"),
        (24, 18, "while.5"), (25, 8, "fusion.6"), (33, 8, "fusion.7")]


def _hand():
    """Two sweeps, 0-100 and 100-200 ms. In each the program runs from 14
    (114) to 58 (158) ms inside its ``mc.launch`` span; a one-ms copy
    program at 99 ms starts outside any launch. The first sweep's
    rebuild holds a generation-2 collection."""
    spans, prog, ops, modules = [], [], [], []
    for k, t in enumerate((0, 100)):
        spans += [[t * MS, 100 * MS, "bench.sweep"],
                  [t * MS, 10 * MS, "bench.build"],
                  [(t + 10) * MS, 80 * MS, "bench.engine"],
                  [(t + 90) * MS, 10 * MS, "bench.rollup"]]
        ids = {"call": k, "C": 4, "N": 64, "B": 2}
        pack = 2 if k == 0 else 3
        rebuild = 28 if k == 0 else 22
        prog += [[(t + 10) * MS, pack * MS, "mc.pack", ids],
                 [(t + 10 + pack) * MS, (50 - pack) * MS, "mc.launch", ids],
                 [(t + 60) * MS, rebuild * MS, "mc.rebuild", ids],
                 [(t + 60 + rebuild) * MS, 1 * MS, "mc.rebuild",
                  {"call": k}]]
        modules.append([(t + 14) * MS, 44 * MS, "jit_mc_grid_c4_n64(77)"])
        ops += [[(t + 14 + s) * MS, d * MS, n] for s, d, n in TRIP]
    prog.append([62 * MS, 24 * MS, "mc.gc", {"generation": 2}])
    modules.append([99 * MS, 1 * MS, "jit_copy(5)"])
    ops.append([99 * MS, 1 * MS, "copy.9"])
    return {"devices": 1, "spans": sorted(spans), "ops": sorted(ops),
            "modules": sorted(modules),
            "prog_spans": sorted(prog, key=lambda r: r[:3])}


# Per sweep: one program of B=2 cells that ran 4 trips.
CELLS = [{"cycle_ff": 10, "window_ff": 20, "advance": 3, "micro": 7},
         {"cycle_ff": 2, "window_ff": 0, "advance": 1, "micro": 5}]


def _run(trace, by_layer=True):
    results = [SimpleNamespace(mc_stats=dict(
        iters=4, events=sum(c.values()),
        **({"events_by_layer": c} if by_layer else {}))) for c in CELLS]
    sweeps = [runner.Sweep(n_cells=2, device_cells=2, programs=[(2, 4, 6)],
                           results=list(results)) for _ in range(2)]
    return runner.RunView(sweeps=sweeps, trace=trace, compile={})


@pytest.fixture
def programs(monkeypatch):
    tables = {p.name: p for p in map(scopes.hlo_program, (HLO_64, HLO_128))}
    monkeypatch.setattr(scopes, "program_tables", lambda: tables)
    return tables


def _cut(tr, at_ms):
    """``tr`` as the profiler leaves it when it drops everything after
    ``at_ms``: an op is written when it ends, so ops (the loops too)
    that end later are gone, and the module event ends at the last op
    that is left."""
    tr = dict(tr, ops=[r for r in tr["ops"] if r[0] + r[1] <= at_ms * MS])
    last = max(r[0] + r[1] for r in tr["ops"])
    tr["modules"] = [[s, min(d, last - s), n] for s, d, n in tr["modules"]
                     if s < last]
    return tr


def test_hlo_scopes_take_the_outermost_scope():
    name, table, loops = scopes.hlo_program(HLO_64)
    assert name == "jit_mc_grid_c4_n64"
    assert loops == {"while.1"}     # the entry computation's loop
    assert table["fusion.1"] == "mc_rotation"
    assert table["fusion.6"] == "mc_micro"    # micro calls the advances
    assert table["while.1"] == scopes.LOOP
    # no metadata: a fusion takes its body's scope, a copy its user's
    assert table["fusion.8"] == table["copy-done.1"] == \
        table["copy-start.1"] == "mc_window_ff"
    assert scopes.scope_of("jit(f)/vmap()/while/cond/lt") == scopes.LOOP


def test_self_time_of_nested_ops():
    ops = [[s * MS, d * MS, n] for s, d, n in sorted(TRIP)]
    own = dict(zip((r[2] for r in ops), scopes.self_ns(ops)))
    assert own == {"while.1": 2 * MS, "fusion.1": 6 * MS,
                   "while.2": 2 * MS, "fusion.2": 8 * MS,
                   "fusion.3": 4 * MS, "fusion.4": 4 * MS,
                   "while.5": 2 * MS, "fusion.6": 8 * MS,
                   "fusion.7": 8 * MS}
    assert sum(own.values()) == 44 * MS


def test_self_ms_by_scope(programs):
    tr = _hand()
    got = scopes.read(_run(tr))
    assert (got["sweeps"], got["trips"]) == (2, 8)
    # the module event picks the n64 program's `fusion.3` (window), not
    # n128's (rotation); the copy program's op reads as `loop`
    assert got["ms"] == {"mc_rotation": 12.0, "mc_cycle_ff": 20.0,
                         "mc_window_ff": 8.0, "mc_advance": 8.0,
                         "mc_micro": 36.0, "loop": 5.0}
    busy, _ = tracefile.busy_share(tr)
    assert sum(got["ms"].values()) == busy / MS


def test_scope_and_counter_readers(programs):
    run = _run(_hand())
    got = {name: spec.metric_reader(name)(run) for name in (
        "rotation_ms_per_trip", "cycle_ff_ms_per_trip",
        "window_ff_ms_per_trip", "advance_ms_per_trip",
        "micro_ms_per_trip", "cycle_ff_events_per_ms",
        "window_ff_events_per_ms", "advance_events_per_ms",
        "micro_events_per_ms")}
    # 8 trips in the window; events over two sweeps of both cells
    assert got == pytest.approx({
        "rotation_ms_per_trip": 12 / 8, "cycle_ff_ms_per_trip": 20 / 8,
        "window_ff_ms_per_trip": 8 / 8, "advance_ms_per_trip": 8 / 8,
        "micro_ms_per_trip": 36 / 8, "cycle_ff_events_per_ms": 24 / 20,
        "window_ff_events_per_ms": 40 / 8, "advance_events_per_ms": 8 / 8,
        "micro_events_per_ms": 24 / 36}, rel=1e-12)
    # the five scopes and the loop add up to ms_per_trip
    ms = scopes.read(run)["ms"]
    assert sum(ms.values()) / 8 == pytest.approx(
        spec.metric_reader("ms_per_trip")(run), rel=1e-12)


def test_scope_readers_read_only_sweeps_the_trace_holds_whole(programs):
    """The profiler drops the second sweep's program 30 ms in: its loop
    event is never written. The readers then read the first sweep
    alone, its time, its trips and its cells' events."""
    run = _run(_cut(_hand(), 144))
    got = scopes.read(run)
    assert (got["sweeps"], got["trips"]) == (1, 4)
    assert got["ms"] == {"mc_rotation": 6.0, "mc_cycle_ff": 10.0,
                         "mc_window_ff": 4.0, "mc_advance": 4.0,
                         "mc_micro": 18.0, "loop": 3.0}
    assert got["events"] == {"cycle_ff": 12, "window_ff": 20,
                             "advance": 4, "micro": 12}
    assert spec.metric_reader("micro_ms_per_trip")(run) == 18 / 4
    assert spec.metric_reader("window_ff_events_per_ms")(run) == 20 / 4
    # cut inside the first sweep: no sweep is whole, nothing is read
    run = _run(_cut(_hand(), 50))
    assert scopes.read(run) is None
    assert spec.metric_reader("micro_ms_per_trip")(run) is None


def test_span_readers():
    run = _run(_hand())
    got = {name: spec.metric_reader(name)(run) for name in (
        "pack_ms", "launch_host_ms", "rebuild_ms", "gc_ms")}
    # launch: 48 ms and 47 ms, with 44 ms of device time inside each
    assert got == pytest.approx({"pack_ms": (2 + 3) / 2,
                                 "launch_host_ms": (4 + 3) / 2,
                                 "rebuild_ms": (29 + 23) / 2,
                                 "gc_ms": 24 / 2}, rel=1e-12)


def test_readers_find_nothing_in_a_program_without_them(monkeypatch):
    """A program with no spans, no kept executables and no per-layer
    counters: every new reader gives None, and none raises."""
    monkeypatch.setattr(scopes, "program_tables", lambda: None)
    tr = _hand()
    del tr["prog_spans"]
    run = _run(tr, by_layer=False)
    for name in ("pack_ms", "launch_host_ms", "rebuild_ms", "gc_ms",
                 "rotation_ms_per_trip", "micro_ms_per_trip",
                 "cycle_ff_events_per_ms", "advance_events_per_ms"):
        assert spec.metric_reader(name)(run) is None, name
        assert spec.metric_reader(name)(_run(None)) is None, name
    # a program without the engine's scope names
    monkeypatch.setattr(scopes, "SCOPES", ())
    assert scopes.read(_run(_hand())) is None


def test_gaps_named_by_the_span_that_held_them():
    tr = _hand()
    # 158-200 ms: the rebuild holds 23 of 42 ms; 58-99 ms: the rebuild
    # holds 29 and the collection inside it 24 of 41 ms, so the
    # collection names it
    assert progtrace.idle_gaps(tr, k=4) == [
        ["bench.engine/mc.rebuild", 0.042], ["bench.engine/mc.gc", 0.041],
        ["bench.build", 0.014], ["bench.build", 0.014]]
    # without program spans, the stage names of `tracefile.idle_gaps`
    del tr["prog_spans"]
    assert progtrace.idle_gaps(tr) == tracefile.idle_gaps(tr)


def test_clock_check_and_idle_cover():
    tr = _hand()
    # 88 of the 89 ms of program time start inside an mc.launch span
    assert progtrace.launch_clock_share(tr) == pytest.approx(88 / 89)
    # device-idle time inside bench.engine: 36 ms a sweep, of which
    # program spans cover 35 and 29 ms
    assert progtrace.engine_idle_cover(tr) == pytest.approx(64 / 72)


def test_unchanged_state_with_layer_counters_is_not_correct(
        tmp_path, monkeypatch):
    """`test_faults`' unchanged state, with the kernel's whole output
    (the per-layer counters too): the answers, not a missing key, make
    the run not correct."""
    import repro.mc.kernels as kernels
    from test_faults import state_unchanged

    real = kernels.run_grid

    def broken(arrival, service, n_tasks, n_fifo, limit, *, n_cores):
        out = state_unchanged(real, arrival, service, n_tasks, n_fifo,
                              limit, n_cores)
        out["events_by_layer"] = np.zeros((len(n_tasks), 4), np.int32)
        return out

    monkeypatch.setattr(kernels, "run_grid", broken)
    root = tiny.write(str(tmp_path))
    r = runner.run_cell(tiny.CELL, 99, 0.05, False, t_start=time.time(),
                        root=root, cache_dir=str(tmp_path / "jax"),
                        require_tpu=False)
    assert r["correct"] is False
    assert r["failed"] == 0
    assert r["checks"]["time_dev"]["value"] > 1e-9
