"""The per-layer metric ``window_run_share``: the share of a sweep's
while-loop trips on which a cell's batch ran the window fast-forward."""
import json
import os
import time
from types import SimpleNamespace

import tiny
from benchkit import runner, spec

SEED = 2 ** 31 + 54321
NAME = "window_run_share"


def _view(stats, sweeps=2):
    """A run of ``sweeps`` sweeps of the cells whose ``mc_stats`` are
    ``stats``; a cell the gate refused is None."""
    results = [None if s is None else SimpleNamespace(mc_stats=dict(s))
               for s in stats]
    return runner.RunView(sweeps=[SimpleNamespace(results=results)] * sweeps,
                          trace=None, compile={})


COUNTED = [{"iters": 100, "window_trips": 17},
           {"iters": 60, "window_trips": 0},
           None]


def test_reader_leaves_out_a_program_without_the_counter():
    """The parent's engine runs the window on every trip and counts
    nothing: the metric is left out, nothing raises."""
    bare = [None if s is None else {"iters": s["iters"]} for s in COUNTED]
    assert spec.metric_reader(NAME)(_view(bare)) is None
    assert spec.metric_reader(NAME)(_view([None])) is None


def test_reader_arithmetic():
    # 2 sweeps x 17 window trips over 2 x (100 + 60) trips
    assert spec.metric_reader(NAME)(_view(COUNTED)) == 34 / 320
    idle = [dict(s, window_trips=0) for s in COUNTED[:2]]
    assert spec.metric_reader(NAME)(_view(idle)) == 0.0


def test_no_trips_reads_nothing():
    empty = [{"iters": 0, "window_trips": 0}]
    assert spec.metric_reader(NAME)(_view(empty)) is None
    assert spec.metric_reader(NAME)(_view(COUNTED, sweeps=0)) is None


def test_in_the_repo_benchmark_for_both_cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "device while-loop"
    assert entry["moves"] == "sim_tasks_per_s"
    assert sorted(entry["workloads"]) == ["ep3_node.load_sweep",
                                          "paper_host.overload"]


def test_tiny_traced_run_reads_the_share(tmp_path):
    """The made-up cell on 2 cores in place of 4, at loads 6 and 12 in
    place of 1 and 3, so that the window fires on some trips and not on
    others: on the CPU the metric reads in (0, 1)."""
    root = tiny.write(str(tmp_path))
    for name, change in (
            ("BENCHMARK.json", lambda b: b["per_layer"].append(
                {"name": NAME, "unit": "ratio"})),
            ("bench/configs/tiny_host.json",
             lambda c: c.update(cores_per_node=2)),
            ("bench/traffic/mix.json",
             lambda t: t.update(loads=[6.0, 12.0]))):
        path = os.path.join(root, name)
        with open(path) as f:
            obj = json.load(f)
        change(obj)
        with open(path, "w") as f:
            json.dump(obj, f)
    r = runner.run_cell(tiny.CELL, SEED, 0.05, True, t_start=time.time(),
                        root=root, cache_dir=str(tmp_path / "jax"),
                        require_tpu=False)
    assert r["correct"] is True
    assert 0 < r["metrics"][NAME]["value"] < 1
