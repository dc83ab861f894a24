"""The per-layer metric ``live_slot_share``: the share of the task slots
a sweep's while-loop trips work over that were live at a trip's start."""
import json
import os
import time
from types import SimpleNamespace

import tiny
from benchkit import runner, spec

SEED = 2 ** 31 + 97531
NAME = "live_slot_share"


def _view(stats, sweeps=2):
    """A run of ``sweeps`` sweeps of the cells whose ``mc_stats`` are
    ``stats``; a cell the gate refused is None."""
    results = [None if s is None else SimpleNamespace(mc_stats=dict(s))
               for s in stats]
    return runner.RunView(sweeps=[SimpleNamespace(results=results)] * sweeps,
                          trace=None, compile={})


COUNTED = [{"iters": 100, "n_slots": 64, "live_slots": 1600},
           {"iters": 10, "n_slots": 4096, "live_slots": 4096},
           None]


def test_reader_leaves_out_a_program_without_the_counter():
    """An engine that counts no live slots: the metric is left out,
    nothing raises."""
    bare = [None if s is None else {"iters": s["iters"]} for s in COUNTED]
    assert spec.metric_reader(NAME)(_view(bare)) is None
    assert spec.metric_reader(NAME)(_view([None])) is None


def test_reader_arithmetic():
    # 2 sweeps x (1,600 + 4,096) live slots over
    # 2 x (100 x 64 + 10 x 4,096) slot-trips
    assert spec.metric_reader(NAME)(_view(COUNTED)) == \
        2 * 5696 / (2 * (6400 + 40960))
    full = [dict(s, live_slots=s["iters"] * s["n_slots"])
            for s in COUNTED[:2]]
    assert spec.metric_reader(NAME)(_view(full)) == 1.0


def test_no_trips_reads_nothing():
    empty = [{"iters": 0, "n_slots": 64, "live_slots": 0}]
    assert spec.metric_reader(NAME)(_view(empty)) is None
    assert spec.metric_reader(NAME)(_view(COUNTED, sweeps=0)) is None


def test_in_the_repo_benchmark_for_all_four_cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "device while-loop"
    assert entry["moves"] == "sim_tasks_per_s"
    assert entry["better"] == "higher" and entry["unit"] == "ratio"
    assert sorted(entry["workloads"]) == [
        "ep3_node.load_sweep", "paper_host.overload", "paper_host.util75",
        "paper_host_long.backlog"]


def test_tiny_traced_run_reads_the_share(tmp_path):
    """The made-up cell with the metric: on the CPU it reads in (0, 1)."""
    root = tiny.write(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({"name": NAME, "unit": "ratio"})
    with open(path, "w") as f:
        json.dump(bench, f)
    r = runner.run_cell(tiny.CELL, SEED, 0.05, True, t_start=time.time(),
                        root=root, cache_dir=str(tmp_path / "jax"),
                        require_tpu=False)
    assert r["correct"] is True
    assert 0 < r["metrics"][NAME]["value"] < 1
