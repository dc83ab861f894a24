"""The paper host's cell, ``paper_host.overload``, and the two per-layer
metrics that read the window fast-forward's firing counters."""
import json
import os
import time
from types import SimpleNamespace

import pytest
import tiny
from benchkit import runner, scopes, spec, traffic

SEED = 2 ** 31 + 12345
NEW = ("window_fill", "window_ms_per_fire")


def test_paper_cell_plans_one_deep_dense_program():
    """From the repo's ``BENCHMARK.json``: one trace of 513-1,024
    invocations under fifo, cfs and hybrid, all in one (50, 1,024)
    bucket, whose window scans 256 chunks and commits by the select."""
    from repro.mc.engine import _bucket
    from repro.mc.kernels import window_commit, window_len
    cell = spec.load_cell("paper_host.overload")
    assert cell.chips == 1 and cell.config["cores_per_node"] == 50
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    plan = traffic.plan(cell.config, cell.traffic, SEED)
    assert sorted(c.policy for c in plan.cells) == ["cfs", "fifo", "hybrid"]
    (tr,) = plan.traces.values()
    n = len(tr["arrival"])
    assert 513 <= n <= 1024
    assert _bucket(n) == 1024
    assert window_len(1024) == 256 and window_commit(1024) == "dense"


def _view(stats, sweeps=2):
    """A run of ``sweeps`` sweeps of two cells whose ``mc_stats`` are
    ``stats``."""
    results = [SimpleNamespace(mc_stats=dict(s)) for s in stats]
    return runner.RunView(sweeps=[SimpleNamespace(results=results)] * sweeps,
                          trace=None, compile={})


COUNTED = [{"events_by_layer": {"window_ff": 30}, "window_len": 64,
            "fires_by_layer": {"cycle_ff": 9, "window_ff": 3}},
           {"events_by_layer": {"window_ff": 10}, "window_len": 64,
            "fires_by_layer": {"cycle_ff": 0, "window_ff": 1}}]


@pytest.fixture
def window_ms(monkeypatch):
    """``scopes.read`` as over two whole sweeps holding 12 ms of
    ``mc_window_ff`` self time."""
    monkeypatch.setattr(scopes, "read", lambda run: {
        "ms": {"mc_window_ff": 12.0}, "sweeps": 2, "trips": 50,
        "events": None})


def test_readers_leave_out_a_program_without_counters(window_ms):
    """The parent's engine counts no firings: both metrics are left out,
    nothing raises."""
    bare = [{k: v for k, v in s.items() if k != "fires_by_layer"}
            for s in COUNTED]
    for name in NEW:
        assert spec.metric_reader(name)(_view(bare)) is None


def test_readers_arithmetic(window_ms):
    view = _view(COUNTED)
    # 2 sweeps x (30 + 10) events over 2 x (3 + 1) firings x 64 chunks
    assert spec.metric_reader("window_fill")(view) == 40 / (4 * 64)
    # 12 ms over 2 whole sweeps x 4 firings a sweep
    assert spec.metric_reader("window_ms_per_fire")(view) == 12.0 / 8


def test_tiny_traced_run_reads_window_fill(tmp_path):
    """The made-up cell with the two metrics, on 2 cores in place of 4
    so that its runqueues grow and the window fires: on the CPU the
    counter metric reads in (0, 1]; the device-trace one finds no device
    ops and is left out."""
    root = tiny.write(str(tmp_path))
    for name, change in (
            ("BENCHMARK.json", lambda b: b["per_layer"].extend(
                {"name": n, "unit": "x"} for n in NEW)),
            ("bench/configs/tiny_host.json",
             lambda c: c.update(cores_per_node=2))):
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
    assert 0 < r["metrics"]["window_fill"]["value"] <= 1
    assert "window_ms_per_fire" not in r["metrics"]
