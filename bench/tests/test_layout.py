"""A cell is found from files alone, and a run meets the output
contract, on the CPU at a tiny size."""
import contextlib
import io
import time

import pytest
import tiny
from benchkit import runner, spec, traffic

SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax-cache"))


def test_made_up_cell_builds_its_sweep(tmp_path):
    root = tiny.write(str(tmp_path))
    cell = spec.load_cell(tiny.CELL, root)
    assert cell.config["cores_per_node"] == 4 and cell.chips == 1
    plan = traffic.plan(cell.config, cell.traffic, SEED)
    assert len(plan.cells) == 2 * 2 * 3
    assert plan.cells == traffic.plan(cell.config, cell.traffic,
                                      SEED).cells
    scenarios = traffic.build_scenarios(cell.config, plan)
    for sc, c in zip(scenarios, plan.cells):
        ref = traffic.cell_traffic(plan, c)
        tasks, _ = sc.workload.build()
        assert [t.arrival for t in tasks] == list(ref["arrival"])
        assert [t.service for t in tasks] == list(ref["service"])


@pytest.mark.parametrize("trace", [False, True])
def test_run_of_made_up_cell(tmp_path, cache, trace):
    root = tiny.write(str(tmp_path))
    r = runner.run_cell(tiny.CELL, SEED, 0.05, trace, t_start=time.time(),
                        root=root, cache_dir=cache, require_tpu=False)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] % 12 == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"
    if trace:
        # No device plane on the CPU: the device-trace readers that need
        # a busy time find nothing and the metric is left out.
        assert {"trip_waste", "device_trips", "device_cell_share",
                "window_compiles", "build_ms", "rollup_ms"} \
            <= set(r["metrics"])
        assert "ms_per_trip" not in r["metrics"]
        assert r["metrics"]["device_cell_share"]["value"] == 1.0
        assert r["metrics"]["window_compiles"]["value"] == 0
        assert "breakdown" in r
    else:
        assert set(r["metrics"]) == {"sim_tasks_per_s", "setup_s"}
        assert r["metrics"]["sim_tasks_per_s"]["value"] > 0


def test_without_a_tpu_no_result_and_nonzero_exit():
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = runner.main(["--workload", "ep3_node.load_sweep", "--seed",
                          str(SEED), "--seconds", "1", "--trace", "0"])
    assert rc != 0 and out.getvalue() == ""


def test_sweep_size_is_the_configurations(tmp_path):
    root = tiny.write(str(tmp_path))
    cell = spec.load_cell(tiny.CELL, root)
    config = dict(cell.config, traces_per_sweep=3)
    with pytest.raises(ValueError, match="sweeps 3"):
        traffic.plan(config, cell.traffic, SEED)
