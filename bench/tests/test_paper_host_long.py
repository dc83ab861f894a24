"""The paper host in its backlog regime, ``paper_host_long.backlog``,
and the same host with headroom, ``paper_host.util75``: what each cell
plans from the repo's ``BENCHMARK.json``, and a cut of the backlog
configuration that the CPU runs, held to the plain reference."""
import json
import os

import numpy as np
import pytest
from benchkit import check, runner, spec, traffic

SEED = 2 ** 31 + 424242
BACKLOG = "paper_host_long.backlog"
UTIL75 = "paper_host.util75"


def _one_trace(cell):
    plan = traffic.plan(cell.config, cell.traffic, SEED)
    (seed, tr), = plan.traces.items()
    return plan, seed, len(tr["arrival"])


def test_backlog_plans_one_deep_scatter_program():
    """One trace of 2,049-4,096 invocations in the first 20 s under
    fifo, cfs and hybrid at load 1.0, all in one (50, 4,096) bucket,
    whose window scans 256 chunks and commits by scatter."""
    from repro.mc.engine import _bucket
    from repro.mc.kernels import window_commit, window_len
    cell = spec.load_cell(BACKLOG)
    assert cell.chips == 1 and cell.config["cores_per_node"] == 50
    assert cell.config["horizon_s"] == 20
    assert "live_slot_share" in {m["name"] for m in cell.per_layer}
    plan, _, n = _one_trace(cell)
    assert sorted(c.policy for c in plan.cells) == ["cfs", "fifo", "hybrid"]
    assert {c.load for c in plan.cells} == {1.0}
    assert 2049 <= n <= 4096
    assert _bucket(n) == 4096
    assert window_len(4096) == 256 and window_commit(4096) == "scatter"


def test_util75_plans_the_overload_trace_at_load_027():
    """The overload cell's trace, in its (50, 1,024) bucket with the
    dense commit, offered at load 0.27."""
    from repro.mc.engine import _bucket
    from repro.mc.kernels import window_commit
    cell = spec.load_cell(UTIL75)
    assert cell.chips == 1 and cell.config["cores_per_node"] == 50
    assert "live_slot_share" in {m["name"] for m in cell.per_layer}
    plan, seed, n = _one_trace(cell)
    assert sorted(c.policy for c in plan.cells) == ["cfs", "fifo", "hybrid"]
    assert {c.load for c in plan.cells} == {0.27}
    assert _bucket(n) == 1024 and window_commit(1024) == "dense"
    _, overload_seed, overload_n = _one_trace(
        spec.load_cell("paper_host.overload"))
    assert (seed, n) == (overload_seed, overload_n)


@pytest.fixture(scope="module")
def cut(tmp_path_factory):
    """The backlog configuration's own file, cut to the first second of
    its trace on 34 of its 50 cores (317 invocations, a (34, 512)
    bucket), so that the CPU runs it in seconds while cfs runqueues
    still pass the cycle's 8 members: at 50 cores a cut short enough
    for the CPU keeps them within 7. The traffic's stratum takes the
    block's first trace, the one the cell draws. One sweep through the
    program's front door."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax")))
    cell = spec.load_cell(BACKLOG)
    with open(os.path.join(spec.ROOT, "bench", "configs",
                           "paper_host_long.json")) as f:
        config = json.load(f)
    assert config == cell.config
    config.update(horizon_s=1, cores_per_node=34)
    mix = dict(cell.traffic,
               trace_seeds=[dict(cell.traffic["trace_seeds"][0],
                                 tasks_min=1)])
    plan = traffic.plan(config, mix, SEED)
    assert list(plan.traces) == list(_one_trace(cell)[0].traces)
    return config, plan, runner.run_sweep(config, plan)


def _judged(config, plan, answer):
    got = check.worst([check.compare(answer(k, c),
                                     check.reference(config, plan, c))
                       for k, c in enumerate(plan.cells)], 0)
    return check.judge(got, check.limits(config)), got


def test_cut_matches_the_reference_and_the_control_fails(cut):
    config, plan, sw = cut
    assert sw.failed == 0
    ok, got = _judged(config, plan,
                      lambda k, c: check.observed(sw.results[k]))
    assert ok, got
    ok, got = _judged(config, plan, lambda k, c: check.reference(
        config, plan, c, dtype=np.float32))
    assert not ok, got


def test_cut_keeps_cfs_past_the_cycle(cut):
    """cfs runqueues grow past the cycle's 8 members, so the batch sorts
    on some trips and the window fires; every cell counts live slots."""
    _, plan, sw = cut
    stats = {c.policy: r.mc_stats for c, r in zip(plan.cells, sw.results)}
    assert stats["cfs"]["rq_depth_max"] > 8
    assert stats["cfs"]["rotation_deep_trips"] > 0
    assert stats["cfs"]["fires_by_layer"]["window_ff"] > 0
    view = runner.RunView(sweeps=[sw], trace=None, compile={})
    assert 0 < spec.metric_reader("live_slot_share")(view) <= 1
    for st in stats.values():
        assert st["n_slots"] == 512
        assert 0 < st["live_slots"] <= st["iters"] * 317
