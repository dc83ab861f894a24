"""End-to-end behaviour: the paper's headline claims on a downscaled
workload (fast), plus HLO analysis self-checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import run_policy
from repro.costmodel.hlo_analysis import analyze, shape_bytes


@pytest.fixture(scope="module")
def results(small_workload):
    return {p: run_policy(p, small_workload, n_cores=10)
            for p in ("fifo", "cfs", "hybrid")}


def test_obs2_fifo_vs_cfs_tradeoff(results):
    """Obs. 2: FIFO better execution, CFS better response."""
    f, c = results["fifo"], results["cfs"]
    assert f.execution().mean() < c.execution().mean()
    assert c.p("response", 99) < f.p("response", 99)


def test_obs5_cfs_cost_blowup(results):
    """Obs. 5 / Fig. 1: CFS costs several times FIFO (>=10x at the
    paper's full 12.4k-invocation scale; >=3x on this downscale)."""
    assert results["cfs"].cost_usd() > 3.0 * results["fifo"].cost_usd()


def test_hybrid_execution_near_fifo(results):
    """Hybrid keeps execution time near-optimal (Fig. 6/12)."""
    f, h, c = (results[p] for p in ("fifo", "hybrid", "cfs"))
    assert h.execution().mean() < 2.0 * f.execution().mean()
    assert h.execution().mean() < 0.5 * c.execution().mean()


def test_hybrid_cost_saves_vs_cfs(results):
    """Conclusion 4: hybrid significantly cheaper than CFS."""
    assert results["hybrid"].cost_usd() < 0.4 * results["cfs"].cost_usd()


def test_preemption_counts_ordering(results):
    """Fig. 13: hybrid has orders of magnitude fewer preemptions."""
    assert results["hybrid"].total_preemptions() < \
        0.2 * results["cfs"].total_preemptions()


def test_microvm_mode_admission_cap(small_workload):
    r = run_policy("hybrid", small_workload, n_cores=10, microvm=True)
    n = len(small_workload)
    assert len(r.tasks) + len(r.failed) == n
    # boot overhead shifts execution up
    assert r.execution().min() >= 100.0


# -- HLO analysis self-checks -------------------------------------------------

def test_hlo_shape_bytes():
    assert shape_bytes("f32[2,3]{1,0}") == 24
    assert shape_bytes("bf16[128]") == 256
    assert shape_bytes("(f32[2], s32[4])") == 24
    assert shape_bytes("pred[]") == 1


def test_hlo_while_trip_counts():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f_scan(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def f_unroll(x, ws):
        for i in range(ws.shape[0]):
            x = jnp.tanh(x @ ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    fs = analyze(jax.jit(f_scan).lower(x, ws).compile().as_text())
    fu = analyze(jax.jit(f_unroll).lower(x, ws).compile().as_text())
    analytic = 6 * 2 * 64 * 64 * 64
    assert fs["flops"] == pytest.approx(analytic, rel=0.01)
    assert fu["flops"] == pytest.approx(analytic, rel=0.01)
