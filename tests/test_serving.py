"""Serving gateway and the per-family request model."""
import pytest

from repro.configs import get_config
from repro.serving import (kv_bytes, preemption_penalty_ms,
                           requests_from_trace, run_gateway)
from repro.traces import TraceSpec

SMALL = TraceSpec(minutes=1, invocations_per_min=6000, n_functions=60,
                  seed=5)  # overload: 50 slots, rho ~= 2


def test_kv_bytes_family_scaling():
    dense = get_config("deepseek-7b")
    ssm = get_config("rwkv6-1.6b")
    hyb = get_config("zamba2-1.2b")
    # SSM state is constant in seq len; attention KV is linear
    assert kv_bytes(ssm, 4096) == kv_bytes(ssm, 65536)
    assert kv_bytes(dense, 65536) > 10 * kv_bytes(dense, 4096)
    assert kv_bytes(hyb, 65536) < kv_bytes(dense, 65536)
    # sliding-window archs cap most layers' KV
    g = get_config("gemma3-12b")
    assert kv_bytes(g, 65536) < kv_bytes(dense, 65536)


def test_preemption_penalty_cheaper_for_ssm():
    assert preemption_penalty_ms(get_config("rwkv6-1.6b"), 32768) < \
        preemption_penalty_ms(get_config("deepseek-7b"), 32768)


@pytest.fixture(scope="module")
def gw_requests():
    return requests_from_trace(get_config("deepseek-7b"), SMALL)


def test_gateway_hybrid_cheaper_than_cfs(gw_requests):
    cfg = get_config("deepseek-7b")
    cfs = run_gateway(cfg, "cfs", requests=gw_requests)
    hyb = run_gateway(cfg, "hybrid", requests=gw_requests)
    assert hyb.cost_usd() < cfs.cost_usd()
    assert hyb.sim.p("execution", 99) < cfs.sim.p("execution", 99)


def test_gateway_preemption_penalty_paid(gw_requests):
    cfg = get_config("deepseek-7b")
    hyb = run_gateway(cfg, "hybrid", requests=gw_requests)
    migrated = [t for t in hyb.sim.tasks if t.migrations > 0]
    assert migrated
    # migrated tasks paid at least one swap penalty in execution span
    pen = preemption_penalty_ms(cfg, 4096)
    assert all(t.execution >= t.service + pen - 1e-6 for t in migrated)


def test_gateway_straggler_redispatch(gw_requests):
    cfg = get_config("deepseek-7b")
    r = run_gateway(cfg, "hybrid", requests=gw_requests,
                    straggler_factor=3.0)
    assert r.redispatches >= 0          # hook wired (count depends on load)
