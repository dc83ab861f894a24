"""A run whose timed path is broken underneath comes out not correct.

The harness's look for a chip is skipped; everything else of a run
goes as on the chip, with the device program's output broken in one of
the ways a later change could break it. The cell runs on one chip, so
there is no exchange between chips to leave out.
"""
import time

import numpy as np
import pytest
import tiny
from benchkit import runner


def state_unchanged(real, arrival, service, n_tasks, n_fifo, limit,
                    n_cores):
    """The while loop returns the state it started from."""
    B, N = arrival.shape
    return {"completion": np.full((B, N), np.nan),
            "first_run": np.full((B, N), np.nan),
            "cpu_time": np.zeros((B, N)),
            "preemptions": np.zeros((B, N), np.int32),
            "ctx_switches": np.zeros((B, N), np.int32),
            "migrations": np.zeros((B, N), np.int32),
            "ok": np.ones(B, bool), "n_iters": np.zeros(B, np.int32),
            "n_events": np.zeros(B, np.int32)}


def half_batch(real, arrival, service, n_tasks, n_fifo, limit, n_cores):
    """Only the first half of the batch runs; the rest is given its
    answers."""
    h = max(1, len(n_tasks) // 2)
    out = real(arrival[:h], service[:h], n_tasks[:h], n_fifo[:h],
               limit[:h], n_cores=n_cores)
    idx = np.arange(len(n_tasks)) % h
    return {k: v[idx] for k, v in out.items()}


def answer_altered(real, arrival, service, n_tasks, n_fifo, limit,
                   n_cores):
    """One task's completion is moved by a millisecond."""
    out = real(arrival, service, n_tasks, n_fifo, limit, n_cores=n_cores)
    out["completion"][0, 0] += 1.0
    return out


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax-cache"))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
def test_broken_timed_path_is_not_correct(fault, tmp_path, cache,
                                          monkeypatch):
    import repro.mc.kernels as kernels

    real = kernels.run_grid

    def broken(arrival, service, n_tasks, n_fifo, limit, *, n_cores):
        return fault(real, arrival, service, n_tasks, n_fifo, limit,
                     n_cores)

    monkeypatch.setattr(kernels, "run_grid", broken)
    root = tiny.write(str(tmp_path))
    r = runner.run_cell(tiny.CELL, 99, 0.05, False, t_start=time.time(),
                        root=root, cache_dir=cache, require_tpu=False)
    assert r["correct"] is False
