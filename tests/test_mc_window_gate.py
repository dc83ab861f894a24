"""The window fast-forward's chunk-0 test (``head`` of
``kernels.window_engine``), which the kernel's batch-wide predicate
reads to skip the window on trips where it would retire nothing.

On any state ``head`` must name exactly the cores the window retires
chunks on, and where it names none the window must return its input
unchanged, so that skipping it keeps every bit.
"""
import numpy as np
import pytest

from repro.core.events import cfs_slice_ms


def _random_state(rng, n_cores, n_slots):
    """A state of ``n_slots`` tasks on ``n_cores`` cores: most cores run
    a task, the rest of the tasks are queued (core c's share falling as
    1 / (c + 1)^2), done or not yet arrived. Vruntimes are short sums of
    the CFS slices and remaining work is often within one slice, so that
    chunks complete, pushbacks land at the tail and pushbacks do not,
    all of them often."""
    from repro.mc import kernels
    C, N = n_cores, n_slots
    stat = rng.choice([0, 2, 4], N, p=[0.1, 0.8, 0.1]).astype(np.int32)
    cur = rng.choice(N, C, replace=False).astype(np.int32)
    cur[rng.random(C) < 0.15] = -1
    stat[cur[cur >= 0]] = 3
    share = 1.0 / np.arange(1, C + 1) ** 2
    qcore = rng.choice(C, N, p=share / share.sum()).astype(np.int32)
    slices = np.array([cfs_slice_ms(k, kernels.SCHED_LATENCY_MS,
                                    kernels.MIN_GRANULARITY_MS)
                       for k in range(kernels._SLICE_KMAX + 1)])
    vr = np.array([rng.choice(slices, rng.integers(1, 4)).sum()
                   for _ in range(N)])
    rem = np.where(rng.random(N) < 0.3, rng.uniform(0.5, 4.0, N),
                   rng.uniform(4.0, 400.0, N))
    seq = rng.integers(0, 16, N).astype(np.int32)
    rqn = np.bincount(qcore[stat == 2], minlength=C).astype(np.int32)
    k = rng.integers(0, kernels._SLICE_KMAX + 1, C)
    clen = np.minimum(rem[np.maximum(cur, 0)], slices[k])
    end = rng.uniform(100.0, 130.0, C)
    fr = np.where(rng.random(N) < 0.5, np.nan, rng.uniform(0.0, 100.0, N))
    comp = np.where(stat == 4, rng.uniform(0.0, 100.0, N), np.nan)
    st = dict(
        rem=rem, vr=vr.view(np.int64), cpu=rng.uniform(0.0, 50.0, N),
        seq=seq, qcore=qcore, stat=stat, fr=fr, comp=comp,
        npre=rng.integers(0, 5, N).astype(np.int32),
        nctx=rng.integers(0, 5, N).astype(np.int32),
        nmig=np.zeros(N, np.int32),
        cur=cur, end=np.where(cur >= 0, end, np.inf),
        clen=np.where(cur >= 0, clen, 0.0),
        cslb=slices[k].view(np.int64),
        last=rng.integers(-1, N, C).astype(np.int32),
        minvr=np.full(C, slices[0]).view(np.int64),
        seqc=np.full(C, 16, np.int32), rqn=rqn,
        ptr=np.int32(N), rr=np.int32(0), rrc=np.int32(0),
        it=np.int32(0), ev=np.int32(rng.integers(0, 1000)))
    elig = (cur >= 0) & (rng.random(C) < 0.8)
    ta = rng.uniform(100.0, 160.0)
    tf = rng.choice([np.inf, rng.uniform(100.0, 160.0)])
    fcid = np.int32(rng.integers(0, C + 1))
    return st, elig, ta, tf, fcid


@pytest.fixture(scope="module", params=[(4, 64), (8, 256)],
                ids=["w64", "w256"])
def window(request):
    """The bucket's jitted ``head`` and ``window_ff``, and random states
    of it, each with its pop order."""
    import jax
    from repro.mc import kernels
    C, N = request.param
    with jax.enable_x64(True):
        head, window_ff = kernels.window_engine(C, N)
        rot = jax.jit(kernels.rotation_sorted, static_argnums=4)
        rng = np.random.default_rng(18)
        states = []
        for _ in range(12):
            st, elig, ta, tf, fcid = _random_state(rng, C, N)
            r = rot(st["stat"], st["qcore"], st["vr"], st["seq"], C)
            states.append((st, elig, ta, tf, fcid, r))
        yield jax.jit(head), jax.jit(window_ff), states


def _chunk0(st, elig, ta, tf, fcid):
    """bool[C], the window's chunk-0 rule in NumPy: an eligible core
    with a queue whose in-flight chunk ends before the barrier and
    either completes or pushes its task back at or after every queued
    key (IEEE double adds; vruntime bits order as their values)."""
    from repro.core.events import _EPS
    C = len(st["cur"])
    cur = np.maximum(st["cur"], 0)
    comp = st["rem"][cur] - st["clen"] <= _EPS
    pushed = (st["vr"][cur].view(np.float64) +
              st["cslb"].view(np.float64)).view(np.int64)
    queued = (st["stat"] == 2)[None, :] & \
        (st["qcore"][None, :] == np.arange(C)[:, None])
    tail = np.where(queued, st["vr"][None, :], -1).max(axis=1)
    end = st["end"]
    before = (end < ta) & ((end < tf) | ((end == tf) & (np.arange(C) < fcid)))
    return elig & queued.any(axis=1) & before & (comp | (pushed >= tail))


def test_head_is_the_windows_did(window):
    """On every core of every state the chunk-0 test names exactly the
    cores the window retires chunks on, and follows the rule; some
    cores retire one chunk, some more, some none."""
    import jax
    head, window_ff, states = window
    retired = []
    with jax.enable_x64(True):
        for st, elig, ta, tf, fcid, rot in states:
            want = np.asarray(head(st, elig, ta, tf, fcid, rot))
            np.testing.assert_array_equal(
                want, _chunk0(st, elig, ta, tf, fcid))
            out, did = window_ff(st, elig, ta, tf, fcid, rot)
            np.testing.assert_array_equal(np.asarray(did), want)
            retired.append((int(out["ev"]) - int(st["ev"]), want.sum()))
            assert (retired[-1][0] > 0) == bool(want.any())
    assert any(n == 0 for n, _ in retired)
    assert any(n > fired > 0 for n, fired in retired)


def test_window_without_head_is_identity(window):
    """Where the chunk-0 test names no core the window returns its input
    in every field, bit for bit: skipping it is exact."""
    import jax
    head, window_ff, states = window
    with jax.enable_x64(True):
        for st, elig, ta, tf, fcid, rot in states:
            fired = np.asarray(head(st, elig, ta, tf, fcid, rot))
            cold = elig & ~fired
            assert not np.asarray(head(st, cold, ta, tf, fcid, rot)).any()
            out, did = window_ff(st, cold, ta, tf, fcid, rot)
            assert not np.asarray(did).any()
            assert set(out) == set(st)
            for key, value in st.items():
                np.testing.assert_array_equal(np.asarray(out[key]), value,
                                              err_msg=key)
