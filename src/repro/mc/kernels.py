"""Batched Monte-Carlo kernels for the supported scheduling regime.

One cell = one (policy, seed, load) trajectory of the single-node
engine; the kernel below advances a WHOLE GRID of cells in a single
compiled XLA program via ``jax.vmap`` (DESIGN.md Sec. 16).

The kernel is a faithful re-expression of the scalar event loop
(`core/events.py` + `core/policies.py` + `core/hybrid.py`) restricted
to the regime the analytic fast-forwards already closed:

* single node, no container pool, no interference, no util timers,
* policies: ``fifo``, ``cfs``, ``hybrid`` with a STATIC time limit,
* default Linux knobs (sched_latency 24 ms, min_granularity 3 ms,
  ctx_switch 0.06 ms).

Within that regime the event braid has exactly three interacting
event classes, totally ordered by the scalar heap key ``(t, klass,
tie)``: arrivals (klass 0, tid order), hybrid FIFO-core expiries
(klass 2, cid tie-break — they touch shared state: the global queue,
migration round-robin, CFS runqueues), and CFS-core expiries (klass 2,
core-local).  CFS expiries before the next arrival/FIFO barrier are
INDEPENDENT across cores, so the kernel advances every eligible CFS
core in one vectorized step — and, since PR 9, retires MANY chunk
expiries per outer iteration via the Sec. 13 closed forms re-expressed
as fixed-length ``lax.scan`` batches:

* **cycle engine** — the stable-alternation-cycle fast-forward: when a
  core's runnable set is small (``<= _CYCLE_K`` members) the pop order
  is a fixed rotation; a scan walks up to one window of chunks across
  MULTIPLE rounds, carrying per-member (remaining, vruntime, cpu)
  accumulators and the end-time left fold, stopping at the first
  completion, instability, or barrier.  The lone-task solo regime is
  the ``k == 1`` case of the same engine.
* **window engine** — PR 4's ``_window_fast_forward`` twin: one full
  rotation of a deeper runqueue evaluated at once (stability /
  slice-constancy / bound / completion masks as vector predicates over
  the chunk axis), completions retired inline, the surviving prefix
  committed by a dense ownership select (by scatter in buckets of
  more than 2,048 slots, where the select costs more: `window_commit`).
  It runs only on the trips where it retires a chunk on some core of
  some cell of the batch (`window_engine`'s ``head``).
* **generic advance** — the original one-event expire+pick, kept as
  the universal fallback: any chunk the batches decline (unstable
  push, slice change, the completing chunk of a cycle) retires here
  with identical arithmetic.

Barrier events (arrivals in tid order, the minimal FIFO expiry) are
then re-serialized exactly as the heap would.

Equivalence contract (DESIGN.md Sec. 16). Under ``jax.enable_x64``
on the CPU backend every float is computed by the SAME operation
sequence as the scalar engine — the shared pure helpers of
``core/events.py`` (`chunk_run_ms`, `chunk_end_ms`, `cfs_slice_ms`,
`fifo_budget_ms`) re-bound to ``jnp.minimum``/``jnp.maximum`` — so
per-task digests (completion, first_run, preemptions, ctx_switches,
migrations, cpu_time) and every cost roll-up derived from them match
the scalar engine bit-for-bit.  The multi-event batches preserve the
contract because they only ever retire FULL chunks whose parameters
the event path would compute identically: end times accumulate
through an explicit left fold ``e = (e + ctx) + run`` inside
``lax.scan`` — NEVER ``cumsum``, which XLA may reassociate — and the
only associative scans used for predicates are exact ones (integer
``cumsum`` of completion flags, ``cummax`` of push keys).  The TPU has
no IEEE float64: its compiler runs the same program in double-f32
(pairs of f32), so there the floats agree within the limits the
benchmark's configurations state (DESIGN.md Sec. 16). The integer
observables stay exact there because the one float whose rounding
steers the trajectory, the CFS vruntime, is kept as integer IEEE bit
patterns (`vr_add`), bit-for-bit on every backend.

A plain-FIFO cell runs as the hybrid machinery with ``n_fifo == C``
and an infinite budget: ``min(rem, inf) == rem`` and ``max(inf - 0.0,
0.01) == inf`` are bitwise no-ops, completions always beat the
(unreachable) migration branch, so the braid degenerates to FIFO's
run-to-completion semantics with identical arithmetic.  A pure-CFS
cell is ``n_fifo == 0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.events import (_EPS, cfs_slice_ms, chunk_completes,
                               chunk_end_ms, chunk_run_ms,
                               fifo_budget_ms)

from .spans import EVENT_LAYERS, FIRE_LAYERS, SCOPES

# Default Linux knobs of the supported regime (see module docstring);
# the dispatch gate (repro.mc.dispatch) refuses cells that override
# them, so baking them into the compiled program is safe.
SCHED_LATENCY_MS = 24.0
MIN_GRANULARITY_MS = 3.0
CTX_SWITCH_MS = 0.06

_INF = float("inf")
_I32MAX = 2 ** 31 - 1

# Vruntimes are kept as the int64 bit patterns of their IEEE-754
# binary64 values ("vr bits"); see `vr_add`. For non-negative doubles
# the bit-pattern order is the value order, so every vruntime compare,
# min/max, cummax and sort runs on exact integers on any backend.
# Sentinels: _VR_TOP sorts after every vruntime, _VR_BOT before.
_VR_TOP = np.iinfo(np.int64).max
_VR_BOT = -1
# The CFS slice granted at a pick, as vr bits, per post-pop runqueue
# length k: latency / k floored at the granularity, constant from
# k = latency // granularity on. A pushed-back chunk always ran one full
# slice, so these are the only increments a vruntime ever receives.
_SLICE_KMAX = int(SCHED_LATENCY_MS // MIN_GRANULARITY_MS)
_SLICE_BITS = np.array(
    [cfs_slice_ms(k, SCHED_LATENCY_MS, MIN_GRANULARITY_MS)
     for k in range(_SLICE_KMAX + 1)], np.float64).view(np.int64)
_FRAC = (1 << 52) - 1
_HIDDEN = 1 << 52


def vr_add(a, b):
    """IEEE-754 binary64 sum ``a + b`` of two non-negative finite
    doubles given as int64 bit patterns, rounded to nearest-even —
    bit-for-bit what a float64 add returns, computed in integers.

    The TPU has no IEEE float64 (its compiler runs f64 as pairs of f32),
    and CFS pick order hangs on vruntime near-ties whose IEEE rounding
    the scalar engine's answer depends on; integer arithmetic is exact
    everywhere. Operands are 0 or normal numbers (vruntimes are sums of
    slices >= the granularity).
    """
    hi = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    e = hi >> 52
    # 53-bit significands with 3 guard/round/sticky bits below.
    ma = ((hi & _FRAC) | _HIDDEN) << 3
    mb = ((lo & _FRAC) | _HIDDEN) << 3
    d = jnp.minimum(e - (lo >> 52), 60)
    sticky = (mb & ((jnp.ones_like(mb) << d) - 1)) != 0
    s = ma + ((mb >> d) | sticky.astype(mb.dtype))
    carry = s >> 56                       # the sum overflowed 53 bits
    s = (s >> carry) | (s & carry)
    e = e + carry
    low = s & 7
    m = s >> 3
    m = m + ((low > 4) | ((low == 4) & ((m & 1) == 1))).astype(m.dtype)
    ovf = m >> 53                         # rounding carried out
    m = m >> ovf
    out = ((e + ovf) << 52) | (m & _FRAC)
    return jnp.where(lo == 0, hi, out)


# Safety valve: an upper bound on outer-loop iterations so a regime
# bug hangs nothing — the engine checks the `ok` output and raises.
_MAX_ITERS_PER_TASK = 1024

# Multi-event retirement knobs. The cycle engine covers alternation
# cycles of up to _CYCLE_K members (running task + up to _CYCLE_K - 1
# queued); both batches retire up to one window of chunks per outer
# iteration. The window length is the kernel twin of the scalar
# engine's adaptive 64/256 `Core.ff_w` escalation — under jit shapes
# are static, so the choice is made per compiled (C, N) bucket (small
# buckets take the 64-chunk window, deep-queue buckets the 256) rather
# than per core at runtime.
_CYCLE_K = 8
_WINDOW_SMALL = 64
_WINDOW_DEEP = 256
_MICRO_STEPS = 8

# The largest bucket (task slots N) whose window fast-forward commits
# by the dense select (see `window_commit`).
_DENSE_COMMIT_MAX_SLOTS = 2048
# The name of the batch axis `grid_kernel` maps the cell kernel over:
# the rotation reads the deepest runqueue of the whole batch.
_CELLS = "cells"


def window_len(n_slots: int) -> int:
    """W, the chunks the cycle and window fast-forwards of an N-slot
    bucket scan per core and trip: a window firing retires at most W."""
    return _WINDOW_SMALL if n_slots <= 128 else _WINDOW_DEEP


def window_commit(n_slots: int) -> str:
    """How the window fast-forward of an N-slot bucket commits the
    chunks it retires into the per-task state: ``"dense"``, one select
    per field over the ownership mask [C, W + 1, N] reduced over
    (core, chunk), where N is at most ``_DENSE_COMMIT_MAX_SLOTS``; else
    ``"scatter"``, indexed writes of the window's C x (W + 1) updates.

    The two give the same bits. Under ``vmap`` both cost in proportion
    to B x C x (W + 1): the TPU runs the batched scatters one update
    after another, and the select reads N slots for each update on the
    vector unit. So N alone decides. On a TPU v5 lite chip the select
    ran whole trips 2.0-2.2 times faster at N = 256, 512 and 1,024 (4,
    8 and 50 cores), as fast at 2,048, and 1.3 and 1.7 times slower at
    4,096 and 8,192 (50 cores)."""
    return "dense" if n_slots <= _DENSE_COMMIT_MAX_SLOTS else "scatter"


def _members(stat, qcore, n_cores: int):
    """bool[C, N]: task i is queued on core c's runqueue."""
    cids = jnp.arange(n_cores, dtype=jnp.int32)
    return (stat[None, :] == 2) & (qcore[None, :] == cids[:, None])


def _pop(member, vr, seq):
    """Each core's next pop (rq_pop): the least vruntime among its
    members, the least seq among the members tied at it, the first
    index among those. Returns (vruntime bits, task index) per core;
    on a core without members, ``_VR_TOP`` and index 0."""
    vkey = jnp.where(member, vr[None, :], _VR_TOP)
    vmin = jnp.min(vkey, axis=1)
    tie = member & (vkey == vmin[:, None])
    smin = jnp.min(jnp.where(tie, seq[None, :], _I32MAX), axis=1)
    pop = jnp.argmax(tie & (seq[None, :] == smin[:, None]), axis=1)
    return vmin, pop.astype(jnp.int32)


def rotation_sorted(stat, qcore, vr, seq, n_cores: int):
    """Per-core runqueue pop order: queued tasks sorted by (vruntime,
    seq) — two stable argsorts == lexsort. [C, N] task indices; entries
    past ``rqn[c]`` are padding."""
    member = _members(stat, qcore, n_cores)
    skey = jnp.where(member, seq[None, :], _I32MAX)
    p1 = jnp.argsort(skey, axis=1, stable=True)
    vkey = jnp.where(member, vr[None, :], _VR_TOP)
    vg = jnp.take_along_axis(vkey, p1, axis=1)
    p2 = jnp.argsort(vg, axis=1, stable=True)
    return jnp.take_along_axis(p1, p2, axis=1).astype(jnp.int32)


def rotation_prefix(stat, qcore, vr, seq, n_cores: int, n_pops: int):
    """The first ``n_pops`` (P) entries of `rotation_sorted`, by P pops.

    Each pop (`_pop`) follows the (vruntime, seq, index) order of the
    two stable argsorts, then drops the popped task from the members.
    [C, N] task indices: entry j < P is what `rotation_sorted` gives
    wherever j < ``rqn[c]``; entries at or past ``rqn[c]``, and all
    columns from P on, hold some in-range index and must not be read.
    Every reader masks the pop order by ``rqn``, so this serves any trip
    on which no core holds more than P queued tasks.

    At P = 7 the pops cost in proportion to 7 N a core, the sort to
    N log N. On a TPU v5 lite chip the kernel with the pops and the sort
    behind a batch-wide conditional ran whole trips faster than the sort
    on every trip at each N measured: 1.025 and 1.005 times at N = 64
    and 128 (4 cores, B = 132 and 60, a cell deep on 41 and 102 trips),
    1.12, 1.25 and 1.48 times at 256, 512 and 1,024 (50 cores, B = 3, no
    cell deep), and 1.16 times at 2,048 (50 cores, a cell deep on 339 of
    577 trips). So every bucket takes it."""
    member = _members(stat, qcore, n_cores)
    tids = jnp.arange(stat.shape[0], dtype=jnp.int32)
    pops = []
    for _ in range(n_pops):
        _, pop = _pop(member, vr, seq)
        pops.append(pop)
        member = member & (tids[None, :] != pop[:, None])
    pad = jnp.zeros((n_cores, stat.shape[0] - n_pops), jnp.int32)
    return jnp.concatenate([jnp.stack(pops, axis=1), pad], axis=1)


def _sel_tree(pred, new, old):
    """Per-cell select between two state pytrees (scalar bool pred)."""
    return {k: jnp.where(pred, new[k], old[k]) for k in old}


def before_barrier(e, ta, tf, fcid):
    """Strictly-before-barrier test for CFS expiries at ``e``, [C] or
    [C, W] with cores first (heap order: arrivals win ties, FIFO
    expiries tie-break on core id)."""
    cw = jnp.arange(e.shape[0], dtype=jnp.int32) < fcid
    if e.ndim == 2:
        cw = cw[:, None]
    return (e < ta) & ((e < tf) | ((e == tf) & cw))


def slice_bits(k):
    """The slice ``cfs_slice_ms(k)`` as vr bits."""
    return jnp.asarray(_SLICE_BITS)[jnp.clip(k, 0, _SLICE_KMAX)]


def window_engine(n_cores: int, n_slots: int):
    """The window fast-forward of a (C, N) bucket: ``(head,
    window_ff)``, two functions of the kernel's state dict ``st``, the
    CFS cores eligible for it ``elig`` (bool[C]), the barrier (``ta``,
    ``tf``, ``fcid``) and the trip's pop order ``rot`` [C, N].

    ``window_ff`` returns the state with the window's retirements
    committed and bool[C], the cores it retired chunks on. ``head`` is
    its chunk-0 test, bool[C]: the cores on which the window retires
    the in-flight chunk. Each chunk retires only after every chunk
    before it, so those are exactly the cores ``window_ff`` retires
    chunks on; where ``head`` holds on no core, ``window_ff`` returns
    ``st`` unchanged in every field.

    The scalar engine's `_window_fast_forward` twin: evaluate ONE
    rotation of a core's runqueue (up to W chunks) at once. Chunk 0 is
    the in-flight chunk; chunk i >= 1 pops rotation[i - 1]. All masks
    are vector predicates over the chunk axis; only the end-time chain
    is sequential (explicit lax.scan left fold). Completions retire
    inline; the integer cumsum of completion flags and the cummax of
    push keys are the ONLY associative scans (both exact under
    reassociation).
    """
    C, N = n_cores, n_slots
    LAT, GRAN, CTX = SCHED_LATENCY_MS, MIN_GRANULARITY_MS, CTX_SWITCH_MS
    W = window_len(N)
    commit = window_commit(N)
    cids = jnp.arange(C, dtype=jnp.int32)

    def head(st, elig, ta, tf, fcid, rot):
        """An eligible core with a queue retires its in-flight chunk
        before the barrier where the chunk completes, or where its
        pushback lands at or after the queue's deepest key, that of the
        queue's last pop. O(C) gathers, none of the window's [C, W + 1]
        arrays."""
        k1 = st["rqn"]
        cur = jnp.where(elig, st["cur"], 0)
        tail = jnp.take_along_axis(
            rot, jnp.maximum(k1 - 1, 0)[:, None], 1)[:, 0]
        comp0 = chunk_completes(st["rem"][cur], st["clen"])
        stab0 = vr_add(st["vr"][cur], st["cslb"]) >= st["vr"][tail]
        return elig & (k1 >= 1) & \
            before_barrier(st["end"], ta, tf, fcid) & (comp0 | stab0)

    def window_ff(st, elig, ta, tf, fcid, rot):
        k1 = st["rqn"]
        winm = elig & (k1 >= 1)
        cur = st["cur"]
        ii = jnp.arange(W + 1, dtype=jnp.int32)[None, :]
        u = jnp.concatenate([jnp.where(winm, cur, N)[:, None],
                             rot[:, :W]], axis=1)       # [C, W+1]
        uvalid = winm[:, None] & (ii <= k1[:, None])
        u = jnp.where(uvalid, u, N)
        su = jnp.minimum(u, N - 1)
        rem0 = st["rem"][su]
        vr0 = st["vr"][su]
        fr0 = st["fr"][su]
        s = cfs_slice_ms(k1, LAT, GRAN, _max=jnp.maximum)
        runs = chunk_run_ms(rem0, s[:, None], _min=jnp.minimum,
                            _max=jnp.maximum)
        runs = jnp.where(ii == 0, st["clen"][:, None], runs)
        comp = chunk_completes(rem0, runs)
        cum = jnp.cumsum(comp.astype(jnp.int32), axis=1)
        cumx = jnp.concatenate(
            [jnp.zeros((C, 1), jnp.int32), cum[:, :-1]], axis=1)
        # slice at chunk i's pick: queue holds k1 - (completions
        # among chunks < i) entries after the pop.
        s_i = cfs_slice_ms(k1[:, None] - cumx, LAT, GRAN,
                           _max=jnp.maximum)
        slice_ok = s_i == s[:, None]
        pushed = vr_add(vr0, jnp.where(ii == 0, st["cslb"][:, None],
                                       slice_bits(k1)[:, None]))
        # Stability: a non-completing pushback must land at the
        # tail — at/after the deepest original key and every
        # earlier in-window push (exact cummax).
        pkey = jnp.where(comp, _VR_BOT, pushed)
        # Deepest original key: rotation is sorted, so the last
        # queue entry (possibly beyond the window) carries it.
        tail_tid = jnp.take_along_axis(
            rot, jnp.maximum(k1 - 1, 0)[:, None], 1)[:, 0]
        tail0 = st["vr"][tail_tid]
        prior = jnp.concatenate(
            [tail0[:, None],
             jnp.maximum(tail0[:, None],
                         lax.cummax(pkey, axis=1)[:, :-1])], axis=1)
        stab = pushed >= prior

        def estep(e, run_col):
            e2 = chunk_end_ms(e, CTX, run_col)
            return e2, e2

        _, etail = lax.scan(estep, st["end"], runs[:, 1:].T,
                            unroll=8)
        E = jnp.concatenate([st["end"][:, None], etail.T], axis=1)
        # Chunk 0 is `head`'s test; column W never holds, so the window
        # retires c >= 1 chunks exactly where `head` holds.
        ok = jnp.where(ii == 0, head(st, elig, ta, tf, fcid, rot)[:, None],
                       uvalid & (ii < k1[:, None]) & (ii < W) &
                       before_barrier(E, ta, tf, fcid) & slice_ok &
                       (comp | stab))
        c = jnp.argmax(~ok, axis=1).astype(jnp.int32)
        did = c >= 1

        cm = c[:, None]
        Ec1 = jnp.take_along_axis(E, jnp.maximum(cm - 1, 0), 1)[:, 0]
        cumc = jnp.take_along_axis(cumx, cm, 1)[:, 0]
        s_c = jnp.take_along_axis(s_i, cm, 1)[:, 0]
        rem_c = jnp.take_along_axis(rem0, cm, 1)[:, 0]
        run_c = chunk_run_ms(rem_c, s_c, _min=jnp.minimum,
                             _max=jnp.maximum)
        end_c = chunk_end_ms(Ec1, CTX, run_c)
        u_c = jnp.take_along_axis(u, cm, 1)[:, 0]
        u_cp = jnp.take_along_axis(u, jnp.maximum(cm - 1, 0),
                                   1)[:, 0]
        vr0_c = jnp.take_along_axis(vr0, cm, 1)[:, 0]

        R = winm[:, None] & (ii < cm)          # retired chunks
        Rc = R & comp
        Rp = R & ~comp
        # picks: chunks 1..c start at the previous chunk's end;
        # rotation members are pairwise distinct and distinct from
        # the chunk-0 task, so every pick charges a ctx switch.
        P = winm[:, None] & (ii >= 1) & (ii <= cm)
        Pf = P & jnp.isnan(fr0)                # first runs
        Eprev = jnp.concatenate([jnp.zeros((C, 1)), E[:, :-1]],
                                axis=1)
        pushseq = st["seqc"][:, None] + (ii - cumx)
        rem_w = jnp.where(comp, 0.0, rem0 - runs)
        qc = jnp.broadcast_to(cids[:, None], (C, W + 1))

        if commit == "dense":
            # Every valid entry of `u` is a distinct task (running
            # on one core or queued on one), so the commit is a
            # permutation write: each task reads its one update out
            # of the window, selected by ownership and reduced over
            # (core, chunk) against a neutral below every update.
            tids = jnp.arange(N, dtype=jnp.int32)
            own = u[:, :, None] == tids            # [C, W+1, N]

            def hit(mask):
                return jnp.any(own & mask[:, :, None], axis=(0, 1))

            def read(mask, v):
                low = (-_INF if jnp.issubdtype(v.dtype, jnp.floating)
                       else jnp.iinfo(v.dtype).min)
                return jnp.max(jnp.where(own & mask[:, :, None],
                                         v[:, :, None], low),
                               axis=(0, 1))

            hR, hRc, hRp, hP, hPf = map(hit, (R, Rc, Rp, P, Pf))
            running = jnp.any(did[:, None] & (u_c[:, None] == tids),
                              axis=0)
            new = dict(
                rem=jnp.where(hR, read(R, rem_w), st["rem"]),
                cpu=jnp.where(hR, st["cpu"] + read(R, runs),
                              st["cpu"]),
                comp=jnp.where(hRc, read(Rc, E), st["comp"]),
                vr=jnp.where(hRp, read(Rp, pushed), st["vr"]),
                npre=jnp.where(hRp, st["npre"] + 1, st["npre"]),
                seq=jnp.where(hRp, read(Rp, pushseq), st["seq"]),
                qcore=jnp.where(hRp, read(Rp, qc), st["qcore"]),
                fr=jnp.where(hPf, read(Pf, Eprev), st["fr"]),
                nctx=jnp.where(hP, st["nctx"] + 1, st["nctx"]),
                stat=jnp.where(running, 3, jnp.where(
                    hRc, 4, jnp.where(hRp, 2, st["stat"]))),
            )
        else:
            tR = jnp.where(R, u, N).reshape(-1)
            tRc = jnp.where(Rc, u, N).reshape(-1)
            tRp = jnp.where(Rp, u, N).reshape(-1)
            tfr = jnp.where(Pf, u, N).reshape(-1)
            tP = jnp.where(P, u, N).reshape(-1)
            new = dict(
                rem=st["rem"].at[tR].set(rem_w.reshape(-1),
                                         mode="drop"),
                cpu=st["cpu"].at[tR].add(runs.reshape(-1),
                                         mode="drop"),
                comp=st["comp"].at[tRc].set(E.reshape(-1),
                                            mode="drop"),
                vr=st["vr"].at[tRp].set(pushed.reshape(-1),
                                        mode="drop"),
                npre=st["npre"].at[tRp].add(1, mode="drop"),
                seq=st["seq"].at[tRp].set(pushseq.reshape(-1),
                                          mode="drop"),
                qcore=st["qcore"].at[tRp].set(qc.reshape(-1),
                                              mode="drop"),
                fr=st["fr"].at[tfr].set(Eprev.reshape(-1),
                                        mode="drop"),
                nctx=st["nctx"].at[tP].add(1, mode="drop"),
                stat=st["stat"].at[tRp].set(2, mode="drop")
                    .at[tRc].set(4, mode="drop")
                    .at[jnp.where(did, u_c, N)].set(3, mode="drop"),
            )

        st2 = dict(
            st,
            **new,
            cur=jnp.where(did, u_c, st["cur"]),
            last=jnp.where(did, u_cp, st["last"]),
            end=jnp.where(did, end_c, st["end"]),
            clen=jnp.where(did, run_c, st["clen"]),
            cslb=jnp.where(did, slice_bits(k1 - cumc), st["cslb"]),
            # pops ratchet min_vruntime through nondecreasing keys:
            # the iterated max equals one max against the last pop.
            minvr=jnp.where(did,
                            jnp.maximum(st["minvr"], vr0_c),
                            st["minvr"]),
            seqc=st["seqc"] + jnp.where(did, c - cumc, 0),
            rqn=jnp.where(did, k1 - cumc, st["rqn"]),
            ev=st["ev"] + jnp.sum(jnp.where(did, c, 0), dtype=jnp.int32),
        )
        return st2, did

    return head, window_ff


def make_cell_kernel(n_cores: int, n_slots: int):
    """Build the single-cell simulator for a static shape bucket.

    ``n_cores`` (C) and ``n_slots`` (N, padded task capacity) are
    compile-time constants; everything per-cell (arrival/service
    arrays, task count, FIFO split, migration budget) is traced, so
    one compilation serves every cell of the bucket and ``jax.vmap``
    batches them into a single program. The rotation reads the whole
    batch's deepest runqueue, so the kernel runs only mapped over the
    axis named ``_CELLS``.
    """
    C, N = n_cores, n_slots
    LAT, GRAN, CTX = SCHED_LATENCY_MS, MIN_GRANULARITY_MS, CTX_SWITCH_MS
    KC = _CYCLE_K
    W = window_len(N)
    window_head, window_ff = window_engine(C, N)

    cids = jnp.arange(C, dtype=jnp.int32)

    def kernel(arrival, service, n_tasks, n_fifo, limit):
        """Run one cell to completion.

        arrival, service : f64[N]   (padded with +inf / 1.0)
        n_tasks          : i32      live prefix length
        n_fifo           : i32      C => plain FIFO, 0 => pure CFS
        limit            : f64      FIFO budget (inf outside hybrid)
        """
        is_fifo = cids < n_fifo
        n_cfs = C - n_fifo
        budget = fifo_budget_ms(limit, 0.0, _max=jnp.maximum)

        st = dict(
            # per-task
            rem=service,
            vr=jnp.zeros(N, jnp.int64),         # vr bits
            cpu=jnp.zeros(N),
            seq=jnp.zeros(N, jnp.int32),
            qcore=jnp.zeros(N, jnp.int32),
            stat=jnp.zeros(N, jnp.int32),   # 0 unarrived, 1 fifo-q,
                                            # 2 on-rq, 3 running, 4 done
            fr=jnp.full(N, jnp.nan),
            comp=jnp.full(N, jnp.nan),
            npre=jnp.zeros(N, jnp.int32),
            nctx=jnp.zeros(N, jnp.int32),
            nmig=jnp.zeros(N, jnp.int32),
            # per-core
            cur=jnp.full(C, -1, jnp.int32),
            end=jnp.full(C, _INF),
            clen=jnp.zeros(C),
            # the in-flight chunk's slice as vr bits: what a pushback
            # adds to the task's vruntime
            cslb=jnp.zeros(C, jnp.int64),
            last=jnp.full(C, -1, jnp.int32),
            minvr=jnp.zeros(C, jnp.int64),
            seqc=jnp.zeros(C, jnp.int32),
            rqn=jnp.zeros(C, jnp.int32),
            # scalars
            ptr=jnp.int32(0),
            rr=jnp.int32(0),
            rrc=jnp.int32(0),
            it=jnp.int32(0),
            ev=jnp.int32(0),
            # counters written by the outer loop only, never read back
            # into the state: events retired per layer (EVENT_LAYERS
            # order), (trip, core) firings per multi-event layer
            # (FIRE_LAYERS order), the deepest runqueue at a trip's end,
            # the trips that began with a runqueue deeper than KC - 1,
            # the trips on which the batch ran the window, the slots
            # live (arrived, not done) at each trip's start, summed (in
            # int64: trips x live slots outgrows int32 on a long trace)
            evl=jnp.zeros(len(EVENT_LAYERS), jnp.int32),
            fires=jnp.zeros(len(FIRE_LAYERS), jnp.int32),
            rqmax=jnp.int32(0),
            rdeep=jnp.int32(0),
            wtrips=jnp.int32(0),
            lsl=jnp.int64(0),
        )

        def t_arr(st):
            p = st["ptr"]
            return jnp.where(p < n_tasks, arrival[jnp.minimum(p, N - 1)],
                             _INF)

        def fifo_candidate(st):
            """Minimal pending FIFO-group expiry: (time, cid, any)."""
            busy = is_fifo & (st["cur"] >= 0)
            e = jnp.where(busy, st["end"], _INF)
            tmin = jnp.min(e)
            fcid = jnp.argmax(busy & (e == tmin)).astype(jnp.int32)
            return tmin, fcid, jnp.any(busy)

        # -- shared pick machinery ------------------------------------
        def cfs_pick_start(st, pickm, t_c, ctx_ref):
            """Pop-and-start on every core where ``pickm`` (bool[C]).

            ``t_c``   f64[C]: the instant each picking core picks at.
            ``ctx_ref`` i32[C]: the "last_task" each core compares the
            popped task against (ctx charge iff different).
            Mirrors pick_next's rq_pop + slice_for + _start_chunk.
            """
            stat = st["stat"]
            member = _members(stat, st["qcore"], C)
            vmin, ntid = _pop(member, st["vr"], st["seq"])
            pickm = pickm & jnp.any(member, axis=1)

            drop = jnp.where(pickm, ntid, N)
            # rq_pop: min_vruntime ratchets to the popped key.
            minvr = jnp.where(pickm, jnp.maximum(st["minvr"], vmin),
                              st["minvr"])
            rqn = st["rqn"] - pickm.astype(jnp.int32)
            stat = stat.at[drop].set(3, mode="drop")
            # slice_for reads nr_running AFTER the pop, core.task still
            # unset: nr == len(rq) == rqn.
            slc = cfs_slice_ms(rqn, LAT, GRAN, _max=jnp.maximum)
            slb = slice_bits(rqn)
            ctx = jnp.where(ctx_ref == ntid, 0.0, CTX)
            gat = jnp.where(pickm, ntid, 0)
            fr_v = st["fr"][gat]
            fr = st["fr"].at[
                jnp.where(pickm & jnp.isnan(fr_v), ntid, N)
            ].set(t_c, mode="drop")
            nctx = st["nctx"].at[
                jnp.where(pickm & (ctx > 0.0), ntid, N)
            ].add(1, mode="drop")
            run = chunk_run_ms(st["rem"][gat], slc,
                               _min=jnp.minimum, _max=jnp.maximum)
            nend = chunk_end_ms(t_c, ctx, run)
            return dict(st, stat=stat, fr=fr, nctx=nctx, minvr=minvr,
                        rqn=rqn,
                        cur=jnp.where(pickm, ntid, st["cur"]),
                        end=jnp.where(pickm, nend, st["end"]),
                        clen=jnp.where(pickm, run, st["clen"]),
                        cslb=jnp.where(pickm, slb, st["cslb"])), pickm

        # -- step 1: stable-alternation-cycle fast-forward ------------
        # A CFS core whose runnable set is small (k = rqn + 1 <= KC
        # members) pops in a FIXED rotation while every pushback lands
        # at the queue tail: slice-expiry -> push -> pop(next) -> start
        # repeats with no shared reads.  One fixed-length lax.scan
        # walks up to W chunks across MULTIPLE rounds, carrying the
        # per-member accumulators in [C, KC] slots and the end time as
        # an explicit left fold e = (e + ctx) + run (never cumsum).
        # It stops at the first would-be completion, unstable push, or
        # barrier; the chunk left in flight retires via the window or
        # generic paths with identical arithmetic.  k == 1 is the solo
        # regime of PR 3's fast-forward (ctx == 0, slice == latency).
        def cycle_ff(st, ta, tf, fcid, rot):
            cur, rqn = st["cur"], st["rqn"]
            act0 = (~is_fifo) & (cur >= 0) & (rqn + 1 <= KC) & \
                before_barrier(st["end"], ta, tf, fcid)
            jj = jnp.arange(KC, dtype=jnp.int32)[None, :]
            idx = jnp.concatenate([cur[:, None], rot[:, :KC - 1]], axis=1)
            valid = act0[:, None] & (jj < (rqn + 1)[:, None])
            idx = jnp.where(valid, idx, N)
            safe = jnp.minimum(idx, N - 1)
            # Members never complete inside the batch, so the queue
            # size — and with it the slice and the ctx charge — is
            # invariant across the whole scan.
            s = cfs_slice_ms(rqn, LAT, GRAN, _max=jnp.maximum)
            sb = slice_bits(rqn)
            ctx = jnp.where(rqn > 0, CTX, 0.0)
            kk = jnp.maximum(rqn + 1, 1)
            fr0 = st["fr"][safe]
            cy = dict(
                e=st["end"], L=st["clen"], Lb=st["cslb"],
                m=jnp.zeros(C, jnp.int32),
                rem=st["rem"][safe], vr=st["vr"][safe],
                # Seed with the live totals: each fire then does ONE
                # left-chained `cpu + L` exactly like the scalar
                # `task.cpu_time += chunk_len` (a zero-seeded subtotal
                # scatter-added later would reassociate the chain).
                cpu=st["cpu"][safe],
                np=jnp.zeros((C, KC), jnp.int32),
                nctx=jnp.zeros((C, KC), jnp.int32),
                sq=jnp.full((C, KC), -1, jnp.int32),
                frv=fr0, frs=~jnp.isnan(fr0),
                mv=st["minvr"], alive=act0, c=jnp.zeros(C, jnp.int32),
            )

            def step(cy, _):
                e, L, m = cy["e"], cy["L"], cy["m"]
                onem = jj == m[:, None]
                r0 = jnp.take_along_axis(cy["rem"], m[:, None], 1)[:, 0]
                v0 = jnp.take_along_axis(cy["vr"], m[:, None], 1)[:, 0]
                r2 = r0 - L
                v2 = vr_add(v0, cy["Lb"])
                # Stability: the pushback must land at the tail — at
                # or after every queued member's key (the push's seq is
                # fresher, so an equal vruntime still sorts after).
                qmax = jnp.max(jnp.where(valid & ~onem, cy["vr"],
                                         _VR_BOT), axis=1)
                fire = cy["alive"] & (r2 > _EPS) & (v2 >= qmax) & \
                    before_barrier(e, ta, tf, fcid)
                fm = fire[:, None] & onem
                rem_u = jnp.where(fm, r2[:, None], cy["rem"])
                vr_u = jnp.where(fm, v2[:, None], cy["vr"])
                m2 = (m + 1) % kk
                onem2 = jj == m2[:, None]
                fm2 = fire[:, None] & onem2
                r_n = jnp.take_along_axis(rem_u, m2[:, None], 1)[:, 0]
                v_pop = jnp.take_along_axis(vr_u, m2[:, None], 1)[:, 0]
                run2 = chunk_run_ms(r_n, s, _min=jnp.minimum,
                                    _max=jnp.maximum)
                e2 = chunk_end_ms(e, ctx, run2)    # the left fold
                stamp = fm2 & ~cy["frs"]
                return dict(
                    e=jnp.where(fire, e2, e),
                    L=jnp.where(fire, run2, L),
                    Lb=jnp.where(fire, sb, cy["Lb"]),
                    m=jnp.where(fire, m2, m),
                    rem=rem_u, vr=vr_u,
                    cpu=jnp.where(fm, cy["cpu"] + L[:, None], cy["cpu"]),
                    np=cy["np"] + fm.astype(jnp.int32),
                    nctx=cy["nctx"] +
                        (fm2 & (ctx > 0.0)[:, None]).astype(jnp.int32),
                    sq=jnp.where(fm, cy["c"][:, None], cy["sq"]),
                    frv=jnp.where(stamp, e[:, None], cy["frv"]),
                    frs=cy["frs"] | fm2,
                    mv=jnp.where(fire, jnp.maximum(cy["mv"], v_pop),
                                 cy["mv"]),
                    alive=fire,
                    c=cy["c"] + fire.astype(jnp.int32),
                ), None

            cy, _ = lax.scan(step, cy, None, length=W, unroll=8)

            did = act0 & (cy["c"] >= 1)
            vc = valid & did[:, None]
            tgt = jnp.where(vc, idx, N).reshape(-1)
            m_f = cy["m"]
            cur2 = jnp.take_along_axis(idx, m_f[:, None], 1)[:, 0]
            last2 = jnp.take_along_axis(idx, ((m_f - 1) % kk)[:, None],
                                        1)[:, 0]
            pushed = vc & (cy["sq"] >= 0)
            return dict(
                st,
                rem=st["rem"].at[tgt].set(cy["rem"].reshape(-1),
                                          mode="drop"),
                vr=st["vr"].at[tgt].set(cy["vr"].reshape(-1),
                                        mode="drop"),
                cpu=st["cpu"].at[tgt].set(cy["cpu"].reshape(-1),
                                          mode="drop"),
                npre=st["npre"].at[tgt].add(cy["np"].reshape(-1),
                                            mode="drop"),
                nctx=st["nctx"].at[tgt].add(cy["nctx"].reshape(-1),
                                            mode="drop"),
                fr=st["fr"].at[tgt].set(cy["frv"].reshape(-1),
                                        mode="drop"),
                seq=st["seq"].at[
                    jnp.where(pushed, idx, N).reshape(-1)
                ].set((st["seqc"][:, None] + cy["sq"]).reshape(-1),
                      mode="drop"),
                stat=st["stat"].at[tgt].set(2, mode="drop")
                    .at[jnp.where(did, cur2, N)].set(3, mode="drop"),
                cur=jnp.where(did, cur2, st["cur"]),
                last=jnp.where(did, last2, st["last"]),
                end=jnp.where(did, cy["e"], st["end"]),
                clen=jnp.where(did, cy["L"], st["clen"]),
                cslb=jnp.where(did, cy["Lb"], st["cslb"]),
                minvr=jnp.where(did, cy["mv"], st["minvr"]),
                seqc=st["seqc"] + jnp.where(did, cy["c"], 0),
                ev=st["ev"] + jnp.sum(jnp.where(did, cy["c"], 0),
                      dtype=jnp.int32),
            ), did

        # -- step 2: the window fast-forward, `window_engine` ---------

        # -- step 3: generic one-event CFS advance --------------------
        def cfs_advance(st, elig):
            """Advance every eligible CFS core one event: expire the
            in-flight chunk (complete or vruntime-charge + rq_push),
            then pick-and-start from the core's own runqueue — the
            exact hook order of `_run_core`."""
            cur = st["cur"]
            tid = jnp.where(elig, cur, 0)
            sidx = jnp.where(elig, cur, N)
            t_c, L = st["end"], st["clen"]
            rem2 = st["rem"][tid] - L
            d = chunk_completes(st["rem"][tid], L)  # rem2 <= _EPS, shared form
            pb = elig & ~d                      # pushback (chunk limit)
            de = elig & d                       # completion
            pidx = jnp.where(pb, cur, N)
            vr2 = vr_add(st["vr"][tid], st["cslb"])
            st = dict(
                st,
                rem=st["rem"].at[sidx].set(jnp.where(d, 0.0, rem2),
                                           mode="drop"),
                cpu=st["cpu"].at[sidx].add(L, mode="drop"),
                comp=st["comp"].at[jnp.where(de, cur, N)].set(t_c,
                                                              mode="drop"),
                vr=st["vr"].at[pidx].set(vr2, mode="drop"),
                npre=st["npre"].at[pidx].add(1, mode="drop"),
                seq=st["seq"].at[pidx].set(st["seqc"], mode="drop"),
                qcore=st["qcore"].at[pidx].set(cids, mode="drop"),
                stat=st["stat"].at[sidx].set(jnp.where(d, 4, 2),
                                             mode="drop"),
                seqc=st["seqc"] + pb.astype(jnp.int32),
                rqn=st["rqn"] + pb.astype(jnp.int32),
                last=jnp.where(elig, cur, st["last"]),
                cur=jnp.where(elig, -1, cur),
                # the in-flight record is consumed; an empty-rq pick
                # leaves the core idle (restored below if it picks).
                end=jnp.where(elig, _INF, st["end"]),
                clen=jnp.where(elig, 0.0, st["clen"]),
                ev=st["ev"] + jnp.sum(elig, dtype=jnp.int32),
            )
            picked, _ = cfs_pick_start(st, elig, t_c, st["last"])
            return picked

        # -- step 4: the minimal FIFO-group expiry --------------------
        def fifo_advance(st, fcid, t_f):
            c = fcid
            cur = st["cur"][c]
            tid = jnp.where(cur >= 0, cur, 0)
            L = st["clen"][c]
            rem2 = st["rem"][tid] - L
            d = chunk_completes(st["rem"][tid], L)  # rem2 <= _EPS, shared form
            st = dict(
                st,
                rem=st["rem"].at[tid].set(jnp.where(d, 0.0, rem2)),
                cpu=st["cpu"].at[tid].add(L),
                comp=jnp.where(d, st["comp"].at[tid].set(t_f), st["comp"]),
                stat=jnp.where(d, st["stat"].at[tid].set(4), st["stat"]),
                last=st["last"].at[c].set(cur),
                cur=st["cur"].at[c].set(-1),
                ev=st["ev"] + 1,
            )
            # -- budget expiry: migrate to a CFS core, round robin ----
            mig = ~d
            tgt = n_fifo + st["rrc"] % jnp.maximum(n_cfs, 1)
            midx = jnp.where(mig, tid, N)
            vrm = jnp.maximum(st["vr"][tid], st["minvr"][tgt])
            st_m = dict(
                st,
                npre=st["npre"].at[midx].add(1, mode="drop"),
                nmig=st["nmig"].at[midx].add(1, mode="drop"),
                rrc=st["rrc"] + mig.astype(jnp.int32),
                vr=st["vr"].at[midx].set(vrm, mode="drop"),
                seq=st["seq"].at[midx].set(st["seqc"][tgt], mode="drop"),
                qcore=st["qcore"].at[midx].set(tgt, mode="drop"),
                stat=st["stat"].at[midx].set(2, mode="drop"),
            )
            st_m["seqc"] = st_m["seqc"].at[jnp.where(mig, tgt, C)].add(
                1, mode="drop")
            st_m["rqn"] = st_m["rqn"].at[jnp.where(mig, tgt, C)].add(
                1, mode="drop")
            # kick(target): pick iff the target core is idle.
            kick = mig & (st_m["cur"][tgt] < 0)
            picked, _ = cfs_pick_start(
                st_m, (cids == tgt) & kick, jnp.full(C, t_f),
                st_m["last"])
            st = _sel_tree(mig, picked, st)

            # -- then the FIFO core itself picks from the global queue
            qm = st["stat"] == 1
            anyq = jnp.any(qm)
            ntid = jnp.argmax(qm).astype(jnp.int32)   # min tid: queue
            # order == arrival order == tid order (fresh tasks only).
            ctx = jnp.where(st["last"][c] == ntid, 0.0, CTX)
            fr_v = st["fr"][ntid]
            run = chunk_run_ms(st["rem"][ntid], budget,
                               _min=jnp.minimum, _max=jnp.maximum)
            nend = chunk_end_ms(t_f, ctx, run)
            started = dict(
                st,
                stat=st["stat"].at[ntid].set(3),
                fr=jnp.where(jnp.isnan(fr_v), st["fr"].at[ntid].set(t_f),
                             st["fr"]),
                nctx=jnp.where(ctx > 0.0, st["nctx"].at[ntid].add(1),
                               st["nctx"]),
                cur=st["cur"].at[c].set(ntid),
                end=st["end"].at[c].set(nend),
                clen=st["clen"].at[c].set(run),
            )
            return _sel_tree(anyq, started, st)

        # -- step 5: one arrival --------------------------------------
        def arrival_step(st, ta):
            tid = jnp.minimum(st["ptr"], N - 1)
            st = dict(st, ptr=st["ptr"] + 1, ev=st["ev"] + 1)

            # hybrid / plain-fifo routing: global FIFO queue + first
            # idle FIFO core (idle_core scans in cid order).
            st_q = dict(st, stat=st["stat"].at[tid].set(1))
            idle = is_fifo & (st_q["cur"] < 0)
            anyi = jnp.any(idle)
            c = jnp.argmax(idle).astype(jnp.int32)
            qm = st_q["stat"] == 1
            ntid = jnp.argmax(qm).astype(jnp.int32)
            ctx = jnp.where(st_q["last"][c] == ntid, 0.0, CTX)
            fr_v = st_q["fr"][ntid]
            run = chunk_run_ms(st_q["rem"][ntid], budget,
                               _min=jnp.minimum, _max=jnp.maximum)
            nend = chunk_end_ms(ta, ctx, run)
            st_d = dict(
                st_q,
                stat=st_q["stat"].at[ntid].set(3),
                fr=jnp.where(jnp.isnan(fr_v),
                             st_q["fr"].at[ntid].set(ta), st_q["fr"]),
                nctx=jnp.where(ctx > 0.0, st_q["nctx"].at[ntid].add(1),
                               st_q["nctx"]),
                cur=st_q["cur"].at[c].set(ntid),
                end=st_q["end"].at[c].set(nend),
                clen=st_q["clen"].at[c].set(run),
            )
            st_f = _sel_tree(anyi, st_d, st_q)

            # pure-CFS routing: least-loaded with rotating scan start,
            # early-exit on idle == lexicographic (nr, rotation) argmin.
            nr = st["rqn"] + (st["cur"] >= 0).astype(jnp.int32)
            rot = (cids - st["rr"]) % C
            nmin = jnp.min(nr)
            cand = nr == nmin
            rmin = jnp.min(jnp.where(cand, rot, C))
            core = jnp.argmax(cand & (rot == rmin)).astype(jnp.int32)
            vrp = jnp.maximum(st["vr"][tid], st["minvr"][core])
            st_c = dict(
                st,
                rr=(st["rr"] + 1) % C,
                vr=st["vr"].at[tid].set(vrp),
                seq=st["seq"].at[tid].set(st["seqc"][core]),
                qcore=st["qcore"].at[tid].set(core),
                stat=st["stat"].at[tid].set(2),
                seqc=st["seqc"].at[core].add(1),
                rqn=st["rqn"].at[core].add(1),
            )
            kick = st_c["cur"][core] < 0
            picked, _ = cfs_pick_start(
                st_c, (cids == core) & kick, jnp.full(C, ta),
                st_c["last"])
            st_c = _sel_tree(kick, picked, st_c)

            return _sel_tree(n_fifo > 0, st_f, st_c)

        # -- one-event micro step (PR 7's whole body): barriers, then
        # exactly one of {generic CFS advance on all eligible cores,
        # earliest FIFO expiry, next arrival}. No rotation, no scan.
        def micro(st):
            ta = t_arr(st)
            tf, fcid, anyf = fifo_candidate(st)
            elig = (~is_fifo) & (st["cur"] >= 0) & \
                before_barrier(st["end"], ta, tf, fcid)
            any_cfs = jnp.any(elig)
            do_f = anyf & ~any_cfs & (tf < ta)
            do_a = ~any_cfs & ~do_f & (st["ptr"] < n_tasks)
            st_cfs = cfs_advance(st, elig)
            st_fifo = fifo_advance(st, fcid, tf)
            st_arr = arrival_step(st, ta)
            return _sel_tree(
                any_cfs, st_cfs,
                _sel_tree(do_f, st_fifo, _sel_tree(do_a, st_arr, st)))

        # -- outer loop ------------------------------------------------
        max_it = jnp.int32(_MAX_ITERS_PER_TASK) * \
            jnp.maximum(n_tasks, 1) + 64

        def cond(st):
            live = (st["ptr"] < n_tasks) | jnp.any(st["cur"] >= 0)
            return live & (st["it"] < max_it)

        # Each retirement layer runs under its own named scope (the
        # SCOPES of repro.mc.spans), so a device trace splits a trip by
        # layer. `micro` calls the same advance functions, so an op's
        # layer is the OUTERMOST scope of its name stack.
        rotation_s, cycle_s, window_s, advance_s, micro_s = SCOPES

        def body(st):
            evl, fires, rqmax = st["evl"], st["fires"], st["rqmax"]
            rdeep, wtrips, lsl = st["rdeep"], st["wtrips"], st["lsl"]
            nlive = jnp.sum((st["stat"] >= 1) & (st["stat"] <= 3),
                            dtype=jnp.int32)
            ev0 = st["ev"]
            st = dict(st, it=st["it"] + 1)
            ta = t_arr(st)
            tf, fcid, _ = fifo_candidate(st)
            # ONE rotation serves both engines: the cycle commits task
            # state only on the cores it fires, so the pre-cycle rows
            # of every unfired core are still the exact pop order the
            # window needs (fired-but-still-eligible cores fall to the
            # rotation-free generic advance this iteration). Both read
            # only entries below a core's rqn; the cycle, at most KC - 1
            # of them.
            with jax.named_scope(rotation_s):
                depth = jnp.max(st["rqn"])
                # The pop prefix serves every trip on which no cell of
                # the batch is deep. The predicate is the batch's, not
                # the cell's, so under vmap it stays a real conditional
                # and the sort runs only on the deep trips.
                rot = lax.cond(
                    lax.pmax(depth, _CELLS) > KC - 1,
                    lambda *a: rotation_sorted(*a, C),
                    lambda *a: rotation_prefix(*a, C, KC - 1),
                    st["stat"], st["qcore"], st["vr"], st["seq"])
            with jax.named_scope(cycle_s):
                st, cdid = cycle_ff(st, ta, tf, fcid, rot)
            ev1 = st["ev"]

            tf, fcid, anyf = fifo_candidate(st)
            e = st["end"]
            elig = (~is_fifo) & (st["cur"] >= 0) & \
                before_barrier(e, ta, tf, fcid)
            any_cfs = jnp.any(elig)
            do_f = anyf & ~any_cfs & (tf < ta)
            do_a = ~any_cfs & ~do_f & (st["ptr"] < n_tasks)

            with jax.named_scope(window_s):
                # Like the rotation's, the predicate is the batch's: the
                # window runs on the trips where some core of some cell
                # retires its in-flight chunk by it, and elsewhere would
                # return its input unchanged.
                welig = elig & ~cdid
                wrun = lax.pmax(jnp.any(window_head(
                    st, welig, ta, tf, fcid, rot)).astype(jnp.int32), _CELLS)
                st_w, handled = lax.cond(
                    wrun > 0, window_ff,
                    lambda st, *_: (st, jnp.zeros(C, jnp.bool_)),
                    st, welig, ta, tf, fcid, rot)
            with jax.named_scope(advance_s):
                st_cfs = cfs_advance(st_w, elig & ~handled)
                st_fifo = fifo_advance(st, fcid, tf)
                st_arr = arrival_step(st, ta)
                stn = _sel_tree(
                    any_cfs, st_cfs,
                    _sel_tree(do_f, st_fifo, _sel_tree(do_a, st_arr, st)))
            # the window's events and firings count only where its
            # state is selected
            ev_w = jnp.where(any_cfs, st_w["ev"] - ev1, 0)
            fire_w = jnp.where(any_cfs, jnp.sum(handled, dtype=jnp.int32), 0)
            ev2 = stn["ev"]

            # Micro-step chain: the sparse phases (arrival interleave,
            # FIFO expiries, unstable pushes) advance one event at a
            # time; retiring a handful of them per while-loop trip with
            # the sort-free one-event machinery amortizes the fixed
            # per-iteration cost (rotation sorts, cycle/window scans,
            # state selects) over several events.
            with jax.named_scope(micro_s):
                stn = lax.fori_loop(0, _MICRO_STEPS,
                                    lambda _, s: micro(s), stn)
            return dict(
                stn,
                evl=evl + jnp.stack(
                    [ev1 - ev0, ev_w, ev2 - ev1 - ev_w, stn["ev"] - ev2]),
                fires=fires + jnp.stack(
                    [jnp.sum(cdid, dtype=jnp.int32), fire_w]),
                rqmax=jnp.maximum(rqmax, jnp.max(stn["rqn"])),
                rdeep=rdeep + (depth > KC - 1).astype(jnp.int32),
                wtrips=wtrips + wrun,
                lsl=lsl + nlive)

        out = lax.while_loop(cond, body, st)
        live = jnp.arange(N) < n_tasks
        ok = jnp.all(jnp.where(live, out["stat"] == 4, True)) & \
            (out["it"] < max_it)
        return dict(completion=out["comp"], first_run=out["fr"],
                    preemptions=out["npre"], ctx_switches=out["nctx"],
                    migrations=out["nmig"], cpu_time=out["cpu"],
                    ok=ok, n_iters=out["it"], n_events=out["ev"],
                    events_by_layer=out["evl"], fires_by_layer=out["fires"],
                    rq_depth_max=out["rqmax"],
                    rotation_deep_trips=out["rdeep"],
                    window_trips=out["wtrips"], live_slots=out["lsl"])

    return kernel


# One compiled program per (C, N) shape bucket; each call batches an
# arbitrary number of cells along the leading axis.
_GRID_CACHE: dict = {}
# The shapes and dtypes of the arguments `run_grid` passed, by
# (C, N, B), for `executables`.
_RUN_ARGS: dict = {}


def grid_kernel(n_cores: int, n_slots: int):
    key = (n_cores, n_slots)
    fn = _GRID_CACHE.get(key)
    if fn is None:
        kernel = make_cell_kernel(n_cores, n_slots)
        # the HLO module's name, `jit_mc_grid_c<C>_n<N>`, tells the
        # buckets' programs apart in a device trace
        kernel.__name__ = kernel.__qualname__ = \
            f"mc_grid_c{n_cores}_n{n_slots}"
        fn = jax.jit(jax.vmap(kernel, axis_name=_CELLS))
        _GRID_CACHE[key] = fn
    return fn


def executables() -> dict:
    """The compiled grid programs ``run_grid`` has run, by ``(C, N, B)``.
    JAX's in-memory caches hand back the executable that ran, so
    nothing compiles again. The HLO text of each (``.as_text()``) names
    the scope of every device op in its ``op_name`` metadata."""
    with jax.enable_x64(True):
        return {(C, N, B): grid_kernel(C, N).lower(*args).compile()
                for (C, N, B), args in sorted(_RUN_ARGS.items())}


def run_grid(arrival, service, n_tasks, n_fifo, limit, *, n_cores: int):
    """Advance a whole grid of cells in one device program.

    arrival, service : f64[B, N]
    n_tasks, n_fifo  : i32[B]
    limit            : f64[B]

    Returns a dict of [B, N] observable arrays (see ``make_cell_kernel``)
    and the per-cell counters ``ok``, ``n_iters``, ``n_events``,
    ``rq_depth_max``, ``rotation_deep_trips``, ``window_trips``,
    ``live_slots`` [B], ``events_by_layer``
    [B, 4] (``spans.EVENT_LAYERS`` order) and ``fires_by_layer`` [B, 2]
    (``spans.FIRE_LAYERS`` order), as NumPy, computed under x64 on
    whatever backend JAX selected.
    """
    B, n_slots = arrival.shape
    with jax.enable_x64(True):
        args = (jnp.asarray(arrival, jnp.float64),
                jnp.asarray(service, jnp.float64),
                jnp.asarray(n_tasks, jnp.int32),
                jnp.asarray(n_fifo, jnp.int32),
                jnp.asarray(limit, jnp.float64))
        _RUN_ARGS[(n_cores, n_slots, B)] = [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        out = grid_kernel(n_cores, n_slots)(*args)
        return {k: jax.device_get(v) for k, v in out.items()}
