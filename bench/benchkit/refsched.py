"""Plain reference of one host's scheduling and its bill.

A straightforward discrete-event simulation of the three policies the
paper compares (arXiv:2411.08448 Secs. III-IV) on one host with ``C``
cores, written for reading, not for speed, and importing nothing of the
program:

* ``fifo``: one global queue, every task runs to completion on the
  first idle core (lowest core id first).
* ``cfs``: per-core runqueues ordered by (vruntime, push sequence). A
  pick pops the head and grants ``max(24 / n, 3)`` ms, ``n`` being the
  runqueue length after the pop; an expired slice adds the slice to the
  task's vruntime and pushes it back. Arrivals go to the least-loaded
  core, scanning from a start that rotates by one per arrival, an idle
  core winning at once; a task entering a runqueue starts at the
  core's ``min_vruntime``, the largest vruntime popped there so far.
* ``hybrid``: cores ``0 .. n_fifo-1`` form a FIFO group fed by the
  global queue, with a budget of ``max(limit - cpu_time, 0.01)`` ms;
  a task that exhausts it migrates, round robin, onto the CFS cores,
  whose idle target picks at once. Then the FIFO core picks again.

Every chunk charges a 0.06 ms context switch unless the core's
previous task runs again, and is at least 1e-9 ms long; a chunk ends
its task when the remaining work minus the chunk is at most 1e-9 ms.
Events at one instant run arrivals first (in task order), then chunk
expiries in core order.

``dtype`` sets the precision of every time: ``float`` is IEEE double,
as the scheduler's configuration states; ``numpy.float32`` is the
lower precision the correctness control runs in.
"""
from __future__ import annotations

import heapq
import math
from bisect import insort
from collections import deque

import numpy as np

SCHED_LATENCY_MS = 24.0
MIN_GRANULARITY_MS = 3.0
CTX_SWITCH_MS = 0.06
EPS_MS = 1e-9
FIFO_BUDGET_FLOOR_MS = 0.01
# The hybrid's defaults: half the cores in the FIFO group (the paper's
# best split, Fig. 11) and the time limit at the trace's p90.
HYBRID_LIMIT_MS = 1633.0
# AWS Lambda x86 prices (2024): per GB-second of wall-clock execution,
# and per request.
PRICE_PER_GB_SECOND = 1.66667e-5
PRICE_PER_REQUEST = 2.0e-7

_ARRIVAL, _EXPIRY = 0, 2


def simulate(arrival, service, n_cores: int, policy: str,
             n_fifo=None, limit_ms: float = HYBRID_LIMIT_MS,
             dtype=float) -> dict:
    """Run one host to completion. Returns per-task arrays
    ``completion``, ``first_run``, ``cpu_time`` (ms) and
    ``preemptions``, ``ctx_switches``, ``migrations``."""
    F = dtype
    C, n = n_cores, len(arrival)
    if policy == "fifo":
        n_fifo, limit_ms = C, math.inf
    elif policy == "cfs":
        n_fifo, limit_ms = 0, math.inf
    elif policy != "hybrid":
        raise ValueError(f"unknown policy {policy!r}")
    elif n_fifo is None:
        n_fifo = C // 2
    lat, gran, ctx_ms = F(SCHED_LATENCY_MS), F(MIN_GRANULARITY_MS), \
        F(CTX_SWITCH_MS)
    eps, floor, limit = F(EPS_MS), F(FIFO_BUDGET_FLOOR_MS), F(limit_ms)
    zero = F(0.0)

    arr = [F(a) for a in arrival]
    rem = [F(s) for s in service]
    cpu = [zero] * n
    vr = [zero] * n
    first = [None] * n
    comp = [None] * n
    npre, nctx, nmig = [0] * n, [0] * n, [0] * n

    cur = [None] * C          # running task per core
    clen = [zero] * C         # length of the running chunk
    last = [None] * C         # the core's previous task
    rq = [[] for _ in range(C)]   # sorted (vruntime, seq, task)
    rq_seq = [0] * C
    minvr = [zero] * C
    fifo_q: deque = deque()
    rr_place = 0
    rr_mig = 0

    heap = [(arr[i], _ARRIVAL, i) for i in range(n)]
    heapq.heapify(heap)

    def start(c, task, t, run_limit):
        ctx = zero if last[c] == task else ctx_ms
        if first[task] is None:
            first[task] = t
        run = rem[task] if run_limit is None else min(rem[task], run_limit)
        run = max(run, eps)
        if ctx > zero:
            nctx[task] += 1
        cur[c] = task
        clen[c] = run
        heapq.heappush(heap, ((t + ctx) + run, _EXPIRY, c))

    def rq_push(c, task):
        insort(rq[c], (vr[task], rq_seq[c], task))
        rq_seq[c] += 1

    def pick(c, t):
        if c < n_fifo:
            if fifo_q:
                task = fifo_q.popleft()
                start(c, task, t, max(limit - cpu[task], floor))
        elif rq[c]:
            v, _, task = rq[c].pop(0)
            minvr[c] = max(minvr[c], v)
            start(c, task, t, max(lat / max(1, len(rq[c])), gran))

    while heap:
        t, kind, x = heapq.heappop(heap)
        if kind == _ARRIVAL:
            if n_fifo > 0:
                fifo_q.append(x)
                for c in range(n_fifo):
                    if cur[c] is None:
                        pick(c, t)
                        break
                continue
            best, best_nr = None, None
            start_c = rr_place
            rr_place = (rr_place + 1) % C
            for i in range(C):
                c = (start_c + i) % C
                nr = len(rq[c]) + (cur[c] is not None)
                if nr == 0:
                    best = c
                    break
                if best_nr is None or nr < best_nr:
                    best, best_nr = c, nr
            vr[x] = max(vr[x], minvr[best])
            rq_push(best, x)
            if cur[best] is None:
                pick(best, t)
            continue

        c, task = x, cur[x]
        rem[task] = rem[task] - clen[c]
        cpu[task] = cpu[task] + clen[c]
        cur[c] = None
        last[c] = task
        if rem[task] <= eps:
            comp[task] = t
        elif c < n_fifo:
            npre[task] += 1
            nmig[task] += 1
            tgt = n_fifo + rr_mig % (C - n_fifo)
            rr_mig += 1
            vr[task] = max(vr[task], minvr[tgt])
            rq_push(tgt, task)
            if cur[tgt] is None:
                pick(tgt, t)
        else:
            vr[task] = vr[task] + clen[c]
            npre[task] += 1
            rq_push(c, task)
        pick(c, t)

    if any(v is None for v in comp):
        raise RuntimeError("reference left tasks unfinished")
    return {"completion": np.array(comp, np.float64),
            "first_run": np.array(first, np.float64),
            "cpu_time": np.array(cpu, np.float64),
            "preemptions": np.array(npre, np.int64),
            "ctx_switches": np.array(nctx, np.int64),
            "migrations": np.array(nmig, np.int64)}


def rollup(arrival, mem_mb, out: dict) -> dict:
    """A cell's summary row, as the paper reports it (OSTEP metrics,
    Sec. II-B; cost, Table I): percentiles in seconds and the bill in
    USD, every invocation billed for its wall-clock execution."""
    # Finished tasks in (completion, task) order, so that the mean sums
    # in the order a bill is read.
    order = np.lexsort((np.arange(len(out["completion"])),
                        out["completion"]))
    completion, first_run = out["completion"][order], \
        out["first_run"][order]
    arrival = np.asarray(arrival, np.float64)[order]
    mem_mb = np.asarray(mem_mb)[order]
    execution = completion - first_run
    cost = math.fsum(
        float(e) * ((float(m) / 1024.0) * PRICE_PER_GB_SECOND / 1000.0)
        + PRICE_PER_REQUEST for e, m in zip(execution, mem_mb))
    n = len(completion)
    return {
        "n": n,
        "preemptions": int(out["preemptions"].sum()),
        "ctx_switches": int(out["ctx_switches"].sum()),
        "mean_execution_s": float(execution.mean()) / 1e3,
        "p50_execution_s": float(np.percentile(execution, 50)) / 1e3,
        "p99_execution_s": float(np.percentile(execution, 99)) / 1e3,
        "p99_response_s":
            float(np.percentile(first_run - arrival, 99)) / 1e3,
        "p99_turnaround_s":
            float(np.percentile(completion - arrival, 99)) / 1e3,
        "makespan_s": float(completion.max()) / 1e3,
        "cost_usd": cost,
        "usd_per_1k_requests": cost / n * 1000.0,
    }
