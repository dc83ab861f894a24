"""Device time of the engine's while-loop trip, split by retirement layer.

The kernel wraps each layer of its loop body in a ``jax.named_scope``
(``repro.mc.spans.SCOPES``). The scope reaches the compiled program as
the ``op_name`` metadata of each HLO instruction; the device trace's
``XLA Ops`` events carry the instruction's name. So an op's layer is
read from the HLO text of the program that ran it (which
``repro.mc.kernels.executables`` hands back): the outermost ``mc_``
segment of its ``op_name``, ``loop`` where there is none. The TPU
compiler leaves some ops without an ``op_name``; ``hlo_program`` gives
them the scope of what they fuse or feed.

``XLA Ops`` events nest: a ``while`` spans the ops of its body. An op's
self time is its duration minus its direct children's, so self times
add up to the device's busy time once.

The profiler drops the trace's tail once it grows too large: the last
program's ops stop part-way and its ``XLA Modules`` event ends there.
So only whole sweeps are read: sweeps each of whose programs the trace
holds to its end. A ``while`` event is written when the loop ends, so a
program ran to its end inside the trace where the trace holds every
``while`` of its entry computation. Self time, trips and event counts
all come from those sweeps alone.
"""
from __future__ import annotations

import re
from typing import NamedTuple

import numpy as np

from . import counters, tracefile

try:        # the engine's own names; a program without them has no scopes
    from repro.mc.spans import EVENT_LAYERS, SCOPES
except ImportError:
    EVENT_LAYERS = SCOPES = ()
LOOP = "loop"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
# `%name = <shape> opcode(operands), attributes`, once `/*index=5*/`
# comments are gone; a tuple shape has spaces
_COMMENT = re.compile(r"/\*.*?\*/")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(?:\([^=]*?\)\S*|\S+)"
                    r"\s+([\w\-]+)\(([^)]*)\)(.*)$")
_OP_NAME = re.compile(r'op_name="(jit\([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,]+)")


class Program(NamedTuple):
    name: str       # the HLO module's name
    scopes: dict    # instruction name -> scope
    loops: frozenset  # the `while` instructions of the entry computation


def scope_of(op_name: str) -> str:
    """The outermost ``mc_`` scope of an ``op_name``, else ``loop``."""
    for seg in op_name.split("/"):
        if seg in SCOPES:
            return seg
    return LOOP


def hlo_program(text: str) -> Program:
    """The scope of each instruction of one HLO text, and its entry
    computation's loops.

    An instruction whose metadata names no traced op (the compiler's
    own copies, bitcasts and some fusions) takes the one scope of the
    instructions a fusion calls, else of its users (the layer a copy
    feeds), else of its operands; ``loop`` where they disagree."""
    m = _MODULE.search(text)
    scope, operands, users, called, body = {}, {}, {}, {}, {}
    loops = set()
    comp = ""
    for line in text.splitlines():
        if line and not line[0].isspace():
            comp = line.split(" ", 1)[0].lstrip("%")
            continue
        ins = _INSTR.match(_COMMENT.sub("", line))
        if ins is None:
            continue
        name, opcode, args, rest = ins.groups()
        if comp == "ENTRY" and opcode == "while":
            loops.add(name)
        op = _OP_NAME.search(rest)
        scope[name] = scope_of(op.group(1)) if op else None
        operands[name] = [a.strip().lstrip("%") for a in args.split(",")
                          if a.strip().startswith("%")]
        for a in operands[name]:
            users.setdefault(a, []).append(name)
        c = _CALLS.search(rest)
        if c:
            called[name] = c.group(1)
        body.setdefault(comp, []).append(name)

    def one(names):
        got = {scope.get(n) for n in names} - {None}
        return got.pop() if len(got) == 1 else None

    for name, comp in called.items():
        if scope[name] is None:
            scope[name] = one(body.get(comp, ()))
    changed = True
    while changed:
        changed = False
        for name in scope:
            if scope[name] is None:
                got = one(users.get(name, ())) or one(operands[name])
                if got:
                    scope[name], changed = got, True
    return Program(m.group(1) if m else "",
                   {n: s or LOOP for n, s in scope.items()},
                   frozenset(loops))


def self_ns(ops) -> np.ndarray:
    """Self time of each ``[start, dur, ...]`` op: its duration minus
    that of the ops directly nested in it. Ops on one line nest (a
    ``while`` spans its body's ops) or follow each other."""
    n = len(ops)
    if not n:
        return np.zeros(0)
    s = np.array([r[0] for r in ops], np.float64)
    d = np.array([r[1] for r in ops], np.float64)
    order = np.lexsort((-d, s))          # parents before their children
    s, d = s[order], d[order]
    e = s + d
    # depth: the ops before each in this order that are still open when
    # it starts; `ended` also counts the zero-length ops that start with
    # it, at or after it in the order, so those are taken off again
    pos = np.arange(n)
    ended = np.searchsorted(np.sort(e), s, side="right")
    zeros = np.concatenate([[0], np.cumsum(d == 0)])
    last = np.searchsorted(s, s, side="right")
    depth = pos - ended + (zeros[last] - zeros[pos])
    parent = np.full(n, -1)
    for k in range(1, int(depth.max()) + 1):
        up, kids = pos[depth == k - 1], pos[depth == k]
        parent[kids] = up[np.searchsorted(up, kids) - 1]
    kid = parent >= 0
    own = d - np.bincount(parent[kid], weights=d[kid], minlength=n)
    out = np.empty(n)
    out[order] = own
    return out


def module_of(tr: dict) -> np.ndarray:
    """Index in ``tr["modules"]`` of the program execution each of
    ``tr["ops"]`` ran in; -1 for an op outside any."""
    mods, ops = tr["modules"], tr["ops"]
    if not mods or not ops:
        return np.full(len(ops), -1)
    starts = np.array([m[0] for m in mods], np.float64)
    ends = starts + np.array([m[1] for m in mods], np.float64)
    s = np.array([r[0] for r in ops], np.float64)
    i = np.searchsorted(starts, s, side="right") - 1
    inside = (i >= 0) & (s < ends[np.maximum(i, 0)])
    return np.where(inside, i, -1)


def _program_name(module: str) -> str:
    return module.split("(", 1)[0].lstrip("%")


def whole_sweeps(tr: dict, sweeps, programs: dict, mod) -> list:
    """Indexes of the ``sweeps`` (in the order of the trace's
    ``bench.sweep`` spans) whose every grid program the trace holds to
    its end: one execution of a program of ``programs`` per program of
    the sweep, each with every entry ``while`` of its program among its
    ops."""
    loops = set().union(*(p.loops for p in programs.values()))
    seen = {}
    for r, m in zip(tr["ops"], mod.tolist()):
        if m >= 0 and r[2].lstrip("%") in loops:
            seen.setdefault(m, set()).add(r[2].lstrip("%"))
    spans = tracefile.spans(tr, tracefile.SWEEP)
    out = []
    for k, sw in enumerate(sweeps[:len(spans)]):
        a, b = spans[k]
        whole = [p.loops <= seen.get(j, set())
                 for j, (s, _, name) in enumerate(tr["modules"])
                 if a <= s < b
                 and (p := programs.get(_program_name(name))) is not None]
        if sw.programs and len(whole) == len(sw.programs) and all(whole):
            out.append(k)
    return out


def program_tables():
    """``hlo_program`` of every grid program the engine compiled in
    this process, by module name; None where the engine keeps no
    executables."""
    try:
        from repro.mc.kernels import executables
    except ImportError:
        return None
    progs = [hlo_program(ex.as_text()) for ex in executables().values()]
    return {p.name: p for p in progs}


def read(run):
    """The whole sweeps' device self ms by scope (``loop`` for unscoped
    ops), their trips, and the events their cells retired by layer
    (None where the engine does not count them); None without device
    ops, without the programs' scopes or without a whole sweep. Kept in
    the trace once worked out."""
    tr = run.trace
    if tr is None or not tr["ops"] or not SCOPES:
        return None
    if "_scopes" not in tr:
        tr["_scopes"] = _read(tr, run.sweeps)
    return tr["_scopes"]


def _read(tr: dict, sweeps):
    programs = program_tables()
    if not programs:
        return None
    mod = module_of(tr)
    keep = whole_sweeps(tr, sweeps, programs, mod)
    if not keep:
        return None
    spans = [tracefile.spans(tr, tracefile.SWEEP)[k] for k in keep]
    inside = [any(a <= s < b for a, b in spans)
              for s, _, _ in tr["modules"]]
    tables = [getattr(programs.get(_program_name(m[2])), "scopes", {})
              for m in tr["modules"]]
    ms = {k: 0.0 for k in SCOPES + (LOOP,)}
    for r, t, m in zip(tr["ops"], self_ns(tr["ops"]).tolist(),
                       mod.tolist()):
        if m >= 0 and inside[m]:
            ms[tables[m].get(r[2].lstrip("%"), LOOP)] += t / 1e6
    try:
        events = {layer: sum(r.mc_stats["events_by_layer"][layer]
                             for k in keep for r in sweeps[k].results
                             if r is not None)
                  for layer in EVENT_LAYERS}
    except KeyError:    # a program that does not count them
        events = None
    return {"ms": ms, "events": events, "sweeps": len(keep),
            "trips": sum(counters.device_trips(sweeps[k].programs)
                         for k in keep)}


def ms_per_trip(run, scope: str):
    """Self ms of ``scope`` in the whole sweeps over their trips."""
    got = read(run)
    if got is None or not got["trips"]:
        return None
    return got["ms"][scope] / got["trips"]


def events_per_ms(run, layer: str):
    """Events ``layer`` retired in the whole sweeps per ms of its
    scope's device self time there."""
    got = read(run)
    if got is None or got["events"] is None:
        return None
    ms = got["ms"]["mc_" + layer]
    return got["events"][layer] / ms if ms else None
