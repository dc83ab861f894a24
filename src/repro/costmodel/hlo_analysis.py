"""Post-optimization HLO text analysis for the cost model's features.

XLA's built-in ``compiled.cost_analysis()`` counts every while-loop body
ONCE (verified in this container: a scanned 8-layer MLP reports 1/8th of
the unrolled FLOPs), so scanned layer stacks would be undercounted by
n_layers. This module parses ``compiled.as_text()`` into a computation
call-graph, extracts while-loop trip counts from their condition
computations, and accumulates:

* dot FLOPs           (2 * prod(result dims) * prod(contracting dims))
* HBM traffic         (operand + result bytes of top-level ops; fusion
                       bodies excluded — a fusion reads its operands and
                       writes its result once)
* collective bytes    (operand bytes of all-gather / all-reduce /
                       reduce-scatter / all-to-all / collective-permute)

All quantities are multiplied by the static call multiplicity (ENTRY=1,
while bodies x trip count, nested loops compose). Numbers are PER DEVICE
(the module is the SPMD-partitioned one).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0,
    "f8e4m3b11fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1, "f4e2m1fn": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$")
_NAME_RE = re.compile(r"%([\w.\-]+)")


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


@dataclass
class Op:
    name: str
    type_str: str
    body: str
    kind: str
    operands: list = field(default_factory=list)


@dataclass
class Computation:
    name: str
    ops: dict = field(default_factory=dict)        # name -> Op
    order: list = field(default_factory=list)
    is_fusion_body: bool = False
    is_entry: bool = False
    root: str = ""


def _split_type_and_rest(rest: str):
    """'(f32[2]{0}, s32[]) tuple(...)' -> (type_str, op_body)."""
    rest = rest.strip()
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].strip()
    sp = rest.find(" ")
    if sp < 0:
        return rest, ""
    return rest[:sp], rest[sp + 1:].strip()


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        # computation header: '%name (args) -> type {' or 'ENTRY %name ...'
        if stripped.endswith("{") and ("(" in stripped) and "=" not in \
                stripped.split("(")[0]:
            header = stripped
            is_entry = header.startswith("ENTRY")
            m = _NAME_RE.search(header)
            name = m.group(1) if m else f"comp{len(comps)}"
            cur = Computation(name=name, is_entry=is_entry,
                              is_fusion_body="fused" in name)
            comps[name] = cur
            # parameters: 'param: f32[...]' pairs inside header parens
            sig = header[header.find("(") + 1:header.rfind("->")]
            for pm in re.finditer(r"([\w.\-]+):\s*([^,)]+)", sig):
                pname, ptype = pm.group(1), pm.group(2).strip()
                cur.ops[pname] = Op(pname, ptype, "", "parameter")
            continue
        if stripped == "}" or stripped == "})":
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_RE.match(stripped)
        if not m:
            continue
        name = m.group(1).lstrip("%")
        if stripped.startswith("ROOT"):
            cur.root = name
        type_str, body = _split_type_and_rest(m.group(2))
        kind_m = re.match(r"([\w\-]+)", body)
        kind = kind_m.group(1) if kind_m else ""
        op = Op(name, type_str, body, kind)
        # operand names: inside the FIRST parens of the body
        p0 = body.find("(")
        if p0 >= 0:
            depth, i = 0, p0
            for i in range(p0, len(body)):
                depth += body[i] == "("
                depth -= body[i] == ")"
                if depth == 0:
                    break
            op.operands = [x for x in
                           _NAME_RE.findall(body[p0:i + 1])]
        cur.ops[name] = op
        cur.order.append(name)
    return comps


def _trip_count(cond: Computation) -> int:
    """Largest integer constant in the while condition (scan bound)."""
    best = 1
    for op in cond.ops.values():
        for m in re.finditer(r"constant\((\d+)\)", op.body):
            best = max(best, int(m.group(1)))
    return best


_ATTR_COMP = {
    "while": ("body=", "condition="),
    "fusion": ("calls=",),
    "reduce": ("to_apply=",),
    "sort": ("to_apply=",),
    "map": ("to_apply=",),
    "scatter": ("to_apply=",),
    "all-reduce": ("to_apply=",),
    "reduce-scatter": ("to_apply=",),
    "select-and-scatter": ("select=", "scatter="),
    "call": ("to_apply=",),
    "custom-call": ("called_computations=",),
    "conditional": ("true_computation=", "false_computation=",
                    "branch_computations=",),
}


def analyze(text: str) -> dict:
    comps = parse_hlo(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {"flops": 0, "bytes": 0, "collective_bytes": 0,
                "collectives": {}}

    # multiplicity propagation (memoized DFS from entry)
    mult: dict[str, float] = defaultdict(float)
    mult[entry.name] = 1.0
    stack = [entry.name]
    seen_edges = set()
    while stack:
        cname = stack.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        m_c = mult[cname]
        for opname in comp.order:
            op = comp.ops[opname]
            attrs = _ATTR_COMP.get(op.kind, ())
            for attr in attrs:
                for am in re.finditer(re.escape(attr) +
                                      r"\{?%?([\w.\-]+)", op.body):
                    callee = am.group(1)
                    if callee not in comps:
                        continue
                    factor = 1.0
                    if op.kind == "while" and attr == "body=":
                        cond_m = re.search(r"condition=%?([\w.\-]+)",
                                           op.body)
                        if cond_m and cond_m.group(1) in comps:
                            factor = _trip_count(comps[cond_m.group(1)])
                    edge = (cname, opname, callee)
                    if edge in seen_edges:
                        continue
                    seen_edges.add(edge)
                    mult[callee] += m_c * factor
                    stack.append(callee)

    flops = 0.0
    traffic = 0.0
    coll_bytes = 0.0
    coll_counts: dict[str, float] = defaultdict(float)

    def _fusion_dus_bytes(op: Op) -> Optional[float]:
        """Fusion whose root is an in-place dynamic-update-slice (the
        scan-carried KV-cache write pattern): XLA aliases the big
        operand, so real traffic is ~2x the UPDATE slice, not the whole
        buffer. Returns None when the pattern doesn't apply."""
        cm = re.search(r"calls=%?([\w.\-]+)", op.body)
        if not cm or cm.group(1) not in comps:
            return None
        body_c = comps[cm.group(1)]
        root = body_c.ops.get(body_c.root)
        if root is None or root.kind != "dynamic-update-slice":
            return None
        if len(root.operands) > 1 and root.operands[1] in body_c.ops:
            upd = shape_bytes(body_c.ops[root.operands[1]].type_str)
        else:
            upd = 0.0
        # other (small) fusion inputs still stream through HBM; the
        # largest operand is the aliased buffer itself -> excluded
        others = sorted(shape_bytes(comp.ops[o].type_str)
                        for o in op.operands if o in comp.ops)
        small = sum(others[:-1]) if others else 0.0
        return 2.0 * upd + small

    def op_traffic(comp: Computation, op: Op) -> float:
        """HBM bytes for one op. Slicing/indexing ops only touch the
        slice (XLA does not copy the full operand); control-flow ops
        carry no traffic themselves (their bodies are counted)."""
        res = shape_bytes(op.type_str)
        if op.kind in ("while", "conditional", "call"):
            return 0.0
        if op.kind == "fusion":
            dus = _fusion_dus_bytes(op)
            if dus is not None:
                return dus
            # XLA names fusions after their constituent ops. Two
            # slice-pattern cases where the big operand is NOT streamed:
            # (a) in-place cache writes ("dynamic-update-slice_*"):
            #     traffic = 2x everything except the aliased buffer
            #     (the buffer-sized operand). The CPU backend also wraps
            #     these in bf16<->f32 converts (no native bf16 dot) that
            #     a TPU build would not emit.
            # (b) slice reads ("*bitcast*"/"*slice*" fusions whose
            #     result is far smaller than the largest operand):
            #     traffic = 2x result + small operands.
            ops_b = sorted(shape_bytes(comp.ops[o].type_str)
                           for o in op.operands if o in comp.ops)
            if "dynamic-update-slice" in op.name:
                small = [b for b in ops_b if b < res]
                return 2.0 * sum(small)
            if (("bitcast" in op.name or "slice" in op.name)
                    and ops_b and res * 8 <= ops_b[-1]):
                return 2.0 * res + sum(ops_b[:-1])
        if op.kind in ("dynamic-slice", "gather", "slice"):
            return 2.0 * res
        if op.kind in ("dynamic-update-slice",):
            upd = (shape_bytes(comp.ops[op.operands[1]].type_str)
                   if len(op.operands) > 1 and op.operands[1] in comp.ops
                   else res)
            return 2.0 * upd
        if op.kind == "scatter":
            upd = (shape_bytes(comp.ops[op.operands[2]].type_str)
                   if len(op.operands) > 2 and op.operands[2] in comp.ops
                   else res)
            return 3.0 * upd
        ob = sum(shape_bytes(comp.ops[o].type_str)
                 for o in op.operands if o in comp.ops)
        return ob + res

    for cname, comp in comps.items():
        m_c = mult.get(cname, 0.0)
        if m_c == 0.0:
            continue
        for opname in comp.order:
            op = comp.ops[opname]
            if op.kind in ("parameter", "constant", "get-tuple-element",
                           "tuple", "bitcast"):
                continue
            # -- dot flops (counted everywhere, incl. fusion bodies)
            if op.kind in ("dot", "convolution"):
                _, rdims = shape_dims(op.type_str)
                contract = 1
                cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}",
                               op.body)
                if cm and op.operands:
                    lhs = comp.ops.get(op.operands[0])
                    if lhs is not None:
                        _, ldims = shape_dims(lhs.type_str)
                        for idx in cm.group(1).split(","):
                            if idx and int(idx) < len(ldims):
                                contract *= ldims[int(idx)]
                import numpy as _np
                flops += m_c * 2.0 * float(_np.prod(rdims or [0])) \
                    * contract
            # -- collectives
            if op.kind in COLLECTIVES:
                ob = sum(shape_bytes(comp.ops[o].type_str)
                         for o in op.operands if o in comp.ops)
                coll_bytes += m_c * ob
                coll_counts[op.kind] += m_c
            # -- HBM traffic: top-level ops only (fusion bodies excluded)
            if not comp.is_fusion_body:
                traffic += m_c * op_traffic(comp, op)
    return {
        "flops": flops,
        "bytes": traffic,
        "collective_bytes": coll_bytes,
        "collectives": dict(coll_counts),
        "n_computations": len(comps),
    }
