"""Trip-count-aware cost analysis of optimized HLO text.

XLA's built-in ``compiled.cost_analysis()`` counts every while-loop body
exactly once, so any scan-over-layers / scan-over-microbatches program
(i.e. every cell in this framework) is undercounted by ~L x.  This
module re-derives the roofline inputs from ``compiled.as_text()``:

  1. split the module into computations and build a module-wide
     op-name -> result-shape table (operands are bare %name refs);
  2. find each ``while`` op's body/condition and extract the trip count
     from the condition's ``compare(iter, constant(N)), direction=LT``;
  3. propagate execution multipliers through the call graph
     (while bodies x trip count, fusions/calls x 1 per caller execution);
  4. FLOPs: 2*M*N*K for every ``dot`` (wherever it appears, incl. inside
     fusion computations), x multiplier;
  5. bytes: operand + result buffer sizes of *top-level* ops in
     executable computations (entry + while bodies + conditional
     branches), x multiplier — fusion-internal ops are VMEM-resident and
     excluded, approximating HBM traffic like HloCostAnalysis does;
  6. collective bytes: result sizes of all-reduce / all-gather /
     reduce-scatter / all-to-all / collective-permute, x multiplier,
     split by op kind.

Validated in tests/test_hlo_cost.py against XLA's own counts on
loop-free programs and against scanned-vs-unrolled equivalence.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "while", "conditional", "call", "after-all",
               "add-dependency"}


def _shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    """(elements, bytes) summed over every dtype[dims] group."""
    elems = 0
    bts = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        bts += n * _DTYPE_BYTES[dt]
    return elems, bts


@dataclasses.dataclass
class Op:
    name: str
    rest: str        # everything right of '='
    opcode: str
    result_shape: str


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    ops: List[Op]
    root: Optional[Op]


_RESULT_OPCODE = re.compile(
    r"^(\((?:[^()]|\([^)]*\))*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z0-9\-]+)")


def parse_module(hlo: str):
    """-> (computations dict, name->result_shape table)."""
    comps: Dict[str, Computation] = {}
    shapes: Dict[str, str] = {}
    cur: Optional[Computation] = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_HDR.match(stripped)
            if m and stripped.endswith("{"):
                cur = Computation(name=m.group(2), is_entry=bool(m.group(1)),
                                  ops=[], root=None)
            continue
        if stripped == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        rm = _RESULT_OPCODE.match(rest)
        if not rm:
            continue
        op = Op(name=name, rest=rest, opcode=rm.group(2),
                result_shape=rm.group(1))
        shapes[name] = op.result_shape
        cur.ops.append(op)
        if line.lstrip().startswith("ROOT"):
            cur.root = op
    return comps, shapes


def _operand_names(op: Op) -> List[str]:
    """Names referenced inside the op's argument list (first paren group
    after the opcode), excluding computation references."""
    idx = op.rest.find(op.opcode)
    tail = op.rest[idx + len(op.opcode):]
    if not tail.startswith("("):
        return []
    depth = 0
    end = 0
    for i, ch in enumerate(tail):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return _OPERAND_RE.findall(tail[: end + 1])


def _trip_count(cond: Computation) -> int:
    consts: Dict[str, int] = {}
    for op in cond.ops:
        m = re.search(r"constant\((-?\d+)\)", op.rest)
        if m and ("s32[]" in op.rest or "s64[]" in op.rest
                  or "u32[]" in op.rest):
            consts[op.name] = int(m.group(1))
    root = cond.root or (cond.ops[-1] if cond.ops else None)
    if root is None or "compare" not in root.rest:
        return 1
    if "direction=LT" not in root.rest and "direction=GT" not in root.rest:
        return 1
    for name, val in consts.items():
        if re.search(r"%" + re.escape(name) + r"\b", root.rest):
            return max(val, 1)
    return max(consts.values(), default=1)


def _dot_flops(op: Op, shapes: Dict[str, str]) -> float:
    res_elems, _ = _shape_elems_bytes(op.result_shape)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
    operands = _operand_names(op)
    if not m or not operands:
        return 2.0 * res_elems
    lhs_shape = shapes.get(operands[0], "")
    mm = _SHAPE_RE.search(lhs_shape)
    if not mm:
        return 2.0 * res_elems
    lhs_dims = [int(d) for d in mm.group(2).split(",") if d]
    k = 1
    for idx in (int(i) for i in m.group(1).split(",") if i):
        if idx < len(lhs_dims):
            k *= lhs_dims[idx]
    # batch dims are part of res_elems already; contracted dims give K
    return 2.0 * res_elems * k


def analyze_hlo(hlo: str) -> Dict[str, float]:
    comps, shapes = parse_module(hlo)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
                "collectives_by_op": {}}

    # ---- execution multipliers over the call graph (topological-ish:
    # process callers before callees by repeated relaxation) ----
    mult: Dict[str, float] = {entry.name: 1.0}
    executable = {entry.name}
    order = [entry.name]
    visited = {entry.name}
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult.get(cname, 1.0)
        for op in comp.ops:
            body = re.search(r"body=%?([\w\.\-]+)", op.rest)
            cond = re.search(r"condition=%?([\w\.\-]+)", op.rest)
            if body and cond and cond.group(1) in comps:
                # XLA annotates scan-derived loops with the exact count
                tc = re.search(r'known_trip_count[^\d]*(\d+)', op.rest)
                trips = (int(tc.group(1)) if tc
                         else _trip_count(comps[cond.group(1)]))
                for tgt in (body.group(1), cond.group(1)):
                    mult[tgt] = mult.get(tgt, 0.0) + m * trips
                    if tgt not in visited:
                        visited.add(tgt)
                        order.append(tgt)
                executable.add(body.group(1))
                continue
            call = re.search(r"(?:calls|to_apply)=%?([\w\.\-]+)", op.rest)
            if call:
                tgt = call.group(1)
                mult[tgt] = mult.get(tgt, 0.0) + m
                if tgt not in visited:
                    visited.add(tgt)
                    order.append(tgt)
            br = re.search(r"branch_computations=\{([^}]*)\}", op.rest)
            if br:
                for tgt in re.findall(r"%?([\w\.\-]+)", br.group(1)):
                    mult[tgt] = mult.get(tgt, 0.0) + m
                    executable.add(tgt)
                    if tgt not in visited:
                        visited.add(tgt)
                        order.append(tgt)

    flops = 0.0
    bts = 0.0
    coll: Dict[str, float] = {}
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for op in comp.ops:
            code = op.opcode
            if code in ("dot", "convolution"):
                flops += m * _dot_flops(op, shapes)
            if cname in executable and code not in _SKIP_BYTES:
                _, rb = _shape_elems_bytes(op.result_shape)
                if code in ("slice", "dynamic-slice", "gather"):
                    ob = rb                     # reads only the window
                elif code == "dynamic-update-slice":
                    # in-place: writes + reads the update window only
                    upd = _operand_names(op)
                    _, ub = _shape_elems_bytes(
                        shapes.get(upd[1], "") if len(upd) > 1 else "")
                    bts += m * 2 * ub
                    continue
                else:
                    ob = 0
                    for oname in _operand_names(op):
                        _, b1 = _shape_elems_bytes(shapes.get(oname, ""))
                        ob += b1
                bts += m * (rb + ob)
                base = next((c for c in COLLECTIVES if code.startswith(c)),
                            None)
                if base is not None and not code.endswith("-done"):
                    coll[base] = coll.get(base, 0.0) + m * rb
    return {"flops": flops, "bytes": bts,
            "collective_bytes": sum(coll.values()),
            "collectives_by_op": coll}
