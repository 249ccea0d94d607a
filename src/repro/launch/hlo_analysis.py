"""Trip-count-aware cost analysis of optimized (SPMD-partitioned) HLO.

XLA's ``compiled.cost_analysis()`` visits while-loop bodies ONCE — for
scan-over-layers models that undercounts FLOPs/bytes/collective traffic by
the layer count (verified in tests/test_hlo_analysis.py). This module
parses the optimized HLO text, builds the computation call graph, extracts
scan trip counts from loop conditions, and accumulates:

- ``flops``      — 2·M·N·K for every ``dot`` (descending into fusions),
- ``bytes``      — operand+result bytes of top-level ops (fusions as one
                   node; parameters/GTEs/tuples/bitcasts skipped) — an
                   HBM-traffic approximation in the spirit of XLA's own
                   bytes_accessed,
- ``collectives``— output bytes per collective kind (async ``-start``
                   counted once, ``-done`` skipped),

each multiplied by the enclosing while-loops' trip counts. Shapes in the
partitioned module are per-device, so totals are per-device numbers —
exactly what the roofline terms need.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_CALLED_RE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_CONST_RE = re.compile(r"\bconstant\((\d+)\)")


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _shapes_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        b = _DTYPE_BYTES.get(dt)
        if b:
            total += _shape_elems(dims) * b
    return total


def _first_shape(text: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",") if d]


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_text: str
    rest: str  # everything after the opening paren

    def called(self) -> List[str]:
        return _CALLED_RE.findall(self.rest)


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        header = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", s)
        if header and not s.startswith("%") or (header and "=" not in s.split("(")[0]):
            cur = Computation(header.group(1), [])
            comps[cur.name] = cur
            if s.startswith("ENTRY"):
                comps["__entry__"] = cur
            continue
        if s == "}" or s.startswith("}"):
            # keep cur until next header; nested braces don't occur at line start
            continue
        if cur is None:
            continue
        m = _OP_RE.match(s)
        if m:
            name, result_text, opcode, rest = m.groups()
            cur.ops.append(Op(name, opcode, result_text, rest))
    return comps


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _operand_names(op: Op) -> List[str]:
    """Operand %names (the part of ``rest`` before the attribute section)."""
    part = op.rest.split("), ")[0]
    return _OPERAND_NAME_RE.findall(part)


def _dot_flops(op: Op, shapes: Dict[str, List[int]]) -> float:
    """2 × result_elems × prod(contracting dims of lhs)."""
    res = _first_shape(op.result_text)
    if res is None:
        return 0.0
    _, rdims = res
    relems = 1
    for d in rdims:
        relems *= d
    # lhs shape: inline type if printed, else look up the operand name
    lhs = _first_shape(op.rest.split(",")[0])
    ldims = lhs[1] if lhs else None
    if ldims is None:
        names = _operand_names(op)
        if names and names[0] in shapes:
            ldims = shapes[names[0]]
    if ldims is None:
        return 0.0
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    contract = 1
    if mc:
        for ds in mc.group(1).split(","):
            if ds and int(ds) < len(ldims):
                contract *= ldims[int(ds)]
    return 2.0 * relems * contract


_SKIP_BYTES = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}

_SLICE_OPS = ("dynamic-slice", "slice", "gather")


def _fusion_input_bytes(comp: Optional["Computation"], operand_names: List[str],
                        bytes_by_name: Dict[str, int]) -> int:
    """Effective input bytes of a fusion: parameters consumed (only) by a
    slice-type op are billed at the slice's result size."""
    full = [bytes_by_name.get(nm, 0) for nm in operand_names]
    if comp is None:
        return sum(full)
    # parameter index -> op name
    param_names: Dict[int, str] = {}
    for op in comp.ops:
        if op.opcode == "parameter":
            m = re.match(r"(\d+)\)", op.rest)
            if m:
                param_names[int(m.group(1))] = op.name
    total = 0
    for i, nm in enumerate(operand_names):
        pname = param_names.get(i)
        billed = full[i] if i < len(full) else 0
        if pname is not None:
            sliced = None
            for op in comp.ops:
                if op.opcode in _SLICE_OPS and re.search(
                        r"%" + re.escape(pname) + r"\b", op.rest.split("), ")[0]):
                    sliced = _shapes_bytes(op.result_text)
                    break
            if sliced is not None:
                billed = min(billed, sliced)
        total += billed
    return total


def trip_count(cond: Computation) -> int:
    """Largest integer constant in the loop condition (scan loops compare
    the induction variable against the trip count)."""
    best = 1
    for op in cond.ops:
        for m in _CONST_RE.finditer(op.result_text + " " + op.rest):
            best = max(best, int(m.group(1)))
        if op.opcode == "constant":
            m2 = re.search(r"\bconstant\((\d+)\)", f"constant({op.rest}")
            if m2:
                best = max(best, int(m2.group(1)))
            m3 = re.match(r"(\d+)\)", op.rest)
            if m3:
                best = max(best, int(m3.group(1)))
    return best


def analyze(text: str) -> Dict[str, float]:
    comps = parse_module(text)
    entry = comps.get("__entry__")
    if entry is None:
        # fall back: last computation
        entry = list(comps.values())[-1]
    # module-wide name -> result shape dims (HLO op names are unique)
    shapes: Dict[str, List[int]] = {}
    bytes_by_name: Dict[str, int] = {}
    for comp in comps.values():
        for op in comp.ops:
            fs = _first_shape(op.result_text)
            if fs is not None:
                shapes[op.name] = fs[1]
            bytes_by_name[op.name] = _shapes_bytes(op.result_text)
    memo_flops: Dict[str, float] = {}

    def comp_flops(cname: str, stack=()) -> float:
        if cname in memo_flops:
            return memo_flops[cname]
        comp = comps.get(cname)
        if comp is None or cname in stack:
            return 0.0
        total = 0.0
        for op in comp.ops:
            if op.opcode == "dot":
                total += _dot_flops(op, shapes)
            elif op.opcode == "while":
                # rest contains condition=%c, body=%b
                cond_m = re.search(r"condition=%?([\w.\-]+)", op.rest)
                body_m = re.search(r"body=%?([\w.\-]+)", op.rest)
                tc = trip_count(comps[cond_m.group(1)]) if cond_m and cond_m.group(1) in comps else 1
                if body_m:
                    total += tc * comp_flops(body_m.group(1), stack + (cname,))
            elif op.opcode in ("fusion", "call", "conditional", "map", "reduce",
                               "reduce-window", "scatter", "sort", "all-reduce",
                               "reduce-scatter", "select-and-scatter", "custom-call"):
                for sub in op.called():
                    total += comp_flops(sub, stack + (cname,))
        memo_flops[cname] = total
        return total

    def comp_stats(cname: str, stack=()) -> Tuple[float, Dict[str, float]]:
        comp = comps.get(cname)
        if comp is None or cname in stack:
            return 0.0, {}
        bytes_total = 0.0
        coll: Dict[str, float] = {}
        for op in comp.ops:
            if op.opcode == "while":
                cond_m = re.search(r"condition=%?([\w.\-]+)", op.rest)
                body_m = re.search(r"body=%?([\w.\-]+)", op.rest)
                tc = trip_count(comps[cond_m.group(1)]) if cond_m and cond_m.group(1) in comps else 1
                if body_m:
                    b, c = comp_stats(body_m.group(1), stack + (cname,))
                    bytes_total += tc * b
                    for k, v in c.items():
                        coll[k] = coll.get(k, 0.0) + tc * v
                continue
            if op.opcode in ("call", "conditional"):
                for sub in op.called():
                    b, c = comp_stats(sub, stack + (cname,))
                    bytes_total += b
                    for k, v in c.items():
                        coll[k] = coll.get(k, 0.0) + v
                continue
            kind = None
            base = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
            if base in _COLLECTIVES and not op.opcode.endswith("-done"):
                kind = base
            if kind:
                b = _shapes_bytes(op.result_text)
                coll[kind] = coll.get(kind, 0.0) + b
                bytes_total += b
                continue
            if op.opcode in _SKIP_BYTES or op.opcode.endswith("-done"):
                continue
            # top-level op: result + operand bytes (fusion = one node).
            # Slice-type ops only touch the sliced region, not the whole
            # buffer — billing the full operand per loop iteration would
            # wildly overcount scans reading one layer's weights per step.
            if op.opcode in ("dynamic-slice", "slice", "gather"):
                bytes_total += 2 * _shapes_bytes(op.result_text)
                continue
            if op.opcode in ("dynamic-update-slice", "scatter"):
                names = _operand_names(op)
                upd = bytes_by_name.get(names[1], 0) if len(names) > 1 else 0
                bytes_total += 2 * upd
                continue
            bytes_total += _shapes_bytes(op.result_text)
            operand_part = op.rest.split("), ")[0]
            names = _OPERAND_NAME_RE.findall(operand_part)
            if op.opcode == "fusion" and names:
                # Input-fused slices (scan reading one layer's weights per
                # iteration) must be billed at the slice size, not the full
                # stacked buffer.
                called = op.called()
                eff = _fusion_input_bytes(comps.get(called[0]) if called else None,
                                          names, bytes_by_name)
                bytes_total += eff
            elif names:
                for nm in names:
                    bytes_total += bytes_by_name.get(nm, 0)
            else:
                bytes_total += _shapes_bytes(operand_part)  # inline types
        return bytes_total, coll

    flops = comp_flops(entry.name)
    bytes_total, coll = comp_stats(entry.name)
    # wire-bytes weighting: a ring all-reduce moves ~2× its output bytes
    # (reduce-scatter + all-gather phases); the others move ~1× output.
    coll_total = sum(v * (2.0 if k == "all-reduce" else 1.0) for k, v in coll.items())
    out = {"flops": flops, "bytes": bytes_total, "collective_bytes": coll_total}
    for k in _COLLECTIVES:
        out[f"coll_{k}"] = coll.get(k, 0.0)
    return out


# --- the expert layer's token permutation in a lowered (StableHLO) program

_FUNC_RE = re.compile(r"^\s*func\.func (?:public |private )?@([\w.$\-]+)\(", re.M)
_CALL_RE = re.compile(r"\bcall @([\w.$\-]+)\(")
_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([a-z]\w*)>")
_SIGNATURE_RE = re.compile(r"\}\) : \(([^)]*)\) -> ")
_BROADCAST_RE = re.compile(r"stablehlo\.broadcast_in_dim .*-> tensor<((?:\d+x)*)[a-z]\w*>")


def _dims(shape: str) -> List[int]:
    return [int(s) for s in shape.split("x") if s]


def _region_types(body: str, op: str) -> List[List[List[int]]]:
    """For each generic-form ``op`` (one with a region, e.g. sort or
    scatter), the shapes of its operands, read from the signature after
    its region."""
    out = []
    for m in re.finditer(rf'"stablehlo\.{op}"\(', body):
        sig = _SIGNATURE_RE.search(body, m.end())
        if sig:
            out.append([_dims(s) for s, _ in _TENSOR_RE.findall(sig.group(1))])
    return out


def permutation_census(text: str, t: int, k: int, d: int) -> Dict[str, int]:
    """How many times one call of a lowered program's entry runs each pass
    of an expert layer's token permutation that a dropless layer with T
    tokens, top-k and width D can do without:

    - ``sorts``: sorts whose keys are T·k long;
    - ``broadcasts``: broadcasts to a (…, k, D) buffer of T·k·D elements;
    - ``scatters``: scatters whose updates are T·k rows of D.

    ``text`` is ``jax.jit(f).lower(...).as_text()``; a loop body counts
    once, a private function once per call.  The layer as built in
    ``models/moe.py`` reads ``sorts`` 1 (the sort by expert, in the
    forward pass: the remat'd block saves the routing) and 0 of the rest,
    per MoE layer body."""
    starts = [(m.start(), m.group(1)) for m in _FUNC_RE.finditer(text)]
    bodies = {name: text[s:e] for (s, name), (e, _) in
              zip(starts, starts[1:] + [(len(text), "")])}
    n = t * k

    def direct(body: str) -> Dict[str, int]:
        sorts = sum(1 for types in _region_types(body, "sort")
                    if types and types[0] == [n])
        scatters = sum(1 for types in _region_types(body, "scatter")
                       if len(types) == 3 and types[2][-1:] == [d]
                       and math.prod(types[2]) == n * d)
        broadcasts = sum(1 for s in _BROADCAST_RE.findall(body)
                         if _dims(s)[-2:] == [k, d] and math.prod(_dims(s)) == n * d)
        return {"sorts": sorts, "broadcasts": broadcasts, "scatters": scatters}

    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str) -> Dict[str, int]:
        if name not in memo:
            body = bodies.get(name, "")
            out = direct(body)
            for callee in _CALL_RE.findall(body):
                for key, v in total(callee).items():
                    out[key] += v
            memo[name] = out
        return memo[name]

    entry = "main" if "main" in bodies else starts[0][1]
    return total(entry)
