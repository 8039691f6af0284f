"""Scan-aware cost extraction from compiled HLO text.

``compiled.cost_analysis()`` counts a while-loop body ONCE regardless of its
trip count (tests/test_roofline.py::TestHloCost::
test_scan_flops_scaled_by_trip_count), which undercounts every scanned
layer stack by ~n_layers.  This module rebuilds
FLOP / byte / collective totals from the HLO text itself:

  * split the module into named computations;
  * per computation: matmul FLOPs from `dot(` ops (output size x contracting
    size x 2 — elementwise FLOPs are negligible next to dots for these
    models), HBM byte proxy from op result sizes + entry parameters, and
    collective payload bytes;
  * build the call graph (while bodies/conditions, fusions, calls,
    conditionals) and multiply each computation's cost by the product of
    enclosing while trip counts (parsed from the loop condition's compare
    constant).

Validated against analytic 6*N*D for the dense LMs (test_roofline.py).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

__all__ = ["hlo_costs"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# header lines sit at column 0 and may contain nested tuple types in args
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]"
    r"(?:\{[^}]*\})?))\s+([\w\-]+)\("
)
_CALL_ATTR = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_WIRE_FACTOR = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}


def _dims(shape_str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, []
    dt = m.group(1)
    dims = [int(d) for d in m.group(2).split(",") if d] if m.group(2) else []
    return dt, dims


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(txt: str) -> Dict[str, list[str]]:
    comps: Dict[str, list[str]] = {}
    cur = None
    for line in txt.splitlines():
        m = _COMP_HDR.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            if line.startswith("}"):
                cur = None
                continue
            comps[cur].append(line)
    return comps


_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _dot_flops(line: str, result_shape: str, symtab: dict) -> float:
    """2 x |output| x |contraction| for a dot op.  Final HLO operand refs are
    bare names, so the lhs shape comes from the computation's symbol table."""
    _, out_dims = _dims(result_shape)
    out_n = 1
    for d in out_dims:
        out_n *= d
    cd = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    if cd is None:
        return 0.0
    args = line[line.index("("):]
    names = _OPERAND_RE.findall(args)
    lhs_shape = symtab.get(names[0]) if names else None
    if lhs_shape is None:
        return 0.0
    _, lhs_dims = _dims(lhs_shape)
    contract = 1
    for i in (int(x) for x in cd.group(1).split(",") if x):
        if i < len(lhs_dims):
            contract *= lhs_dims[i]
    return 2.0 * out_n * contract


def hlo_costs(txt: str) -> dict:
    comps = _split_computations(txt)

    # per-computation raw costs + call edges
    raw = {}
    edges = defaultdict(set)           # parent -> {child}
    while_of = {}                      # body/cond comp -> trip count
    fusion_internal = set()            # comps whose ops never touch HBM
    for name, lines in comps.items():
        flops = byts = 0.0
        colls: Dict[str, dict] = {}
        symtab = {}
        for line in lines:
            m = _OP_RE.match(line)
            if m:
                symtab[m.group(1)] = m.group(2)
        for line in lines:
            if "fusion(" in line:
                for callee in _CALL_ATTR.findall(line):
                    fusion_internal.add(callee)
            m = _OP_RE.match(line)
            if m:
                _, result_shape, op = m.groups()
                rb = _shape_bytes(result_shape)
                if op not in ("parameter", "get-tuple-element", "tuple",
                              "bitcast", "constant"):
                    byts += rb
                if op == "dot":
                    flops += _dot_flops(line, result_shape, symtab)
                elif op == "custom-call":
                    # CPU backend: linalg as lapack FFI custom-calls
                    tgt = re.search(r'custom_call_target="([^"]+)"', line)
                    tname = tgt.group(1) if tgt else ""
                    _, dims = _dims(result_shape)
                    if "trsm" in tname and len(dims) >= 2:
                        batch = 1
                        for d in dims[:-2]:
                            batch *= d
                        flops += batch * dims[-2] * dims[-2] * dims[-1]
                    elif "potrf" in tname and len(dims) >= 2:
                        batch = 1
                        for d in dims[:-2]:
                            batch *= d
                        flops += batch * dims[-1] ** 3 / 3.0
                    elif "gemm" in tname or "matmul" in tname:
                        # conservatively: |out| x shared-dim unknown -> skip
                        pass
                elif op == "triangular-solve":
                    # result (..., M, N) vs M x M triangle: ~M^2 N MACs
                    _, dims = _dims(result_shape)
                    if len(dims) >= 2:
                        batch = 1
                        for d in dims[:-2]:
                            batch *= d
                        flops += batch * dims[-2] * dims[-2] * dims[-1]
                elif op == "cholesky":
                    _, dims = _dims(result_shape)
                    if len(dims) >= 2:
                        batch = 1
                        for d in dims[:-2]:
                            batch *= d
                        flops += batch * dims[-1] ** 3 / 3.0
                base = op
                for suffix in ("-start", "-done"):
                    if base.endswith(suffix):
                        base = base[: -len(suffix)]
                if base in _COLLECTIVES and not op.endswith("-done"):
                    rec = colls.setdefault(
                        base, {"bytes": 0.0, "wire_bytes": 0.0, "count": 0}
                    )
                    rec["bytes"] += rb
                    rec["wire_bytes"] += rb * _WIRE_FACTOR[base]
                    rec["count"] += 1
            for callee in _CALL_ATTR.findall(line):
                edges[name].add(callee)
            bm = _BRANCHES.search(line)
            if bm:
                for c in bm.group(1).split(","):
                    edges[name].add(c.strip().lstrip("%"))
            if "while(" in line:
                body = re.search(r"body=%?([\w\.\-]+)", line)
                cond = re.search(r"condition=%?([\w\.\-]+)", line)
                if body and cond:
                    trip = 1
                    consts = [
                        int(c) for c in _CONST_RE.findall(
                            "\n".join(comps.get(cond.group(1), []))
                        )
                    ]
                    if consts:
                        trip = max(consts)
                    while_of[body.group(1)] = trip
                    while_of[cond.group(1)] = trip
        raw[name] = {"flops": flops, "bytes": byts, "colls": colls}

    # multipliers: product of enclosing while trip counts, via DFS from ENTRY
    entry = None
    for name in comps:
        if "main" in name:
            entry = name
            break
    if entry is None:
        entry = next(iter(comps))

    mult = defaultdict(float)

    def visit(name, m):
        if m <= mult[name]:
            return
        mult[name] = m
        for child in edges[name]:
            visit(child, m * while_of.get(child, 1))

    visit(entry, 1.0)

    total = {"flops": 0.0, "bytes": 0.0}
    colls_total: Dict[str, dict] = {}
    for name, r in raw.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        total["flops"] += r["flops"] * m
        if name not in fusion_internal:   # fusion internals never touch HBM
            total["bytes"] += r["bytes"] * m
        for op, rec in r["colls"].items():
            t = colls_total.setdefault(
                op, {"bytes": 0.0, "wire_bytes": 0.0, "count": 0}
            )
            t["bytes"] += rec["bytes"] * m
            t["wire_bytes"] += rec["wire_bytes"] * m
            t["count"] += int(rec["count"] * m)
    total["collectives"] = colls_total
    total["wire_bytes"] = sum(r["wire_bytes"] for r in colls_total.values())
    total["n_while"] = len(while_of) // 2
    return total
