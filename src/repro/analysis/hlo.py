"""Trip-count-aware HLO analysis (home of the former
``benchmarks/hlo_analysis.py`` — that module now re-exports from here).

XLA's ``compiled.cost_analysis()`` counts every ``while`` body ONCE, which
under-reports FLOPs/bytes/collectives by the loop trip count — fatal for a
scan-over-layers model (layer count × microbatch count ≈ 10³×).  This module
re-derives the three roofline inputs directly from the optimized HLO text:

* per-device matmul FLOPs (``dot``/``convolution``/oneDNN matmul
  custom-calls), resolved through a per-computation symbol table since the
  optimized printer references operands by name;
* per-device HBM-traffic estimate: Σ (result + operand bytes) over top-level
  instructions, excluding fusion bodies (a fusion's I/O *is* its HBM
  traffic) and no-traffic ops (parameter/tuple/gte/bitcast/constant/iota);
* per-device collective traffic by kind (operand bytes);

each multiplied through the call graph using ``known_trip_count`` for while
loops.  The SPMD module is the per-device program, so all numbers are
per-device; multiply by chip count for cluster totals.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
                "s4": 1, "u4": 1, "token": 0, "opaque": 0}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TRIP = re.compile(r'"known_trip_count":\s*\{\s*"n"\s*:\s*"?(\d+)"?')
_CALL_ATTR = re.compile(
    r"(?:body|condition|calls|to_apply|branch_computations|"
    r"true_computation|false_computation)=\{?(%[\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")
_NO_TRAFFIC = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "after-all", "iota", "while", "conditional",
               "call", "partition-id", "replica-id"}


def _shapes_of(text: str):
    """All typed shapes in a string → [(elems, bytes, dims, dtype)]."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        dl = []
        for d in dims.split(","):
            if d:
                dl.append(int(d))
                n *= int(d)
        out.append((n, n * _DTYPE_BYTES[dt], dl, dt))
    return out


@dataclass
class _Instr:
    name: str
    opcode: str
    line: str
    result: list          # [(elems, bytes, dims, dt)]
    operands: list        # operand names
    calls: list
    trip: int | None


@dataclass
class _Comp:
    name: str
    instrs: list = field(default_factory=list)
    symbols: dict = field(default_factory=dict)   # name → result shapes


def _operand_names(body: str):
    """Names inside the balanced call parens of an instruction body."""
    start = body.find("(")
    if start < 0:
        return []
    depth = 0
    for i in range(start, len(body)):
        if body[i] == "(":
            depth += 1
        elif body[i] == ")":
            depth -= 1
            if depth == 0:
                inner = body[start + 1:i]
                return re.findall(r"%([\w.\-]+)", inner)
    return []


def parse_hlo(text: str):
    comps: dict[str, _Comp] = {}
    fusion_bodies: set[str] = set()
    entry = None
    cur: _Comp | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s or s.startswith("//"):
            continue
        if s.endswith("{") and "->" in s and "=" not in s.split("->")[0][:20]:
            hdr = s[:-1].strip()
            is_entry = hdr.startswith("ENTRY")
            hdr = hdr[5:].strip() if is_entry else hdr
            m = re.match(r"%?([\w.\-]+)\s*\(", hdr)
            if m:
                cur = _Comp(m.group(1))
                comps[cur.name] = cur
                if is_entry:
                    entry = cur.name
            continue
        if s == "}":
            cur = None
            continue
        if cur is None or " = " not in s:
            continue
        lhs, _, rhs = s.partition(" = ")
        name = lhs.replace("ROOT", "").strip().lstrip("%")
        # rhs starts with result type(s) then "opcode("; blank the layouts
        # first, whose TPU tiling ("{...:T(8,128)(2,1)}") reads as a call
        mo = re.search(r"\b([\w\-]+)\(", _LAYOUT.sub(
            lambda m: " " * len(m.group()), rhs))
        if not mo:
            continue
        opcode = mo.group(1)
        result_txt = rhs[:mo.start()]
        result = _shapes_of(result_txt)
        body = rhs[mo.start():]
        operands = _operand_names(body)
        calls = []
        for cm in _CALL_ATTR.finditer(rhs):
            for nm in cm.group(1).split(","):
                calls.append(nm.strip().lstrip("%"))
        if opcode == "fusion":
            fusion_bodies.update(calls)
        trip = None
        if opcode == "while":
            tm = _TRIP.search(rhs)
            trip = int(tm.group(1)) if tm else 1
        ins = _Instr(name, opcode, s, result, operands, calls, trip)
        cur.instrs.append(ins)
        cur.symbols[name] = result
    return comps, fusion_bodies, entry


def _dot_flops(ins: _Instr, symbols: dict) -> float:
    res_elems = sum(e for e, _, _, _ in ins.result)
    if not ins.operands:
        return 0.0
    lhs_shapes = symbols.get(ins.operands[0])
    if not lhs_shapes:
        return 0.0
    _, _, lhs_dims, _ = lhs_shapes[0]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
    k = 1
    if m and m.group(1):
        for idx in m.group(1).split(","):
            if int(idx) < len(lhs_dims):
                k *= lhs_dims[int(idx)]
    elif lhs_dims:
        k = lhs_dims[-1]
    return 2.0 * res_elems * k


def _cc_flops(ins: _Instr, symbols: dict) -> float:
    low = ins.line.lower()
    if "matmul" not in low and "dot" not in low and "gemm" not in low:
        return 0.0
    return _dot_flops(ins, symbols)


_SLICE_OPS = {"dynamic-slice", "gather", "slice"}


def _fusion_traffic(comp: _Comp) -> int:
    """HBM traffic of one fusion execution: parameters are read at full size
    unless consumed ONLY through (dynamic-)slice/gather (then just the slice
    results are read); the write is the ROOT result, except a
    dynamic-update-slice ROOT writes only its update region."""
    read = 0
    consumers: dict[str, list] = {}
    for ins in comp.instrs:
        for nm in ins.operands:
            consumers.setdefault(nm, []).append(ins)
    for ins in comp.instrs:
        if ins.opcode != "parameter":
            continue
        cons = consumers.get(ins.name, [])
        if cons and all(c.opcode in _SLICE_OPS for c in cons):
            read += sum(sum(b for _, b, _, _ in c.result) for c in cons)
        else:
            read += sum(b for _, b, _, _ in ins.result)
    root = comp.instrs[-1] if comp.instrs else None
    write = 0
    if root is not None:
        if root.opcode == "dynamic-update-slice" and len(root.operands) >= 2:
            upd = comp.symbols.get(root.operands[1], [])
            write = 2 * sum(b for _, b, _, _ in upd)   # read + write region
        else:
            write = sum(b for _, b, _, _ in root.result)
    return read + write


_ALIAS_ENTRY = re.compile(
    r"\{([0-9,\s]*)\}\s*:\s*\((\d+),\s*\{([0-9,\s]*)\}\s*(?:,\s*([\w-]+))?\)")


def _idx(csv: str) -> tuple:
    return tuple(int(x) for x in csv.replace(" ", "").split(",") if x)


def input_output_aliases(text: str) -> list:
    """Parse the module-level ``input_output_alias`` annotation of an
    optimized HLO dump.

    Returns ``[{output_index, param_number, param_index, kind}, ...]`` —
    one entry per output buffer XLA will write in place over an input
    (``param_number`` counts *flattened* entry parameters).  Donated jit
    arguments that XLA accepted show up here; an empty list means every
    output gets a fresh allocation (no donation landed).  This is the
    assertion surface for the decode-step donation contract: the page pool
    must alias through prefill/decode or each step copies the whole pool.
    """
    key = "input_output_alias={"
    start = text.find(key)
    if start < 0:
        return []
    i = start + len(key) - 1
    depth = 0
    inner = None
    for j in range(i, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                inner = text[i + 1:j]
                break
    if inner is None:
        return []
    return [{"output_index": _idx(m.group(1)),
             "param_number": int(m.group(2)),
             "param_index": _idx(m.group(3)),
             "kind": m.group(4) or "may-alias"}
            for m in _ALIAS_ENTRY.finditer(inner)]


def entry_result_shapes(text: str) -> list:
    """Result-tuple shapes of the ENTRY computation, in flat output order.

    Parses the ``ENTRY %main (...) -> (f32[2,4]{1,0}, ...)`` header of an
    (optimized) HLO dump and returns ``[(dtype, dims, nbytes), ...]`` — one
    entry per flat output buffer.  Together with
    :func:`input_output_aliases` this is the audit surface for the
    host-transfer budget: outputs NOT covered by an alias entry are fresh
    allocations whose bytes cross the device boundary when fetched.
    """
    for raw in text.splitlines():
        s = raw.strip()
        if not s.startswith("ENTRY") or "->" not in s:
            continue
        result_txt = s.rsplit("->", 1)[1]
        out = []
        for n, b, dims, dt in _shapes_of(result_txt):
            out.append((dt, tuple(dims), b))
        return out
    return []


def nonaliased_output_bytes(text: str) -> dict:
    """Split the ENTRY outputs of an optimized HLO dump into donated
    (aliased in place over an input) and fresh buffers.

    Returns ``{"total", "aliased", "fresh", "fresh_shapes"}`` where
    ``fresh`` is the byte total of outputs with no ``input_output_alias``
    entry — the upper bound on what a host fetch of the results can move.
    """
    shapes = entry_result_shapes(text)
    aliased_idx = set()
    for a in input_output_aliases(text):
        oi = a["output_index"]
        aliased_idx.add(oi[0] if oi else 0)
    total = sum(b for _, _, b in shapes)
    aliased = sum(b for i, (_, _, b) in enumerate(shapes)
                  if i in aliased_idx)
    fresh = [(i, dt, dims, b) for i, (dt, dims, b) in enumerate(shapes)
             if i not in aliased_idx]
    return {"total": total, "aliased": aliased,
            "fresh": sum(b for _, _, _, b in fresh),
            "fresh_shapes": fresh}


def analyze(text: str) -> dict:
    comps, fusion_bodies, entry = parse_hlo(text)
    memo: dict[str, dict] = {}
    fusion_traffic_memo: dict[str, int] = {}

    def op_bytes(ins: _Instr, symbols) -> int:
        total = 0
        for nm in ins.operands:
            for _, b, _, _ in symbols.get(nm, []):
                total += b
        return total

    def instr_traffic(ins: _Instr, symbols) -> int:
        """HBM bytes moved by one top-level instruction."""
        if ins.opcode in _NO_TRAFFIC:
            return 0
        rb = sum(b for _, b, _, _ in ins.result)
        if ins.opcode == "fusion" and ins.calls:
            body = ins.calls[0]
            if body not in fusion_traffic_memo:
                fusion_traffic_memo[body] = _fusion_traffic(
                    comps.get(body, _Comp(body)))
            return fusion_traffic_memo[body]
        if ins.opcode in _SLICE_OPS:
            return 2 * rb
        if ins.opcode == "dynamic-update-slice" and len(ins.operands) >= 2:
            upd = symbols.get(ins.operands[1], [])
            return 2 * sum(b for _, b, _, _ in upd)
        if ins.opcode == "scatter" and len(ins.operands) >= 3:
            upd = symbols.get(ins.operands[2], [])
            return 2 * sum(b for _, b, _, _ in upd)
        return rb + op_bytes(ins, symbols)

    def total(name: str) -> dict:
        if name in memo:
            return memo[name]
        zero = {"flops": 0.0, "bytes": 0.0,
                "coll": {k: [0.0, 0.0] for k in COLLECTIVES}}
        memo[name] = zero
        comp = comps.get(name)
        if comp is None:
            return zero
        acc = {"flops": 0.0, "bytes": 0.0,
               "coll": {k: [0.0, 0.0] for k in COLLECTIVES}}
        for ins in comp.instrs:
            if ins.opcode == "dot" or ins.opcode == "convolution":
                acc["flops"] += _dot_flops(ins, comp.symbols)
            elif ins.opcode == "custom-call":
                acc["flops"] += _cc_flops(ins, comp.symbols)
            acc["bytes"] += instr_traffic(ins, comp.symbols)
            base = ins.opcode.removesuffix("-start")
            if base in COLLECTIVES and not ins.opcode.endswith("-done"):
                acc["coll"][base][0] += op_bytes(ins, comp.symbols)
                acc["coll"][base][1] += 1
            mult = float(ins.trip) if ins.opcode == "while" else 1.0
            for callee in ins.calls:
                if callee in fusion_bodies:
                    continue
                sub = total(callee)
                acc["flops"] += mult * sub["flops"]
                acc["bytes"] += mult * sub["bytes"]
                for k in COLLECTIVES:
                    acc["coll"][k][0] += mult * sub["coll"][k][0]
                    acc["coll"][k][1] += mult * sub["coll"][k][1]
        memo[name] = acc
        return acc

    if entry is None:
        raise ValueError("no ENTRY computation found")
    out = total(entry)
    return {"flops": out["flops"], "bytes": out["bytes"],
            "collectives": {k: {"bytes": v[0], "count": v[1]}
                            for k, v in out["coll"].items()}}


if __name__ == "__main__":
    import sys
    with open(sys.argv[1]) as f:
        print(json.dumps(analyze(f.read()), indent=1))
