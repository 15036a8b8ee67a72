"""Trip-count-aware collective accounting from partitioned HLO text.

XLA's ``cost_analysis`` counts while-loop (lax.scan) bodies ONCE, not
multiplied by trip count — verified empirically (see EXPERIMENTS.md §Method).
Collectives inside scanned layer stacks would be undercounted by ~num_layers.
This parser:

  1. splits the module into named computations,
  2. reads every ``while`` op's ``body=%comp`` edge and its
     ``known_trip_count`` from backend_config,
  3. propagates multipliers ENTRY -> bodies (nested loops multiply),
  4. sums collective result bytes x multiplier.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\).*?body=%([^\s,]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_COND_RE = re.compile(r"condition=%([^\s,]+)")
_S32_CONST_RE = re.compile(r"%([\w.\-]+) = s32\[\]\S* constant\((\d+)\)")
_LT_ROOT_RE = re.compile(r"ROOT .* compare\(([^)]*)\).*direction=LT")
#: every way one computation invokes another in HLO text: loop body /
#: condition, fusion/call targets, reducer lambdas, conditional branches
_CALLEE_RE = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_COMP_REF_RE = re.compile(r"%([\w.\-]+)")


def shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    is_entry = None
    for line in text.splitlines():
        m = _COMP_HEADER.match(line)
        if m:
            cur = m.group(1)
            if line.lstrip().startswith("ENTRY"):
                is_entry = cur
            comps[cur] = []
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
                continue
            comps[cur].append(line)
    if is_entry is not None:
        comps["__entry__"] = comps[is_entry]
    return comps


def _condition_trip(lines: List[str]) -> Optional[int]:
    """Trip count of a loop whose condition is ``counter < constant`` —
    how a scan lowers; its counter starts at 0. The TPU compiler drops
    the ``known_trip_count`` annotation that the CPU compiler keeps."""

    consts = dict(m.groups() for m in map(_S32_CONST_RE.search, lines) if m)
    for line in lines:
        m = _LT_ROOT_RE.search(line)
        if m:
            for operand in re.findall(r"%([\w.\-]+)", m.group(1)):
                if operand in consts:
                    return int(consts[operand])
    return None


def computation_multipliers(comps: Dict[str, List[str]],
                            follow_calls: bool = False) -> Dict[str, float]:
    """Multiplier per computation = product of enclosing loop trip counts.

    By default only while ``body=`` edges are followed (what the
    collective census needs — collectives never hide inside fusions).
    ``follow_calls=True`` additionally walks ``calls=``/``to_apply=``/
    condition/branch edges at trip 1, so fused computations *inside* a
    scanned loop body inherit the body's trip multiplier — required for
    FLOP attribution (obs.profile), where most compute lives in fusions.
    """

    # edges: computation -> [(callee_body, trip)]
    edges: Dict[str, List[Tuple[str, int]]] = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for line in lines:
            is_while = " while(" in line
            if is_while:
                mb = _WHILE_RE.search(line)
                if mb:
                    mt = _TRIP_RE.search(line)
                    mc = _COND_RE.search(line)
                    trip = (int(mt.group(1)) if mt else
                            (mc and _condition_trip(comps.get(mc.group(1), [])))
                            or 1)
                    edges.setdefault(name, []).append((mb.group(1), trip))
            if not follow_calls:
                continue
            body = _WHILE_RE.search(line).group(1) if is_while and _WHILE_RE.search(line) else None
            for callee in _CALLEE_RE.findall(line):
                if callee == body:
                    continue  # trip-scaled edge already added above
                edges.setdefault(name, []).append((callee, 1))
            mbr = _BRANCHES_RE.search(line)
            if mbr:
                for callee in _COMP_REF_RE.findall(mbr.group(1)):
                    edges.setdefault(name, []).append((callee, 1))

    entry = None
    for name, lines in comps.items():
        if name != "__entry__" and comps.get("__entry__") is lines:
            entry = name
            break

    mult: Dict[str, float] = {}
    if entry is None:
        return {name: 1.0 for name in comps}

    def visit(name: str, m: float):
        # a body may appear once; take max to be safe against re-visits
        if mult.get(name, 0.0) >= m:
            return
        mult[name] = m
        for body, trip in edges.get(name, []):
            visit(body, m * trip)

    visit(entry, 1.0)
    for name in comps:
        mult.setdefault(name, 1.0)
    return mult


def collective_stats(text: str) -> Dict[str, float]:
    """Per-type collective bytes/op counts, trip-count scaled."""

    comps = split_computations(text)
    mult = computation_multipliers(comps)

    out: Dict[str, float] = {f"{c}_bytes": 0.0 for c in COLLECTIVES}
    out.update({f"{c}_count": 0.0 for c in COLLECTIVES})
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 1.0)
        for line in lines:
            for c in COLLECTIVES:
                mm = re.search(rf"=\s+(.*?)\s+{c}(?:-start)?\(", line)
                if mm and f"{c}-done" not in line:
                    out[f"{c}_bytes"] += shape_bytes(mm.group(1)) * m
                    out[f"{c}_count"] += m
                    break
    out["total_bytes"] = sum(out[f"{c}_bytes"] for c in COLLECTIVES)
    out["total_count"] = sum(out[f"{c}_count"] for c in COLLECTIVES)
    return out
