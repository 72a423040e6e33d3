"""Trip-count-aware HLO module analysis.

``compiled.cost_analysis()`` counts every ``while`` (lax.scan) body ONCE —
useless for 80-layer scanned stacks.  This parser segments the post-SPMD HLO
text into computations, extracts per-computation

  * dot FLOPs           (2 · prod(result dims) · prod(contracting dims))
  * HBM traffic proxy   (operand + result bytes of every scheduled op;
                         fusions are the reuse unit)
  * collective bytes    (result sizes of all-gather / all-reduce /
                         reduce-scatter / all-to-all / collective-permute)

then rolls them up through the call graph (fusion ``calls=``, reduce
``to_apply=``, while ``body=/condition=``) multiplying loop bodies by their
static trip counts (the ``constant(N)`` in the cond computation).

All shapes in the partitioned module are per-device, so totals are
per-device quantities — exactly what the roofline terms need.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_module", "ModuleCosts"]

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# op line: `%name = TYPE opcode(%a, %b, ...), attrs`.  TYPE may be a tuple
# and carry TPU tiled layouts (`bf16[16,8192]{1,0:T(8,128)(2,1)}`), so the
# type and the operand list are cut at balanced brackets, not by a regex.
_OPHEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*(?P<opcode>[\w\-]+)\(")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_COMP_HDR_RE = re.compile(
    r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\((?P<params>.*)\)\s*->.*\{"
)
_PARAM_RE = re.compile(r"%?([\w.\-]+):\s*((?:\([^)]*\))|[\w\[\],{}]+)")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_WHILE_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_operands(operands: str) -> List[str]:
    """Split an HLO operand list on top-level commas only.

    Modern HLO prints operands with inline types — e.g.
    ``dot(f32[64,32]{1,0} %a, f32[32,16]{1,0} %b)`` — so commas inside
    ``[...]`` / ``{...}`` / ``(...)`` are part of a shape, not separators.
    """
    parts: List[str] = []
    depth = 0
    cur: List[str] = []
    for ch in operands:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur).strip())
    return [p for p in parts if p]


def _balanced_end(text: str, start: int, stop_at_space: bool) -> int:
    """Index just past the bracketed run that starts at ``text[start]``:
    the matching close of an opening ``(``, or, with ``stop_at_space``, the
    first space outside brackets."""
    depth = 0
    for i in range(start, len(text)):
        ch = text[i]
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
            if depth == 0 and not stop_at_space:
                return i + 1
        elif ch == " " and depth == 0 and stop_at_space:
            return i
    return len(text)


def _parse_op(raw: str) -> Optional[Tuple[str, str, str, str, str]]:
    """``(name, type, opcode, operands, attrs)`` of an op line, else None."""
    head = _OPHEAD_RE.match(raw)
    if not head:
        return None
    i = head.end()
    j = _balanced_end(raw, i, stop_at_space=not raw.startswith("(", i))
    op = _OPCODE_RE.match(raw, j)
    if not op:
        return None
    k = _balanced_end(raw, op.end() - 1, stop_at_space=False)
    return (head.group("name"), raw[i:j], op.group("opcode"),
            raw[op.end():k - 1], raw[k:])


def _operand_type(operand: str, shapes: Dict[str, str]) -> str:
    """Resolve one operand's type string: inline if present, else by name."""
    if _ARRAY_RE.search(operand):
        return operand
    name = operand.split()[-1].lstrip("%") if operand else ""
    return shapes.get(name, "")


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    total_b = 0
    for dt, dims in _ARRAY_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total_b += n * _DTYPE_BYTES[dt]
    return 0, total_b


def _dims_of(type_str: str) -> Optional[List[int]]:
    m = _ARRAY_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Comp:
    name: str
    dot_flops: float = 0.0
    traffic_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    calls: List[str] = dataclasses.field(default_factory=list)
    # (condition, body, trip count the compiler states or None)
    whiles: List[Tuple[str, str, Optional[int]]] = dataclasses.field(
        default_factory=list)
    max_constant: int = 0


@dataclasses.dataclass
class ModuleCosts:
    dot_flops: float
    traffic_bytes: float
    collective_bytes: float
    collective_counts: Dict[str, float]
    num_whiles: int


_SKIP_TRAFFIC_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def _parse_computations(text: str) -> Dict[str, Comp]:
    comps: Dict[str, Comp] = {}
    cur: Optional[Comp] = None
    shapes: Dict[str, str] = {}
    for raw in text.splitlines():
        hdr = _COMP_HDR_RE.match(raw)
        if hdr and "{" in raw:
            cur = Comp(name=hdr.group("name"))
            comps[cur.name] = cur
            shapes = {}
            for pname, ptype in _PARAM_RE.findall(hdr.group("params")):
                shapes[pname] = ptype
            continue
        if cur is None:
            continue
        if raw.startswith("}"):
            cur = None
            continue
        c = _CONST_RE.search(raw)
        if c:
            cur.max_constant = max(cur.max_constant, int(c.group(1)))
        m = _parse_op(raw)
        if not m:
            continue
        name, type_str, opcode, operands, attrs = m
        shapes[name] = type_str

        # An async op's -start, -update and -done all name the wrapped
        # computation: it runs once, so it is visited from the -start only.
        if not opcode.endswith(("-update", "-done")):
            cur.calls.extend(_CALL_RE.findall(attrs))
        w = _WHILE_RE.search(attrs)
        if opcode == "while" and w:
            trip = _TRIP_RE.search(attrs)
            cur.whiles.append((w.group(1), w.group(2),
                               int(trip.group(1)) if trip else None))

        _, out_bytes = _shape_elems_bytes(type_str)
        # An async collective counts once, by its -done, whose result is
        # the collective's (a -start's tuple holds the operands too).
        base = opcode[:-len("-done")] if opcode.endswith("-done") else opcode
        if base in _COLLECTIVES:
            cur.collective_bytes += out_bytes
            cur.collective_counts[base] = cur.collective_counts.get(base, 0) + 1

        if opcode == "dot":
            res_dims = _dims_of(type_str) or []
            ops_list = _split_operands(operands)
            lhs_dims = (
                _dims_of(_operand_type(ops_list[0], shapes)) if ops_list else None
            ) or []
            cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
            k = 1
            if cdims and lhs_dims:
                for idx in cdims.group(1).split(","):
                    if idx and int(idx) < len(lhs_dims):
                        k *= lhs_dims[int(idx)]
            n_out = 1
            for d in res_dims:
                n_out *= d
            cur.dot_flops += 2.0 * n_out * k

        if opcode not in _SKIP_TRAFFIC_OPS and not opcode.endswith("-done"):
            tb = out_bytes
            for operand in _split_operands(operands):
                _, ob = _shape_elems_bytes(_operand_type(operand, shapes))
                tb += ob
            cur.traffic_bytes += tb
    return comps


def analyze_module(text: str) -> ModuleCosts:
    comps = _parse_computations(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line)
            if m:
                entry = m.group("name")
            break
    if entry is None or entry not in comps:
        # fall back: treat every computation with multiplier 1
        totals = ModuleCosts(0.0, 0.0, 0.0, {}, 0)
        for c in comps.values():
            totals = ModuleCosts(
                totals.dot_flops + c.dot_flops,
                totals.traffic_bytes + c.traffic_bytes,
                totals.collective_bytes + c.collective_bytes,
                totals.collective_counts,
                totals.num_whiles,
            )
        return totals

    flops = 0.0
    traffic = 0.0
    coll = 0.0
    counts: Dict[str, float] = {}
    num_whiles = 0
    seen_stack: List[str] = []

    def visit(name: str, mult: float) -> None:
        nonlocal flops, traffic, coll, num_whiles
        c = comps.get(name)
        if c is None or name in seen_stack:
            return
        seen_stack.append(name)
        flops += c.dot_flops * mult
        traffic += c.traffic_bytes * mult
        coll += c.collective_bytes * mult
        for k, v in c.collective_counts.items():
            counts[k] = counts.get(k, 0) + v * mult
        for cal in c.calls:
            visit(cal, mult)
        for cond, body, known in c.whiles:
            num_whiles += 1
            trip = known or max(comps.get(cond, Comp(cond)).max_constant, 1)
            visit(body, mult * trip)
            visit(cond, mult)  # cond cost ~ trip times, negligible: once
        seen_stack.pop()

    visit(entry, 1.0)
    counts["total"] = sum(v for k, v in counts.items())
    return ModuleCosts(
        dot_flops=flops,
        traffic_bytes=traffic,
        collective_bytes=coll,
        collective_counts=counts,
        num_whiles=num_whiles,
    )
