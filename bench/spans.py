"""Read the program's own marks in a profiler trace.

``repro.launch.serve`` opens host spans named ``serve.*`` (the batch, the
per-call compile, prefill, and each decode token's wait, copy, pick and
dispatch), and ``repro.models`` wraps each part of a block in a
``jax.named_scope`` (``repro.models.SCOPES``), which reaches every compiled
op's ``metadata={op_name=...}``.  This module reduces a trace to what those
marks show, on top of ``bench.trace``:

* ``reduce`` returns ``bench.trace.reduce``'s numbers and, in the same
  window, the program's spans, the self time of every device operation, and
  the device's idle time by innermost program span;
* ``op_paths`` and ``op_scopes`` map each op of a compiled program's HLO
  text to its name stack and its scope;
* ``step_scope_s`` splits each program execution's device time by scope.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import stats
from bench import trace as T

PREFIX = "serve."
OUTSIDE = "(outside program spans)"
# (op name, start ns on the host clock, self time s)
Op = Tuple[str, float, float]


@dataclasses.dataclass
class SpanReduced(T.Reduced):
    spans: List[T.Event] = dataclasses.field(default_factory=list)
    ops: List[Op] = dataclasses.field(default_factory=list)
    idle_by_span: Dict[str, float] = dataclasses.field(default_factory=dict)

    def span_s(self, name: str) -> List[float]:
        """Durations of the program spans called ``name``, seconds."""
        return [(e - s) * 1e-9 for n, s, e in self.spans if n == name]


def reduce(trace: T.Trace, window: str, devices: Sequence[int] = (0,),
           prefix: str = PREFIX) -> SpanReduced:
    """``bench.trace.reduce`` over the host span ``window``, with the host
    spans whose names start with ``prefix`` on the window's thread, every
    op of the first device, and all of that device's idle time split by the
    innermost such span (``OUTSIDE`` where none is open)."""
    base = T.reduce(trace, window, devices=devices)
    lo, hi = T.span(trace, window)
    thread = next(evs for evs in trace.host.values()
                  if any(n == window for n, _, _ in evs))
    spans = [ev for ev in thread
             if ev[0].startswith(prefix) and ev[2] > lo and ev[1] < hi]

    dev = trace.devices[devices[0]]
    launches = sorted(ev for evs in trace.host.values() for ev in evs
                      if ev[0] == T.LAUNCH_EVENT)
    off = T.clock_offset_ns(dev.get("XLA Modules", []), launches)
    evs = sorted(((n, s - off, e - off) for n, s, e in dev.get("XLA Ops", [])
                  if e - off > lo and s - off < hi),
                 key=lambda ev: (ev[1], -ev[2]))
    ops = [(T.op_name(n), s, t * 1e-9)
           for (n, s, _), (_, t) in zip(evs, T.self_times(evs))]

    holes = T.gaps([(s, e) for _, s, e in evs], lo, hi)
    idle = T.attribute([(OUTSIDE, lo, hi)] + spans, holes)
    return SpanReduced(
        **{f.name: getattr(base, f.name) for f in dataclasses.fields(base)},
        spans=spans, ops=ops,
        idle_by_span={k: v * 1e-9 for k, v in idle.items()},
    )


def median_span_ms(trace, name: str) -> Optional[float]:
    """Median duration of the program spans called ``name`` in a reduced
    trace, milliseconds; None where there is none, or where the trace was
    reduced without the program's spans."""
    times = trace.span_s(name) if isinstance(trace, SpanReduced) else []
    return 1e3 * stats.median(times) if times else None


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_paths(hlo_text: str) -> Dict[str, Optional[str]]:
    """Each instruction of an HLO module's text -> its ``op_name``, the
    JAX name stack it was traced under, or None where it has none (ops the
    compiler adds, such as some copies)."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is not None:
            path = _OP_NAME.search(line)
            out[m.group(1)] = path.group(1) if path else None
    return out


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, Optional[str]]:
    """Each instruction of an HLO module's text -> the innermost of
    ``scopes`` in its ``op_name`` path, or None where there is none."""
    return {name: next((p for p in reversed(path.split("/")) if p in scopes), None)
            if path else None
            for name, path in op_paths(hlo_text).items()}


def step_scope_s(ops: Sequence[Op], executions: Sequence[T.Event],
                 scope_of: Dict[str, Optional[str]]) -> List[Dict[Optional[str], float]]:
    """For each program execution ``(name, start, end)``, the self time in
    seconds of the ops that start within it, by scope (None: no scope)."""
    starts = [s for _, s, _ in executions]
    out: List[Dict[Optional[str], float]] = [{} for _ in executions]
    for name, start, t in ops:
        k = bisect.bisect_right(starts, start) - 1
        if k >= 0 and start < executions[k][2]:
            scope = scope_of.get(name)
            out[k][scope] = out[k].get(scope, 0.0) + t
    return out
