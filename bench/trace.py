"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData`` alone.  On a TPU each
chip is a plane ``/device:TPU:<i>`` with the lines ``XLA Modules`` (one
event per program execution) and ``XLA Ops`` (one event per operation);
the host is the plane ``/host:CPU`` with one line per thread, on which the
benchmark's own ``jax.profiler.TraceAnnotation`` spans appear.  Event
times are nanoseconds from the start of the profile.

Device and host clocks are offset by about a millisecond.  The offset is
estimated from the program launches: no execution can start on the device
before the host has launched it, so the k-th ``XLA Modules`` event is
paired with the k-th ``PJRT_LoadedExecutable_Execute`` host event and the
smallest difference is taken.  Device events are moved onto the host clock
by it before they are clipped to the benchmark's window span.

Everything below ``load`` works on plain tuples, so that it can be checked
without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

# (name, start_ns, end_ns)
Event = Tuple[str, float, float]

HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"
TOP_N = 10
# Idle gaps shorter than this are pooled, not attributed to a host span.
MIN_GAP_NS = 20_000.0


@dataclasses.dataclass
class Trace:
    """The parts of one profile the benchmark reads."""

    # device index -> line name -> events sorted by start
    devices: Dict[int, Dict[str, List[Event]]]
    # host thread name -> events sorted by start
    host: Dict[str, List[Event]]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None and plane.name != HOST_PLANE:
            continue
        lines = {}
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                   for e in line.events]
            evs.sort(key=lambda e: e[1])
            lines[line.name] = evs
        if m is not None:
            devices[int(m.group(1))] = lines
        else:
            host = lines
    return Trace(devices=devices, host=host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping or touching intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out = []
    t = lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each event's duration less the part its nested events cover."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(e, events[stack[-1]][2]) - s
        stack.append(i)
    return [(ev[0], t) for ev, t in zip(events, self_ns)]


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

def module_name(name: str) -> str:
    """``jit_serve_step(1234)`` -> ``jit_serve_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def span(trace: Trace, name: str) -> Tuple[float, float]:
    """Start and end of the one host span called ``name``."""
    found = [(s, e) for evs in trace.host.values() for n, s, e in evs if n == name]
    if len(found) != 1:
        raise ValueError(f"expected one host span {name!r}, found {len(found)}")
    return found[0]


def clock_offset_ns(modules: Sequence[Event], launches: Sequence[Event]) -> float:
    """Device clock minus host clock, from paired launches (0 if unpaired)."""
    if not modules or len(modules) != len(launches):
        return 0.0
    return min(m[1] - h[1] for m, h in zip(modules, launches))


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over the devices read
    modules: List[Event]               # device 0, host clock, in the window
    device_ops: List[Tuple[str, float]]  # top self time, seconds
    idle_gaps: List[Tuple[str, float]]   # top idle time by host activity
    idle_by_host: Dict[str, float]       # all idle time by host activity

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def attribute(thread: Sequence[Event], holes) -> Dict[str, float]:
    """Split the gaps ``(lo, hi)`` by what one host thread was doing: each
    nanosecond goes to the innermost event open on the thread at that time
    (events on one thread nest).  One sweep over event boundaries."""
    bounds = []
    for i, (_, s, e) in enumerate(thread):
        bounds.append((s, 1, -e, i))   # opens sort outer-first at a tie
        bounds.append((e, 0, 0, i))    # closes before opens at a tie
    bounds.sort()
    out: Dict[str, float] = {}
    stack: List[int] = []
    holes = sorted(holes)
    h = 0
    prev = bounds[0][0] if bounds else 0.0
    for t, is_open, _, i in bounds + [(float("inf"), 0, 0, -1)]:
        # the segment [prev, t) belongs to the top of the stack
        while h < len(holes) and holes[h][1] <= prev:
            h += 1
        k = h
        while k < len(holes) and holes[k][0] < t:
            lo, hi = max(holes[k][0], prev), min(holes[k][1], t)
            if hi > lo:
                name = thread[stack[-1]][0] if stack else "(no host span)"
                out[name] = out.get(name, 0.0) + (hi - lo)
            k += 1
        prev = t
        if i < 0:
            break
        if is_open:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return out


def reduce(trace: Trace, window: str, devices: Sequence[int] = (0,),
           min_gap_ns: float = MIN_GAP_NS) -> Reduced:
    """Busy time, program executions, top operations and idle gaps within
    the host span ``window`` on the given devices.  Gaps shorter than
    ``min_gap_ns`` are pooled under one name rather than attributed."""
    lo, hi = span(trace, window)
    launches = sorted(ev for evs in trace.host.values() for ev in evs
                      if ev[0] == LAUNCH_EVENT)
    busy = []
    first = None
    for d in devices:
        dev = trace.devices[d]
        off = clock_offset_ns(dev.get("XLA Modules", []), launches)
        ops = [(n, s - off, e - off) for n, s, e in dev.get("XLA Ops", [])]
        busy.append(busy_ns([(s, e) for _, s, e in ops], lo, hi))
        if first is None:
            modules = [(module_name(n), s - off, e - off)
                       for n, s, e in dev.get("XLA Modules", [])]
            first = (ops, modules)
    ops, modules = first

    totals: Dict[str, float] = {}
    for name, t in self_times([op for op in ops if op[2] > lo and op[1] < hi]):
        key = op_name(name)
        totals[key] = totals.get(key, 0.0) + t
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP_N]

    holes = gaps([(s, e) for _, s, e in ops], lo, hi)
    small = [g for g in holes if g[1] - g[0] < min_gap_ns]
    large = [g for g in holes if g[1] - g[0] >= min_gap_ns]
    thread = next(evs for evs in trace.host.values()
                  if any(n == window for n, _, _ in evs))
    by_host = attribute(thread, large)
    if small:
        key = f"(gaps under {min_gap_ns / 1e3:g} us)"
        by_host[key] = sum(e - s for s, e in small)
    top_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:TOP_N]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        modules=[m for m in modules if m[2] > lo and m[1] < hi],
        device_ops=[(k, v * 1e-9) for k, v in top_ops],
        idle_gaps=[(k, v * 1e-9) for k, v in top_gaps],
        idle_by_host={k: v * 1e-9 for k, v in by_host.items()},
    )
