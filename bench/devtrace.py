"""Reduce a profiler trace of the window to device busy time, per-program
device time, and idle gaps named by what the engine's host thread did.

The trace is JAX's `.xplane.pb`, read with `jax.profiler.ProfileData`
into plain `Plane`/`Line` tuples first, so the reduction itself runs on
any recorded or synthetic trace. Device planes are those named
`/device:TPU:<n>`. On a device plane, the line of whole programs
("XLA Modules") names each event by its jitted program
(`jit_decode_tokens(<id>)`); the line of operations ("XLA Ops") gives
the busy intervals. A host event named `MARK` brackets the traced
window. The host plane holds one line per thread, with the profiler's
own events (each jitted dispatch, waits on buffers, and the Python
functions its tracer records); the gaps are named from the line of the
thread that dispatched the programs.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, NamedTuple, Sequence, Tuple

MARK = "bench.traced"
DECODE_PREFIX = "jit_decode"           # jit_decode_tokens, jit_decode_persistent
PREFILL = "jit__call"                  # the bucketed prefill's jitted call
MODULES = "XLA Modules"
OPS = "XLA Ops"
HOST = "/host:CPU"
DISPATCH = "PjitFunction("             # host event of one jitted call


class Line(NamedTuple):
    name: str
    events: List[Tuple[str, int, int]]     # (name, start_ns, duration_ns)


class Plane(NamedTuple):
    name: str
    lines: List[Line]


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Plane(p.name, [Line(l.name, [(e.name, int(e.start_ns),
                                          int(e.duration_ns))
                                         for e in l.events])
                           for l in p.lines])
            for p in pd.planes]


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def program_name(event_name: str) -> str:
    """`jit_decode_tokens(123)` -> `jit_decode_tokens`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def decode_seconds(programs: Dict[str, float]) -> float:
    """Device seconds of the decode programs."""
    return sum(s for name, s in programs.items()
               if name.startswith(DECODE_PREFIX))


def prefill_seconds(programs: Dict[str, float]) -> float:
    """Device seconds of the bucketed prefill program
    (`BucketedPrefill._call`, jitted as `jit__call`)."""
    return programs.get(PREFILL, 0.0)


def mark_window(planes: Sequence[Plane]) -> Tuple[int, int]:
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for name, start, dur in line.events:
                if name == MARK:
                    return start, start + dur
    raise ValueError(f"no {MARK!r} event on a host plane of the trace")


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                  # mean over the devices traced
    programs: Dict[str, float]     # program -> device seconds, summed
    idle: List[Tuple[int, int]]    # idle intervals of device 0, trace ns
    window_ns: Tuple[int, int]
    devices: int


def reduce(planes: Sequence[Plane]) -> Reduced:
    """Busy time, per-program device time and device 0's idle intervals
    inside the marked window. A trace without the mark, or a device plane
    without its "XLA Ops" and "XLA Modules" lines, is an error."""
    devs = device_planes(planes)
    if not devs:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    lo, hi = win = mark_window(planes)
    busy, programs, idle0 = [], collections.Counter(), []
    for i, p in enumerate(devs):
        lines = {l.name: l for l in p.lines}
        for want in (OPS, MODULES):
            if want not in lines:
                raise ValueError(f"{p.name} has no {want!r} line; it has "
                                 f"{sorted(lines)}")
        u = merge(clip([(s, s + d) for _, s, d in lines[OPS].events],
                       lo, hi))
        busy.append(sum(b - a for a, b in u))
        for name, s, d in lines[MODULES].events:
            c = clip([(s, s + d)], lo, hi)
            if c:
                programs[program_name(name)] += (c[0][1] - c[0][0]) / 1e9
        if i == 0:
            edges = [lo] + [x for ab in u for x in ab] + [hi]
            idle0 = [(edges[k], edges[k + 1])
                     for k in range(0, len(edges), 2)
                     if edges[k + 1] > edges[k]]
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9,
                   programs=dict(programs), idle=idle0,
                   window_ns=win, devices=len(devs))


def dispatch_line(planes: Sequence[Plane]) -> Line:
    """The host thread that dispatched most jitted programs: the engine's
    pump thread, which runs every step of the served path."""
    host = [l for p in planes if p.name == HOST for l in p.lines]
    best = max(host, default=None, key=lambda l: sum(
        1 for name, _, _ in l.events if name.startswith(DISPATCH)))
    if best is None or not any(n.startswith(DISPATCH)
                               for n, _, _ in best.events):
        raise ValueError(f"no {DISPATCH}...) event on the {HOST} plane")
    return best


def name_gaps(idle: Sequence[Tuple[int, int]], line: Line,
              top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host thread was doing: each gap takes the
    name of the innermost event of `line` open at its middle, after the
    name of the event around that ("outer > inner"); the profiler's host
    events of one thread nest. Largest totals first."""
    events = sorted(line.events, key=lambda e: (e[1], -e[2]))
    total: Dict[str, float] = collections.Counter()
    stack: List[Tuple[str, int]] = []          # (name, end), nested
    k = 0
    for a, b in sorted(idle):
        mid = (a + b) // 2
        while k < len(events) and events[k][1] <= mid:
            name, s, d = events[k]
            while stack and stack[-1][1] <= s:
                stack.pop()
            stack.append((name, s + d))
            k += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        name = " > ".join(n for n, _ in stack[-2:]) or "(no host event)"
        total[name] += (b - a) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:top]


def summary(planes: Sequence[Plane]) -> dict:
    """Plane and line names with event counts: what a trace holds."""
    return {p.name: {l.name: len(l.events) for l in p.lines} for p in planes}
