"""The program's own spans in a traced window, on the device trace's clock.

The served path opens named spans (`repro.obs.spans`: one
`jax.profiler.TraceAnnotation` per span) on its two host threads, the
server's asyncio loop and the engine pump. They land on the host plane
of the same `.xplane.pb` as the device's operations and the
`bench.traced` mark, so a span's interval and a device-idle interval are
read on one clock. A span's args are its event's stats.

`of(run)` loads the window's one `.xplane.pb` (where `bench/run.py`
writes it, `<repo>/.bench_out/trace`) once per run and keeps the spans;
the readers in `bench/metrics/` that need spans share it. A program that
opens no span (an older commit) leaves `Spans.all` empty, and those
readers return None. Window, clipping, merging and device-0's idle
intervals are `bench.devtrace`'s.

    python3 bench/spans.py

prints, for the last traced run, device-0 idle seconds by the innermost
span the pump thread was in, and the medians of each request's parts of
its time to first token.
"""
from __future__ import annotations

import collections
import json
import pathlib
import statistics
import sys
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import devtrace

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_out" / "trace"      # bench/run.py's trace dir

# the span names these readings use (repro/obs/spans.py SPAN_NAMES)
NAMES = frozenset((
    "server.submit", "server.write", "pump.wait", "pump.apply",
    "pump.flush", "engine.step", "sched.schedule", "engine.pace",
    "engine.admit", "prefill.group", "engine.decode", "engine.preempt"))
PUMP = "engine.step"          # a line holding it is the pump thread's
NO_SPAN = "(no span)"


class Span(NamedTuple):
    name: str
    start: int                  # ns, on the trace's clock
    end: int
    line: int                   # the host thread line it was recorded on
    args: Dict[str, Any]


def load(path) -> List[devtrace.Plane]:
    """The trace's host planes with the program's spans and the window
    mark only; each event is (name, start_ns, duration_ns, args)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        lines = []
        for line in p.lines:
            events = []
            for e in line.events:
                if e.name in NAMES:
                    events.append((e.name, int(e.start_ns),
                                   int(e.duration_ns), dict(e.stats)))
                elif e.name == devtrace.MARK:
                    events.append((e.name, int(e.start_ns),
                                   int(e.duration_ns), {}))
            lines.append(devtrace.Line(line.name, events))
        planes.append(devtrace.Plane(p.name, lines))
    return planes


def trace_file(trace_dir=TRACE_DIR) -> pathlib.Path:
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def overlap_ns(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> int:
    """Length of the intersection of two unions of intervals."""
    a, b = devtrace.merge(a), devtrace.merge(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def rid_list(s: Span) -> List[int]:
    """The rids of a `prefill.group` span (space-separated; one rid alone
    reads back as an int)."""
    return [int(x) for x in str(s.args.get("rids", "")).split()]


class Spans:
    """The program spans of one traced window (from the host planes of
    `planes`, events (name, start_ns, duration_ns, args)), and device 0's
    idle intervals inside it."""

    def __init__(self, planes: Sequence[devtrace.Plane],
                 idle: Sequence[Tuple[int, int]]):
        host = [p for p in planes if not p.name.startswith("/device:")]
        self.lo, self.hi = devtrace.mark_window([devtrace.Plane(p.name, [
            devtrace.Line(l.name, [e[:3] for e in l.events])
            for l in p.lines]) for p in host])
        self.idle = devtrace.clip(idle, self.lo, self.hi)
        spans, k = [], 0
        for p in host:
            for line in p.lines:
                spans += [Span(n, s, s + d, k, args)
                          for n, s, d, args in line.events
                          if n != devtrace.MARK]
                k += 1
        self.all = sorted(spans, key=lambda s: (s.start, -s.end))
        self._by_name: Dict[str, List[Span]] = collections.defaultdict(list)
        for s in self.all:
            self._by_name[s.name].append(s)

    def named(self, name: str) -> List[Span]:
        return self._by_name.get(name, [])

    def started(self, name: str) -> List[Span]:
        """Spans of `name` that start inside the window."""
        return [s for s in self.named(name) if self.lo <= s.start < self.hi]

    def pump_line(self) -> Optional[int]:
        lines = collections.Counter(s.line for s in self.named(PUMP))
        return lines.most_common(1)[0][0] if lines else None

    # ------------------------------------------------- per-request joins
    def submits(self) -> List[Tuple[Span, int]]:
        """Every `server.submit` span with its request's rid: the span's
        own, or, where the loop thread had none, the rid `pump.apply`
        recorded for the same connection."""
        by_conn = {s.args.get("conn"): s.args.get("rid", -1)
                   for s in self.named("pump.apply")
                   if s.args.get("kind") == "submit"}
        out = []
        for s in self.named("server.submit"):
            rid = s.args.get("rid", -1)
            if rid < 0:
                rid = by_conn.get(s.args.get("conn"), -1)
            if rid >= 0:
                out.append((s, rid))
        return out

    def first_writes(self) -> Dict[int, Span]:
        """rid -> the `server.write` span that carried its first token."""
        out = {}
        for s in self.named("server.write"):
            if s.args.get("first_index") == 0:
                out.setdefault(s.args.get("rid", -1), s)
        return out

    def first_token_groups(self) -> Dict[int, Span]:
        """rid -> the `prefill.group` that produced its first token, for
        groups that end inside the window: the last group holding the rid
        that ended before the write of its first token (or, with no such
        write in the trace, its last group)."""
        writes = self.first_writes()
        out: Dict[int, Span] = {}
        for g in self.named("prefill.group"):
            for rid in rid_list(g):
                w = writes.get(rid)
                if w is None or g.end <= w.end:
                    out[rid] = g
        return {rid: g for rid, g in out.items()
                if self.lo <= g.end <= self.hi}

    def admit_waits(self) -> Dict[int, float]:
        """rid -> seconds from its `server.submit` span's start to the
        start of its first `engine.admit` after it, for submits inside the
        window (censored at the window's end)."""
        admits = collections.defaultdict(list)
        for s in self.named("engine.admit"):
            admits[s.args.get("rid")].append(s.start)
        out = {}
        for s, rid in self.submits():
            if not self.lo <= s.start < self.hi:
                continue
            after = [a for a in admits.get(rid, ()) if a >= s.start]
            out[rid] = (min(min(after, default=self.hi), self.hi)
                        - s.start) / 1e9
        return out

    def wires(self) -> Dict[int, float]:
        """rid -> seconds from the end of the `prefill.group` that made
        its first token to the end of the `server.write` that carried it
        (censored at the window's end)."""
        writes = self.first_writes()
        return {rid: ((writes[rid].end if rid in writes else self.hi)
                      - g.end) / 1e9
                for rid, g in self.first_token_groups().items()}

    def idle_by_leaf(self) -> Dict[str, float]:
        """Device-0 idle seconds in the window by the innermost span the
        pump thread was in (NO_SPAN where it was in none)."""
        line = self.pump_line()
        spans = [s for s in self.all if s.line == line]
        idle = devtrace.merge(self.idle)
        out: Dict[str, float] = collections.Counter()
        i = 0
        for a, b, name in innermost(spans, self.lo, self.hi):
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            j = i
            while j < len(idle) and idle[j][0] < b:
                out[name] += (min(b, idle[j][1]) - max(a, idle[j][0])) / 1e9
                j += 1
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def innermost(spans: Sequence[Span], lo: int,
              hi: int) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut into pieces, each named by the innermost of the
    nested spans of one thread open over it (NO_SPAN where none is)."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []          # (end, name), nested
    t = lo

    def advance(to: int) -> None:
        nonlocal t
        while t < to:
            while stack and stack[-1][0] <= t:
                stack.pop()
            nxt = min(to, stack[-1][0]) if stack else to
            out.append((t, nxt, stack[-1][1] if stack else NO_SPAN))
            t = nxt

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        if s.end <= lo or s.start >= hi:
            continue
        advance(max(s.start, lo))
        while stack and stack[-1][0] <= t:
            stack.pop()
        stack.append((s.end, s.name))
    advance(hi)
    return out


def of(run) -> Optional[Spans]:
    """The spans of `run`'s traced window (None without one), loaded from
    the trace file once per run."""
    if run.traced is None:
        return None
    if "spans" not in run.traced:
        run.traced["spans"] = Spans(load(trace_file()),
                                    run.traced["reduced"].idle)
    return run.traced["spans"]


def request_parts(sp: Spans) -> Dict[str, float]:
    """Medians, in seconds, of each request's parts of its time to first
    token: the wait for admission, its own pacing sleep (`engine.pace`
    inside its `engine.admit`), its prefill group, and the wire."""
    paces = collections.defaultdict(float)
    pace = sp.named("engine.pace")
    for a in sp.named("engine.admit"):
        paces[a.args.get("rid")] += sum(
            p.end - p.start for p in pace
            if p.line == a.line and a.start <= p.start and p.end <= a.end
        ) / 1e9
    groups = sp.first_token_groups()
    parts = {"admit_wait": sp.admit_waits(),
             "own_pace": {rid: paces[rid] for rid in groups},
             "prefill_group": {rid: (g.end - g.start) / 1e9
                               for rid, g in groups.items()},
             "wire": sp.wires()}
    return {k: statistics.median(v.values()) if v else None
            for k, v in parts.items()}


def main() -> int:
    planes = devtrace.load(str(trace_file()))
    sp = Spans(load(trace_file()), devtrace.reduce(planes).idle)
    idle = sp.idle_by_leaf()
    total = sum(idle.values())
    print(json.dumps({
        "window_s": (sp.hi - sp.lo) / 1e9, "idle_s": total,
        "idle_by_leaf": idle,
        "named_share": 1 - idle.get(NO_SPAN, 0.0) / total if total else None,
        "ttft_parts_median_s": request_parts(sp)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
