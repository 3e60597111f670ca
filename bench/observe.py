"""Counters the benchmark reads from the program's Observer hooks.

`Recorder` is attached to the engine next to the server's own observers.
Every hook appends the event with a `time.monotonic()` stamp, the clock
the load generator stamps the wire with, so the window's bounds select
events without reading the engine's private clock. The hooks run on the
engine pump thread and only append.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Tuple

from repro.obs import Observer

mono = time.monotonic


class Recorder(Observer):
    def __init__(self):
        self.submitted: Dict[int, float] = {}
        self.first_prefill: Dict[int, float] = {}
        self.prefills: List[Tuple[float, int, int]] = []   # (t, rid, tokens)
        # (t, engine t, rid, k, generated after, prompt_len)
        self.emits: List[Tuple[float, float, int, int, int, int]] = []
        self.preempts: List[Tuple[float, int]] = []
        self.dispatches: List[Tuple[float, str, int]] = []
        self.loops: List[Tuple[float, int, int]] = []      # (t, j, steps)
        self.syncs: List[Tuple[float, int]] = []
        self.compiles: List[Tuple[float, object]] = []

    def submit(self, req, t, *, replica=-1):
        self.submitted[req.rid] = mono()

    def prefill(self, req, t, n_tokens, *, replica=-1):
        now = mono()
        self.first_prefill.setdefault(req.rid, now)
        self.prefills.append((now, req.rid, int(n_tokens)))

    def emit(self, req, t, k=1, *, replica=-1):
        self.emits.append((mono(), t, req.rid, int(k), int(req.generated),
                           int(req.prompt_len)))

    def preempt(self, req, t, mode="swap", *, replica=-1):
        self.preempts.append((mono(), req.rid))

    def dispatch(self, t, kind, n=1, *, replica=-1):
        self.dispatches.append((mono(), kind, int(n)))

    def persistent_loop(self, t, j, steps, *, replica=-1):
        self.loops.append((mono(), int(j), int(steps)))

    def sync(self, t, n=1, *, replica=-1):
        self.syncs.append((mono(), int(n)))

    def jit_compile(self, t, key, *, replica=-1):
        self.compiles.append((mono(), key))

    # ------------------------------------------------------------ readings
    def decode_iterations(self, a: float, b: float) -> List[Tuple[int, int]]:
        """Committed decode iterations stamped in [a, b]: (rows, sum over
        rows of the cache length each row attended before its step). A
        decode emission is one with generated >= 2 (the first token comes
        from prefill); one iteration's emissions share the engine time."""
        groups: Dict[float, List[int]] = collections.OrderedDict()
        for now, t, _rid, k, gen, plen in self.emits:
            if a <= now <= b and gen >= 2:
                for i in range(k):
                    groups.setdefault(t, []).append(plen + gen - k + i)
        return [(len(v), sum(v)) for v in groups.values()]

    def device_decode_steps(self, a: float, b: float) -> int:
        """Decode iterations the device ran in [a, b]: one per single-step
        decode dispatch, `steps` per device-resident loop."""
        n = sum(k for now, kind, k in self.dispatches
                if a <= now <= b and kind == "decode")
        return n + sum(s for now, _j, s in self.loops if a <= now <= b)

    def prefill_flushes(self, a: float, b: float
                        ) -> List[Tuple[int, List[int]]]:
        """Flushes of prefills dispatched in [a, b]: (device calls, prompt
        lengths). The engine stamps each admission's prefill when it
        stages it, then dispatches the flush ("prefill", one call per
        length bucket), so a flush's prompts are those stamped since the
        flush before it."""
        out, i = [], 0
        pre = sorted(self.prefills)
        for now, kind, n in sorted(self.dispatches):
            if kind != "prefill":
                continue
            lens = []
            while i < len(pre) and pre[i][0] <= now:
                lens.append(pre[i][2])
                i += 1
            if a <= now <= b and lens:
                out.append((n, lens))
        return out
