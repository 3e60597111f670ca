"""Per-request readings of one window, from the client's records.

Every request due in the window counts as attempted. A request's TTFT is
the time from when it was due to its first token frame; with none by the
window's end it is censored at (window end - due) and misses the TTFT
limit. A request fails when its stream was refused or ended in anything
but `finish` inside the window (HTTP error, shed, eviction, error,
cancel, shutdown).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import qoe

FAIL_ENDS = ("shed", "cancel", "evicted", "error", "shutdown")


def failed_rids(run) -> List[int]:
    out = []
    for req in run.reqs:
        rec = run.recs[req.rid]
        if rec["status"] not in (None, 200) or (
                rec["end"] in FAIL_ENDS and rec["end_t"] <= run.t_end):
            out.append(req.rid)
    return out


def attempted_failed(run) -> Tuple[int, int]:
    return len(run.reqs), len(failed_rids(run))


def received(run, rid: int) -> List[float]:
    """Receipt times of the tokens read inside the window."""
    return [t for t in run.recs[rid]["recv"] if t <= run.t_end]


def ttfts(run) -> List[float]:
    out = []
    for req in run.reqs:
        due = run.t0 + req.due
        got = received(run, req.rid)
        out.append((got[0] if got else run.t_end) - due)
    return out


def qoes(run) -> Dict[int, float]:
    """Eq. 1 QoE of every attempted request; a failed one counts 0."""
    failed = set(failed_rids(run))
    out = {}
    for req in run.reqs:
        if req.rid in failed:
            out[req.rid] = 0.0
            continue
        rec = run.recs[req.rid]
        finished = rec["end"] == "finish" and rec["end_t"] <= run.t_end
        out[req.rid] = qoe.window_qoe(
            received(run, req.rid), run.t0 + req.due, req.ttft, req.tds,
            req.output_len, run.t_end, finished)
    return out


def tokens_in_window(run) -> int:
    return sum(len(received(run, req.rid)) for req in run.reqs)
