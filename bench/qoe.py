"""The paper's Eq. 1 QoE on a client's receipt timeline.

A copy of the exact path of the program's QoE arithmetic (client token
buffer, expected and actual areas), kept here so the yardstick cannot move
with the program. `window_qoe` adds what a window that closes on live
streams needs: a stream cut at the window's end is scored over its
timeline up to that point.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def pace_delivery(recv_times: Sequence[float], tds: float) -> np.ndarray:
    """Client token buffer: token i is shown at d_i = max(e_i, d_{i-1} +
    1/tds); the first token is shown as soon as it arrives."""
    e = np.asarray(recv_times, dtype=np.float64)
    if e.size == 0:
        return e
    gap = 1.0 / tds
    d = np.empty_like(e)
    d[0] = e[0]
    for i in range(1, e.size):
        d[i] = max(e[i], d[i - 1] + gap)
    return d


def expected_area(t: float, ttft: float, tds: float,
                  cap: Optional[float] = None) -> float:
    """Integral over [0, t] of min(T(tau), cap), T(tau) = tds * (tau - ttft)+."""
    if t <= ttft:
        return 0.0
    if cap is None or cap <= 0:
        ramp_end = t
    else:
        ramp_end = min(t, ttft + cap / tds)
    area = 0.5 * tds * (ramp_end - ttft) ** 2
    if cap is not None and cap > 0 and t > ramp_end:
        area += cap * (t - ramp_end)
    return area


def actual_area(delivery_times: np.ndarray, t: float) -> float:
    """Integral over [0, t] of the delivered-token staircase."""
    d = np.asarray(delivery_times, dtype=np.float64)
    return float(np.sum(np.maximum(t - d[d <= t], 0.0)))


def qoe_exact(recv_times: Sequence[float], due: float, ttft: float,
              tds: float, *, response_len: Optional[int] = None) -> float:
    """Eq. 1 over [due, TTLT] on the buffer-paced delivery timeline."""
    e = np.asarray(recv_times, dtype=np.float64) - due
    if e.size == 0:
        return 0.0
    d = pace_delivery(e, tds)
    ttlt = float(d[-1])
    n = response_len if response_len is not None else e.size
    s_exp = expected_area(ttlt, ttft, tds, cap=n)
    if s_exp <= 0.0:
        return 1.0
    return float(np.clip(actual_area(d, ttlt) / s_exp, 0.0, 1.0))


def window_qoe(recv_times: Sequence[float], due: float, ttft: float,
               tds: float, response_len: int, t_end: float,
               finished: bool) -> float:
    """QoE of one request at the window's end `t_end`.

    A finished stream is scored by `qoe_exact`. A stream still open at
    `t_end` is scored over [due, t_end] against the expected curve capped
    at the response length it asked for; a stream with no token yet
    scores 0 once its expected curve has begun, and 1 before."""
    if finished:
        return qoe_exact(recv_times, due, ttft, tds,
                         response_len=response_len)
    horizon = t_end - due
    s_exp = expected_area(horizon, ttft, tds, cap=response_len)
    if s_exp <= 0.0:
        return 1.0
    e = np.asarray(recv_times, dtype=np.float64) - due
    d = pace_delivery(e[e <= horizon], tds)
    return float(np.clip(actual_area(d, horizon) / s_exp, 0.0, 1.0))


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))
