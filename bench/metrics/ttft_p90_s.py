"""ttft_p90_s: 90th percentile of TTFT over all requests attempted,
measured from when each was due (censored at the window's end)."""
from bench import qoe, window


def read(run):
    t = window.ttfts(run)
    return qoe.percentile(t, 90) if t else None
