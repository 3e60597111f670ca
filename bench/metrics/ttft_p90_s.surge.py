"""ttft_p90_s.surge: the ttft_p90_s reading in a cell above the knee.
There a TTFT tail swings with the smallest change, so it is recorded
beside the cell's end-to-end metrics and decides nothing."""
from bench import qoe, window


def read(run):
    t = window.ttfts(run)
    return qoe.percentile(t, 90) if t else None
