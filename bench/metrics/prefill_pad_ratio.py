"""prefill_pad_ratio: tokens the bucketed prefill's device calls
processed (rows x length bucket) over the prompt tokens they held
(`real`), summed over the `prefill.group` spans starting in the traced
span; 1.0 is no padding. None when the program opened no span
(bench/spans.py)."""
from bench import spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp.all:
        return None
    groups = sp.started("prefill.group")
    real = sum(g.args["real"] for g in groups)
    padded = sum(g.args["rows"] * g.args["bucket"] for g in groups)
    return padded / real if real else None
