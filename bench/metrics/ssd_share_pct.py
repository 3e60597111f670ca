"""ssd_share_pct: device time of the operations under the `zamba2.ssd`
scope (the Mamba-2 recurrence: the prefill's token-by-token scan and the
decode step's state update) over the device's busy time in the traced
span, in %. None where no operation ran under the scope
(bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.share(run, "zamba2.ssd")
