"""shared_block_share_pct: device time of the operations under the
`zamba2.shared` scope (each application of a shared attention + MLP
block, with its adapter and linear) over the device's busy time in the
traced span, in %. None where no operation ran under the scope
(bench/scopes.py)."""
from bench import scopes


def read(run):
    return scopes.share(run, "zamba2.shared")
