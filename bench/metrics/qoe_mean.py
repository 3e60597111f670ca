"""qoe_mean: the paper's Eq. 1 QoE from the client's receipt stamps,
averaged over all requests attempted (a failed request counts 0)."""
from bench import window


def read(run):
    q = window.qoes(run)
    return sum(q.values()) / len(q) if q else None
