"""sched_ms_per_step: mean host time of the scheduler's choice of a
step's batch (`sched.schedule` spans starting in the traced span), in
ms. None when the program opened no span (bench/spans.py)."""
from bench import spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp.all:
        return None
    d = [s.end - s.start for s in sp.started("sched.schedule")]
    return sum(d) / len(d) / 1e6 if d else None
