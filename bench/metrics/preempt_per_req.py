"""preempt_per_req: preemptions in the window (the engine's `preempt`
hook) over requests attempted."""


def read(run):
    n = sum(1 for t, _ in run.obs.preempts if run.t0 <= t <= run.t_end)
    return n / len(run.reqs) if run.reqs else None
