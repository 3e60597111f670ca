"""queue_wait_p90_s: 90th percentile, over requests submitted in the
window, of the wait from the engine's `submit` hook to the request's
first `prefill` hook (the scheduler granting it a slot); a request still
waiting at the window's end is censored there. The engine's `admit` hook
fires when a request joins the scheduler's live set, within one
iteration of `submit`, so it does not mark the end of the wait."""
from bench import qoe


def read(run):
    obs = run.obs
    waits = [min(obs.first_prefill.get(rid, run.t_end), run.t_end) - t
             for rid, t in obs.submitted.items()
             if rid >= 0 and run.t0 <= t <= run.t_end]
    return qoe.percentile(waits, 90) if waits else None
