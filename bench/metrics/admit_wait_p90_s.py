"""admit_wait_p90_s: 90th percentile, over requests whose `server.submit`
span starts in the traced span, of the time from that span's start (the
loop thread queuing the submit command) to the start of the request's
first `engine.admit` span (the engine taking it into a slot); a request
not admitted by the span's end is censored there. Unlike
queue_wait_p90_s it holds the command's wait in the pump's queue and
ends before the request's own pacing sleep. None when the program opened
no span (bench/spans.py)."""
from bench import qoe, spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp.all:
        return None
    waits = list(sp.admit_waits().values())
    return qoe.percentile(waits, 90) if waits else None
