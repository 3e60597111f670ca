"""Named program spans on the profiler's clock.

`span(name, **args)` is `jax.profiler.TraceAnnotation`: a context manager
that, while a profiler trace runs, writes one host event named `name`,
with `args` as its stats, into the same `.xplane.pb` as the device's
operations. With no profiler running it is a no-op context manager
(about a microsecond). Args are ints, floats or short strings; a string
must not hold a comma (the annotation's own separator), so lists of ids
are space-separated. `set_metadata(**args)` on the entered span adds args
known only at its end.

The Observer hooks (`repro.obs.observer`) stay the record of events on the
engine's clock; spans are the record of durations on the device trace's
clock, so a gap in the device's work can be put down to the span the
host thread was in. No span sits inside a per-token or per-row loop.

`SPAN_NAMES` lists every span the serving path opens, the thread it runs
on, and what it covers. A request's spans join on `rid` across the two
server threads; where the loop thread does not know it yet it records
`conn`, and `pump.apply` carries both.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

SPAN_NAMES = {
    # sse-loop thread (server/app.py)
    "server.submit": "queuing one stream's submit command for the pump "
                     "(conn, rid: the payload's or -1)",
    "server.write": "writing and draining one batch of SSE frames (conn, "
                    "rid, first_index: first token's index or -1, frames)",
    # engine-pump thread (server/app.py)
    "pump.wait": "blocked on the command queue with nothing to step",
    "pump.apply": "applying one command (kind; conn and rid where known)",
    "pump.flush": "handing new frames of every connection to the loop "
                  "(conns)",
    # engine-pump thread, inside the engine (serving/engine.py)
    "engine.step": "one ServingEngine.step that has work (live at its "
                   "start; admitted, active at its end)",
    "sched.schedule": "the scheduler choosing the step's batch (live; "
                      "chosen)",
    "engine.pace": "a wall-clock sleep to the LatencyModel's schedule (op: "
                   "prefill/chunk/decode/swap/overhead/arrival, "
                   "modeled_us: the modeled price of the op)",
    "engine.admit": "one admission's host half: staged prefill or chunk, "
                    "eager prefill, or swap-in (rid, tokens)",
    "prefill.group": "one bucket group of a prefill flush: device call, "
                     "slot write, first-token read (rows, bucket, real, "
                     "rids, ssd_chunk: the chunk its Mamba-2 layers ran "
                     "with, 0 without them)",
    "engine.decode": "one decode dispatch through its host sync and "
                     "commit, of any kind (rows; j: iterations committed)",
    "engine.preempt": "one preemption (rid, mode)",
}

__all__ = ["span", "SPAN_NAMES"]
