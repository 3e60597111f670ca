"""pace_idle_pct: device-0 idle time inside the engine's wall-clock
pacing sleeps (the pump thread's `engine.pace` spans: `ServingEngine`
sleeping to the LatencyModel's schedule) over the traced span, in %.
None when the program opened no span (bench/spans.py)."""
from bench import devtrace, spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp.all:
        return None
    pace = devtrace.clip([(s.start, s.end) for s in sp.named("engine.pace")],
                         sp.lo, sp.hi)
    return 100.0 * spans.overlap_ns(sp.idle, pace) / (sp.hi - sp.lo)
