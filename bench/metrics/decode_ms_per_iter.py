"""decode_ms_per_iter: device time of the decode programs in the traced
span (profiler events of the programs named jit_decode_*), over the
decode iterations the device ran in it (one per single-step decode
dispatch, `steps` per device-resident loop)."""
from bench import devtrace


def read(run):
    if run.traced is None:
        return None
    a, b = run.traced["mono"]
    steps = run.obs.device_decode_steps(a, b)
    secs = devtrace.decode_seconds(run.traced["reduced"].programs)
    return secs / steps * 1e3 if steps and secs else None
