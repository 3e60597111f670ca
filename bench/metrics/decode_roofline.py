"""decode_roofline: the least time the chip could take for the decode
iterations committed in the traced span, over the device time of the
decode programs there, in %. The least time of one iteration is the
larger of its FLOPs over peak FLOP/s and its bytes (every weight once,
the live KV of each row, recurrent state in and out) over peak bandwidth,
counted from shapes and live lengths (bench/work.py), so any
implementation of the same work is credited the same. Iterations the
device ran and the engine discarded count in the time, not the work."""
from bench import devtrace, work


def read(run):
    if run.traced is None:
        return None
    a, b = run.traced["mono"]
    secs = devtrace.decode_seconds(run.traced["reduced"].programs)
    its = run.obs.decode_iterations(a, b)
    if not secs or not its:
        return None
    c = run.cell.config
    least = work.least_seconds(its, c["model"], c["kind"],
                               run.peaks["bf16_flops"],
                               run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
