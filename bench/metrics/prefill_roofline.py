"""prefill_roofline: the least time the chip could take for the prefill
flushes dispatched in the traced span, over the device time of the
bucketed prefill program there, in %. A flush's least time is the larger
of its FLOPs over peak FLOP/s (every prompt token through every layer,
the head for each prompt's last token) and its bytes over peak bandwidth
(every weight once per device call, each prompt's KV written once),
counted from shapes and prompt lengths (bench/work.py), so padding to a
length bucket costs time and earns nothing."""
from bench import devtrace, work


def read(run):
    if run.traced is None:
        return None
    a, b = run.traced["mono"]
    secs = devtrace.prefill_seconds(run.traced["reduced"].programs)
    flushes = run.obs.prefill_flushes(a, b)
    if not secs or not flushes:
        return None
    c = run.cell.config
    least = 0.0
    for calls, prompts in flushes:
        f, byts = work.prefill_flush(c["model"], c["kind"], calls, prompts)
        least += max(f / run.peaks["bf16_flops"],
                     byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
