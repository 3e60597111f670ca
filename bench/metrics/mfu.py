"""mfu: the whole device step's share of the chip's peak: model FLOPs of
the work dispatched in the traced span (every prefill flush and every
committed decode iteration, counted from shapes by bench/work.py) over
the time the device was busy there (any operation, averaged over the
chips traced) times the peak bf16 FLOP/s, in %. Work moved from one
program to another leaves it as it was; work taken off the device
raises it only as far as the busy time falls."""
from bench import work


def read(run):
    if run.traced is None:
        return None
    a, b = run.traced["mono"]
    c = run.cell.config
    m, kind = c["model"], c["kind"]
    flops = sum(work.prefill_flush(m, kind, calls, prompts)[0]
                for calls, prompts in run.obs.prefill_flushes(a, b))
    for rows, ctx in run.obs.decode_iterations(a, b):
        flops += work.decode_iteration(m, kind, rows, ctx)[0]
    busy = run.traced["reduced"].busy_s * run.cell.chips
    if not flops or not busy:
        return None
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
