"""wire_ms_p90: 90th percentile, over requests whose first token came
from a `prefill.group` span ending in the traced span, of the time from
that span's end (the token's id on the host) to the end of the
`server.write` span that carried frame index 0 (the frame handed to the
socket), in ms: the pump's wait to flush after its step, the hop to the
loop thread, and the write. Censored at the span's end. None when the
program opened no span (bench/spans.py)."""
from bench import qoe, spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp.all:
        return None
    wires = [w * 1e3 for w in sp.wires().values()]
    return qoe.percentile(wires, 90) if wires else None
