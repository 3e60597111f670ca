"""gen_late_p99_ms: 99th percentile of how late the load generator sent
a request after it was due, on the generator's own clock; a request not
sent by the window's end counts as late by then."""
from bench import qoe


def read(run):
    late = [((run.recs[req.rid]["sent"] or run.t_end) - (run.t0 + req.due))
            * 1e3 for req in run.reqs]
    return qoe.percentile(late, 99) if late else None
