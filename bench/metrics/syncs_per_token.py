"""syncs_per_token: host<->device sync rounds the engine counted in the
window (its `sync` hook, the count behind hotpath_stats' host_syncs) over
the tokens it emitted in the window."""


def read(run):
    toks = sum(e[3] for e in run.obs.emits if run.t0 <= e[0] <= run.t_end)
    return run.syncs / toks if toks else None
