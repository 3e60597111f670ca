"""idle_share: 1 - (union of the intervals in which an operation ran on
the device) / (traced span), in %, averaged over the chips traced."""


def read(run):
    if run.traced is None:
        return None
    red = run.traced["reduced"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
