"""Share of the traced window in which no operation ran on the device; host
gaps between batches count as idle."""


def read(run):
    if run.events is None:
        return None
    lo, hi = run.window_ns()
    return 100.0 * (1.0 - run.busy_ns(lo, hi) / (hi - lo))
