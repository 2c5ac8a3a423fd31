"""Share of the traced window in which no operation ran on the device and no
micro-batch was open on the host (no ``serving.batch`` span): the hand-back,
the wait for the next batch and whatever else the host did between
batches.  With ``idle_in_batch.sat`` it sums to ``idle_share.sat``.
Nothing to read where the program writes no spans."""
import spantrace


def read(run):
    idle = spantrace.idle_in_batches_ns(run)
    if idle is None:
        return None
    lo, hi = run.window_ns()
    return 100.0 * (hi - lo - run.busy_ns(lo, hi) - idle) / (hi - lo)
