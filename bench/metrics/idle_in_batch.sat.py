"""Share of the traced window in which no operation ran on the device while
a micro-batch was open on the host (the program's ``serving.batch`` span,
dispatch to logits ready).  With ``idle_between_batches.sat`` it sums to
``idle_share.sat``.  Nothing to read where the program writes no spans."""
import spantrace


def read(run):
    idle = spantrace.idle_in_batches_ns(run)
    if idle is None:
        return None
    lo, hi = run.window_ns()
    return 100.0 * idle / (hi - lo)
