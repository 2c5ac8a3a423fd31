"""``l1-update``'s least time (operations at the bf16 peak or bytes at the
HBM bandwidth, whichever is longer, summed over the window's batches) over
the device time of the served program's operations under its name scope in
the traced window.  Nothing to read where the trace names no scopes."""
import spantrace


def read(run):
    return spantrace.scope_roofline(run, "l1-update")
