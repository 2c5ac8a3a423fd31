"""The model kernels' least time (operations at the bf16 peak or bytes at the
HBM bandwidth, whichever is longer, summed over the window's batches) over
the device time of the served program's operations in the traced window."""


def read(run):
    return run.kernel_roofline()
