"""Model operations of the requests completed in the window (as counted for
``req_per_s``), per second of the window, as a share of the chip's bf16 peak
(the program computes in float32 at ``highest``)."""


def read(run):
    if not run.peak:
        return None
    rate = run.completed() * run.ops_per_request() / run.seconds
    return 100.0 * rate / run.peak["flops_per_s_bf16"]
