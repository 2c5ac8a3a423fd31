"""Mean host time of one micro-batch of the window, from dispatch to logits
ready (``RequestStats.t_execute``), under saturating traffic."""


def read(run):
    return run.batch_ms()
