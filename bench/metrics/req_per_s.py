"""Requests completed in the window per second of the window; a request in
flight at the close counts by the share of its micro-batch's run that lay
inside the window (``Run.completed``)."""


def read(run):
    return run.completed() / run.seconds
