"""Seconds from the process's start to the window's opening: graph, weights,
request pool, engine, calibration, compilation and warm-up."""


def read(run):
    return run.setup_s
