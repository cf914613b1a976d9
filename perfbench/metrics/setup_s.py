"""Seconds from process start to the window's opening: imports, weights,
engine, every compile or cache load, warm-up and the scheduler's history."""


def read(run):
    return run.setup_s
