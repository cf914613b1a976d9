"""Median gap between two tokens of one request, over every gap that ends
in the window: the decode step's pace as a client sees it."""

from perfbench.stats import percentile


def read(run):
    return percentile(run.window.gaps, 50)
