"""Mean time to first token of the short requests sent in the window, from
due time; one still waiting at the close counts at its wait."""

from statistics import fmean

from perfbench.stats import ttfts


def read(run):
    return fmean(ttfts(run.window.sent, run.window.close, "short"))
