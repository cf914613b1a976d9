"""99th percentile of every gap between two tokens of one request that ends
in the window (the engine's ``decode_gaps``, host clock after the sampled
tokens reach the host)."""

from perfbench.stats import percentile


def read(run):
    return percentile(run.window.gaps, 99)
