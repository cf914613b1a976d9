"""Tokens sampled in the window over the window's seconds."""


def read(run):
    return run.window.tokens / run.window_s
