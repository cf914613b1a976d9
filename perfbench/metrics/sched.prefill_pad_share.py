"""Share of the prefill rows' tokens in the window that are padding to the
bucket edge (``ServingEngine`` padded_tokens and real_tokens)."""


def read(run):
    w = run.window
    if w.padded == 0:
        return None
    return 100.0 * (w.padded - w.real) / w.padded
