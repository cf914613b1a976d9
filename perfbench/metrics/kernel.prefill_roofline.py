"""Bucketed prefill time at the roofline over its device time, in the
traced part.  The bound of a batch is the larger of the operations of its
real prompt tokens (padding is not work) over peak FLOP/s and its bytes
(weights once, plus the keys and values written) over peak bandwidth."""

from perfbench import roofline
from perfbench.rundata import PREFILL_PROGRAM


def read(run):
    runs = run.program_runs(PREFILL_PROGRAM)
    batches = run.prefill_batches()
    if not runs or not batches:
        return None
    n = min(len(runs), len(batches))
    device = sum(e - s for s, e in runs[:n]) / 1e9
    bound = sum(roofline.bound_seconds(roofline.prefill_flops(run.cfg, b),
                                       roofline.prefill_bytes(run.cfg, b),
                                       run.peak) for b in batches[:n])
    return 100.0 * bound / device
