"""Decode step time at the roofline over its device time, in the traced
part.  The bound of a step is the larger of its operations over peak FLOP/s
and its bytes (weights once, plus the keys and values of the positions the
active slots hold) over peak bandwidth: the algorithm's work, not the
program's, which reads every slot's whole cache."""

from perfbench import roofline
from perfbench.rundata import DECODE_PROGRAM


def read(run):
    runs = run.program_runs(DECODE_PROGRAM)
    steps = run.decode_steps()
    if not runs or not steps:
        return None
    device = sum(e - s for s, e in runs) / 1e9
    n = min(len(runs), len(steps))
    bound = sum(roofline.bound_seconds(roofline.decode_flops(run.cfg, p),
                                       roofline.decode_bytes(run.cfg, p),
                                       run.peak) for p in steps[:n])
    device *= n / len(runs)
    return 100.0 * bound / device
