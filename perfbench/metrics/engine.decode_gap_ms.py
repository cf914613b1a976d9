"""Mean device-idle time between two consecutive decode steps, in the
traced part of the window, leaving out any pause in which the harness slept
for want of work (no slot active)."""

from perfbench import tracefile
from perfbench.rundata import DECODE_PROGRAM


def read(run):
    runs = run.program_runs(DECODE_PROGRAM)
    if len(runs) < 2:
        return None
    busy = tracefile.Busy(run.trace.ops[0], *run.traced_ns())
    slept = tracefile.Busy([h for h in run.trace.host if h[0] == "bench.idle"],
                           *run.traced_ns())
    idle = []
    for (_, end), (start, _) in zip(runs, runs[1:]):
        if slept.between(end, start):
            continue
        idle.append((start - end) - busy.between(end, start))
    if not idle:
        return None
    return sum(idle) / len(idle) / 1e6
