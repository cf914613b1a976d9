"""Operations of every prefill batch and decode step in the traced part (the
algorithm's work, from the shapes) over the seconds the device was busy in
it times the chip's peak bf16 FLOP/s: the whole step's share of the peak
while it runs, which the offered load does not set."""

from perfbench import roofline, tracefile


def read(run):
    steps, batches = run.decode_steps(), run.prefill_batches()
    if not steps and not batches:
        return None
    flops = sum(roofline.decode_flops(run.cfg, p) for p in steps)
    flops += sum(roofline.prefill_flops(run.cfg, b) for b in batches)
    busy = tracefile.busy_ns(run.trace.ops[0], *run.traced_ns()) / 1e9
    return 100.0 * flops / (busy * run.peak["bf16_flops_per_s"])
