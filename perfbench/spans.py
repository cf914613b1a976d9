#!/usr/bin/env python3
"""The program's own spans in a profiler trace: what the host does while
the chip waits.

The serving engine and the EWSJF scheduler annotate their host work with
``jax.profiler.TraceAnnotation`` (``repro.obs.trace.span``): ``engine.tick``
and, inside it, ``sched.tick``, ``sched.repartition``, ``sched.meta_trial``,
``engine.prefill`` (with ``engine.write_slot``), ``engine.chunk`` and
``engine.decode_step`` (with ``engine.decode_dispatch`` and
``engine.sample``).  ``load`` reads them from the trace's ``.xplane.pb``,
beside what ``tracefile.load`` reads, as (name, start_ns, end_ns, args);
the rest is arithmetic on them and on device 0's operations and programs,
counting only spans that start in the traced window (``window``).
Readings that set a host time against a device time (``readback_ms``, the
launch in ``decode_steps_ms``) move with the offset between the two
clocks; with the profiler's Python tracer on, as the harness traces, the
device's clock runs about a millisecond early.  Their sum does not move.

    python3 perfbench/spans.py --workload qwen3-4b.mixed --seed 7 \\
        --seconds 51

runs one traced window of the cell on the chip, as a ``--trace 1`` run of
``run.py`` does (no correctness check), and prints one JSON line: the
cell's end-to-end and per-layer metrics as the benchmark reads them, the
seconds the device trace covers, the span readings, the device's idle time
by the innermost span the host was in, and the longest tick split the same
way.  A benchmark run never runs this.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import tracefile  # noqa: E402
from perfbench.rundata import DECODE_PROGRAM  # noqa: E402

PREFIXES = ("engine.", "sched.")


def load(path) -> list[tuple[str, int, int, dict]]:
    """Host spans named ``engine.*`` or ``sched.*``, by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    out.sort(key=lambda e: e[1])
    return out


def window(tr, spans) -> tuple[int, int]:
    """The traced window (``bench.traced``), cut where device 0's trace
    stops if the engine still starts decode steps after it: the profiler
    keeps a bounded number of device events, and a long window of a deep
    model outruns it."""
    t0, t1 = tracefile.window(tr)
    end = max((e for _, _, e in tr.ops[0]), default=t0)
    if any(n == "engine.decode_step" and end < s < t1
           for n, s, *_ in spans):
        t1 = end
    return t0, t1


def named(spans, name: str, t0: int, t1: int) -> list[tuple[int, int]]:
    """(start, end) of the spans called ``name`` that start in [t0, t1)."""
    return [(s, e) for n, s, e, *_ in spans if n == name and t0 <= s < t1]


def inside(outer, inner) -> list[list[tuple[int, int]]]:
    """For each outer (start, end), the inner intervals it contains."""
    starts = [s for s, _ in inner]
    out = []
    for s, e in outer:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        out.append([iv for iv in inner[lo:hi] if iv[1] <= e])
    return out


def step_runs(tr, steps, t0: int, t1: int) -> list:
    """Each decode step's ``_decode_fn`` run on device 0: the first that
    ends after the step starts (None if the trace has none).  Not the
    first that starts inside it: on the trace's clock a run can start up to
    about a millisecond before the host span that launched it."""
    runs = tracefile.runs(tr.modules[0], DECODE_PROGRAM, t0, t1)
    ends = [e for _, e in runs]
    out = []
    for s, e in steps:
        i = bisect.bisect_right(ends, s)
        out.append(runs[i] if i < len(runs) and runs[i][0] < e else None)
    return out


def _mean_ms(ns: list) -> float | None:
    return sum(ns) / len(ns) / 1e6 if ns else None


# ---- the per-layer readings --------------------------------------------

def decode_host_ms(tr, spans) -> float | None:
    """Mean over decode steps of the step's span less its ``engine.sample``
    child: host time per step with nothing queued on the chip."""
    t0, t1 = window(tr, spans)
    steps = named(spans, "engine.decode_step", t0, t1)
    samples = inside(steps, named(spans, "engine.sample", t0, t1))
    return _mean_ms([(e - s) - sum(b - a for a, b in kids)
                     for (s, e), kids in zip(steps, samples)])


def readback_ms(tr, spans) -> float | None:
    """Mean over decode steps of the time from the end of the step's
    ``_decode_fn`` run on device 0 (``step_runs``) to the end of its
    ``engine.sample`` span: the argmax, the copy to the host and the host
    waking."""
    t0, t1 = window(tr, spans)
    steps = named(spans, "engine.decode_step", t0, t1)
    samples = inside(steps, named(spans, "engine.sample", t0, t1))
    return _mean_ms([smp[-1][1] - run[1] for smp, run in
                     zip(samples, step_runs(tr, steps, t0, t1))
                     if smp and run])


def prefill_idle_ms(tr, spans) -> float | None:
    """Mean over ``engine.prefill`` spans of device 0's idle time inside
    them: batch building, input copies, the first tokens' read-back and
    the slot writes' dispatch, during which the chip waits."""
    t0, t1 = window(tr, spans)
    busy = tracefile.Busy(tr.ops[0], t0, t1)
    return _mean_ms([(min(e, t1) - s) - busy.between(s, min(e, t1))
                     for s, e in named(spans, "engine.prefill", t0, t1)])


def sched_tick_ms(tr, spans) -> float | None:
    """Mean duration of ``sched.tick`` spans: EWSJF's scoring and batch
    build."""
    t0, t1 = window(tr, spans)
    return _mean_ms([e - s for s, e in named(spans, "sched.tick", t0, t1)])


READINGS = {"engine.decode_host_ms": decode_host_ms,
            "engine.readback_ms": readback_ms,
            "engine.prefill_idle_ms": prefill_idle_ms,
            "sched.tick_ms": sched_tick_ms}


def decode_steps_ms(tr, spans) -> dict:
    """Per decode step, the pieces of the device's idle time between two
    runs: the median idle gap between consecutive ``_decode_fn`` runs with
    no ``bench.idle`` between them, and the mean time from the end of a
    step's ``engine.decode_dispatch`` to the start of its run."""
    t0, t1 = window(tr, spans)
    busy = tracefile.Busy(tr.ops[0], t0, t1)
    slept = tracefile.Busy([h for h in tr.host if h[0] == "bench.idle"],
                           t0, t1)
    runs = tracefile.runs(tr.modules[0], DECODE_PROGRAM, t0, t1)
    idle = [(b - a) - busy.between(a, b)
            for (_, a), (b, _) in zip(runs, runs[1:])
            if not slept.between(a, b)]
    steps = named(spans, "engine.decode_step", t0, t1)
    disp = inside(steps, named(spans, "engine.decode_dispatch", t0, t1))
    launch = [run[0] - d[-1][1] for d, run in
              zip(disp, step_runs(tr, steps, t0, t1)) if d and run]
    return {"idle_median_ms": (statistics.median(idle) / 1e6
                               if idle else None),
            "launch_ms": _mean_ms(launch)}


# ---- where the idle time and the long ticks go --------------------------

def innermost(spans) -> list[tuple[str, int, int]]:
    """Cut time into pieces, each named for the innermost span covering it.
    Spans nest, as those of one thread do; a piece covered by none is left
    out."""
    out: list[tuple[str, int, int]] = []
    stack: list[tuple[str, int]] = []
    cur = 0

    def emit(name, a, b):
        if b > a:
            out.append((name, a, b))

    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            emit(n, cur, end)
            cur = end
        if stack:
            emit(stack[-1][0], cur, s)
            e = min(e, stack[-1][1])
        cur = s
        stack.append((name, e))
    while stack:
        n, end = stack.pop()
        emit(n, cur, end)
        cur = end
    return out


def _pieces(tr, spans) -> list[tuple[str, int, int]]:
    host = [h for h in tr.host if h[0] != tracefile.WINDOW_SPAN]
    return innermost(host + [sp[:3] for sp in spans])


def idle_by_span(tr, spans) -> dict[str, float]:
    """Idle seconds of device 0 in the window, by the innermost span
    (program or ``bench.*``) the host was in at the middle of each gap
    ("none" outside every span).  With no program spans it is
    ``tracefile.idle_by_host_span``."""
    t0, t1 = window(tr, spans)
    pieces = _pieces(tr, spans)
    starts = [s for _, s, _ in pieces]
    out: dict[str, float] = {}
    for s, e in tracefile.gaps(tr.ops[0], t0, t1):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        key = pieces[i][0] if i >= 0 and pieces[i][2] > mid else "none"
        out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out


def longest_tick(tr, spans) -> dict:
    """The longest ``bench.tick`` in the window, and the seconds of it each
    innermost span covered (host spans only: the whole window counts)."""
    t0, t1 = tracefile.window(tr)
    ticks = [(s, e) for n, s, e in tr.host
             if n == "bench.tick" and t0 <= s < t1]
    if not ticks:
        return {}
    a, b = max(ticks, key=lambda t: t[1] - t[0])
    split: dict[str, float] = {}
    for name, s, e in _pieces(tr, spans):
        if s < b and e > a:
            split[name] = split.get(name, 0.0) + (min(e, b) - max(s, a)) / 1e9
    return {"at_s": (a - t0) / 1e9, "seconds": (b - a) / 1e9,
            "by_span": split}


# ---- one traced window on the chip --------------------------------------

def trace_window(workload: str, seed: int, seconds: float):
    """Build, warm and run the cell once with the profiler on; returns the
    run's data and the program's spans."""
    from perfbench import driver
    from perfbench import run as bench
    from perfbench.rundata import RunData
    _, w, cfg, mix, params = bench.load_cell(workload)
    bench.compile_cache()
    device = bench.require_chips(w["chips"])
    cell = driver.Cell(cfg, mix, params, seed)
    cell.build()
    cell.warm()
    cell.history()
    win = cell.run_window(seconds, trace=True)
    cell.free()
    path = sorted(Path(win.trace_dir).rglob("*.xplane.pb"))[-1]
    run = RunData(cfg=cfg, window=win, setup_s=0.0, memory_peak_bytes=0,
                  device_kind=device["kind"], trace=tracefile.load(path))
    return run, load(path)


def report(run, spans) -> dict:
    """What one traced window says, as one JSON-ready dict."""
    from perfbench.rundata import reader
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    tr = run.trace
    t0, t1 = window(tr, spans)
    metrics = {}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("setup_s", "device.peak_hbm_gb"):
            continue
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = float(v)
    for name, fn in READINGS.items():
        metrics[name] = fn(tr, spans)
    top = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))  # noqa: E731
    counts: dict[str, int] = {}
    for n, s, *_ in spans:
        if t0 <= s < t1:
            counts[n] = counts.get(n, 0) + 1
    return {"window_s": run.traced_s(),
            "device_traced_s": (t1 - t0) / 1e9,
            "busy_s": tracefile.busy_ns(tr.ops[0], t0, t1) / 1e9,
            "metrics": metrics,
            "decode_step": decode_steps_ms(tr, spans),
            "idle_gaps": top(idle_by_span(tr, spans)),
            "longest_tick": longest_tick(tr, spans),
            "span_counts": counts}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    run, spans = trace_window(args.workload, args.seed, args.seconds)
    print(json.dumps(dict(report(run, spans), seed=args.seed)), flush=True)


if __name__ == "__main__":
    main()
