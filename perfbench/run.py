#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 perfbench/run.py --workload qwen3-4b.mixed --seed 7 \\
        --seconds 51 --trace 0

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its files are
found by name: ``perfbench/configs/<config>.json`` (sizes, engine, and under
``reference`` the family module ``perfbench/references/<reference>.py``,
which supplies the program's ``ModelConfig``, the weights, the roofline
counts and the plain reference), ``perfbench/traffic/<traffic>.json`` (the
mix) and ``perfbench/cells/<cell>.json`` (rate, scheduler, check limits).
Metric ``m`` is read by ``perfbench/metrics/<m>.py``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and prints the per-layer metrics.  The
last line of standard output is one JSON object; the numbers compared to
decide ``correct`` are the last lines of standard error and the last key of
that object.  With no TPU, or fewer chips than the cell asks, it exits 3
and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
STALL_S = 0.25               # a tick longer than this is reported as a stall
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def process_start() -> float:
    """This process's start on the ``time.monotonic`` clock (Linux)."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        return ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _T0


def load_cell(name: str) -> tuple[dict, dict, dict, dict, dict]:
    """(manifest, workload entry, config, mix, cell parameters)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    bench = ROOT / "perfbench"
    cfg = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    params = json.loads((bench / "cells" / f"{name}.json").read_text())
    return manifest, w, cfg, mix, params


def metric_names(manifest: dict, cell: str, trace: bool) -> list[dict]:
    key = "per_layer" if trace else "end_to_end"
    return [m for m in manifest[key]
            if "workloads" not in m or cell in m["workloads"]]


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chips(chips: int) -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        say(f"no accelerator: {e}")
        raise SystemExit(3)
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"needs {chips} TPU chip(s); JAX has {len(devs)} "
            f"{devs[0].platform} device(s)")
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def read_trace(trace_dir: str):
    from perfbench import tracefile
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return tracefile.load(found[-1])


def breakdown(run) -> dict:
    from perfbench import tracefile
    t0, t1 = run.traced_ns()
    progs = tracefile.program_seconds(run.trace.modules[0], t0, t1)
    idle = tracefile.idle_by_host_span(run.trace)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(progs), "idle_gaps": top(idle)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scheduler", default=None,
                    help="serve with this scheduler instead of the cell's "
                         "(for comparisons kept out of the benchmark)")
    args = ap.parse_args(argv)
    manifest, w, cfg, mix, params = load_cell(args.workload)
    return run_cell(manifest, w, cfg, mix, params, args.seed, args.seconds,
                    bool(args.trace), args.scheduler)


def run_cell(manifest, w, cfg, mix, params, seed, seconds, trace,
             scheduler=None) -> dict:
    """One run; prints the result and returns it."""
    start = process_start()
    compile_cache()
    from perfbench import check, driver, stats, tracefile
    from perfbench.rundata import RunData, reader
    from perfbench.stats import percentile
    device = require_chips(w["chips"])
    cell = driver.Cell(cfg, mix, params, seed, scheduler)
    marks = [("start", start), ("imports", time.monotonic())]
    cell.build()
    marks.append(("weights and engine", time.monotonic()))
    cell.warm()
    marks.append(("warm-up", time.monotonic()))
    cell.history()
    marks.append(("history", time.monotonic()))
    setup_s = time.monotonic() - start
    say("set-up: " + ", ".join(f"{name} {b - a:.3f} s" for (_, a), (name, b)
                               in zip(marks, marks[1:])))
    win = cell.run_window(seconds, trace)
    say(f"window: {len(win.sent)} sent, {len(win.served)} finished, "
        f"{win.failed} failed; {win.close - win.open:.3f} s; generator lag "
        f"p99 {percentile(win.lag, 99) or 0.0:.6f} s, max "
        f"{max(win.lag, default=0.0):.6f} s")
    say(f"programs built inside the window: {win.compiles}")
    say(f"host: {len(win.tick_s)} ticks, longest {max(win.tick_s, default=0.0):.6f}"
        f" s, {sum(t > STALL_S for t in win.tick_s)} over {STALL_S} s; "
        f"{len(win.gc_s)} garbage collections, {sum(win.gc_s):.6f} s, "
        f"longest {max(win.gc_s, default=0.0):.6f} s")
    say("time to first token p90, all / short (not judged): " + " / ".join(
        f"{percentile(stats.ttfts(win.sent, win.close, c), 90) or 0.0:.6f} s"
        for c in (None, "short")))
    device["memory_peak_bytes"] = driver.peak_bytes(w["chips"])
    cell.free()
    run = RunData(cfg=cfg, window=win, setup_s=setup_s,
                  memory_peak_bytes=device["memory_peak_bytes"],
                  device_kind=device["kind"])
    if trace:
        t_read = time.monotonic()
        run.trace = read_trace(win.trace_dir)
        say(f"trace: read in {time.monotonic() - t_read:.3f} s, "
            f"{sum(map(len, run.trace.ops))} device ops")
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        t0, t1 = run.traced_ns()
        busy = [tracefile.busy_ns(ops, t0, t1) / 1e9
                for ops in run.trace.ops[:w["chips"]]]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (t1 - t0) / 1e9
    metrics = {}
    for m in metric_names(manifest, w["name"], trace):
        v = reader(m["name"])(run)
        if v is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    numbers = check.compare(cfg, seed, win.prompts, win.served,
                            cfg["engine"]["s_max"])
    limits = params["limits"]
    checks = {"logit_gap": {"value": numbers["logit_gap"],
                            "limit": limits["logit_gap"]},
              "served_requests_compared": {"value": numbers["requests"],
                                           "limit": 1}}
    correct = (numbers["logit_gap"] <= limits["logit_gap"]
               and numbers["requests"] >= 1)
    result = {"correct": bool(correct), "attempted": len(win.sent),
              "failed": win.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown(run)
    result["checks"] = checks
    say(f"compared {numbers['positions']} served tokens of "
        f"{numbers['requests']} requests; served token is the reference's "
        f"top at {numbers['top_agree']:.4f} of them")
    say(f"logit_gap {numbers['logit_gap']:.6f} limit "
        f"{limits['logit_gap']} (<=)")
    say(f"served_requests_compared {numbers['requests']} limit 1 (>=)")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
