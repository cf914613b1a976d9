#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest Poisson rate the
engine sustains without a growing backlog.

    python3 perfbench/sweep.py --workload qwen3-4b.mixed --seed 5 \\
        --seconds 30 --rates 2 3 4 5

One process builds and warms the cell once, then runs an open-loop window at
each rate (a fresh seed stream each), finishing what each sent before the
next.  Per rate it prints one JSON line: requests sent and finished inside
the window, the queue at the close, time to first token in the window's
first and last thirds (it grows through the window above the knee), and the
output rate.  The knee is read from these by hand and written into the
cell's file as its rate; the sweep is no part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from perfbench import run as bench  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    _, w, cfg, mix, params = bench.load_cell(args.workload)
    bench.compile_cache()
    bench.require_chips(w["chips"])
    from perfbench import driver
    from perfbench.stats import percentile, ttfts
    cell = driver.Cell(cfg, mix, params, args.seed)
    cell.build()
    cell.warm()
    cell.history()
    for k, rate in enumerate(args.rates):
        cell.rate = rate
        cell.seed = args.seed + 1 + k
        win = cell.run_window(args.seconds)
        third = args.seconds / 3
        first = [s for s in win.sent if s.due < win.open + third]
        last = [s for s in win.sent if s.due >= win.close - third]
        done = sum(1 for s in win.sent
                   if s.finished is not None and s.finished <= win.close)
        print(json.dumps({
            "rate_rps": rate, "sent": len(win.sent),
            "finished_in_window": done,
            "queued_at_close": win.waiting_at_close,
            "ttft_p50_first_third_s": percentile(
                ttfts(first, win.close), 50),
            "ttft_p50_last_third_s": percentile(ttfts(last, win.close), 50),
            "ttft_short_p90_s": percentile(
                ttfts(win.sent, win.close, "short"), 90),
            "ttft_p90_s": percentile(ttfts(win.sent, win.close), 90),
            "tbt_p99_s": percentile(win.gaps, 99),
            "output_tok_s": win.tokens / (win.close - win.open),
            "compiles_in_window": win.compiles}), flush=True)


if __name__ == "__main__":
    main()
