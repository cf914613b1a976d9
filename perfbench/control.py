#!/usr/bin/env python3
"""Readings the correctness limit is set from, on the chip.

    python3 perfbench/control.py --workload qwen3-4b.mixed --seconds 20 \\
        --seeds 101 102 103 --control-seeds 101 102 103

For each seed, in one process: the cell's engine serves a window at the
cell's rate, and its served tokens go through the reference as in a
benchmark run (the program's reading of ``logit_gap``).  For each control
seed the reference's float8 twin (every matrix product's operands rounded to
float8 e4m3, one absmax scale per tensor) is put in the program's place on
the same prompts and served tokens: at each position the token it ranks
first is read against the float32 reference (the control's reading).  One
JSON line per seed.  A benchmark run never runs this.
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
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    _, w, cfg, mix, params = bench.load_cell(args.workload)
    bench.compile_cache()
    bench.require_chips(w["chips"])
    from perfbench import check, driver
    for seed in args.seeds:
        cell = driver.Cell(cfg, mix, params, seed)
        cell.build()
        cell.warm()
        cell.history()
        win = cell.run_window(args.seconds)
        cell.free()
        out = check.compare(cfg, seed, win.prompts, win.served,
                            cfg["engine"]["s_max"],
                            control=seed in args.control_seeds)
        out.update(seed=seed, sent=len(win.sent), failed=win.failed,
                   compiles_in_window=win.compiles)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
