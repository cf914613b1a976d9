"""Serving launcher: a command line over :func:`repro.serving.api.serve`,
serving the paper's mixed workload with a pluggable admission scheduler.

    PYTHONPATH=src python -m repro.launch.serve --scheduler ewsjf --requests 32
    PYTHONPATH=src python -m repro.launch.serve --full   # published widths, bf16

``--full`` needs an accelerator that holds the model (qwen3-4b: one 16 GB
TPU v5e chip); without it the architecture's CPU smoke preset is served.
"""

from __future__ import annotations

import argparse

import numpy as np

from ..core import Request
from ..serving.api import SCHEDULERS, serve
from .compile_cache import enable_compile_cache


def mixed_requests(n: int, seed: int = 0) -> list[Request]:
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        short = rng.random() < 0.8
        ln = int(rng.integers(8, 32)) if short else int(rng.integers(96, 200))
        reqs.append(Request(prompt_len=ln, arrival_time=0.0,
                            max_new_tokens=int(rng.integers(2, 10))))
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--scheduler", default="ewsjf", choices=sorted(SCHEDULERS))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths in bfloat16")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    res = serve(args.arch, mixed_requests(args.requests, args.seed),
                scheduler=args.scheduler, smoke=not args.full, seed=args.seed)
    fin, st = res["finished"], res["stats"]
    ttft = np.asarray([r.ttft for r in fin if r.ttft is not None])
    short = np.asarray([r.ttft for r in fin
                        if r.ttft is not None and r.prompt_len <= 32])
    print(f"scheduler={args.scheduler}")
    for k, v in st.items():
        print(f"  {k:16s} {v:.3f}" if isinstance(v, float) else f"  {k:16s} {v}")
    print(f"  mean_ttft        {ttft.mean():.3f}s")
    if len(short):
        print(f"  mean_ttft_short  {short.mean():.3f}s")


if __name__ == "__main__":
    main()
