"""Multi-pod dry run: lower and compile every (arch x shape x mesh) cell on
512 placeholder CPU devices and record memory, cost and collectives.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape decode_32k
    PYTHONPATH=src python -m repro.launch.dryrun --all

A CPU-only planner: it never takes an accelerator from another process.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count and the backend
#   at first init.  512 placeholder host devices back both the 16x16
#   single-pod mesh and the 2x16x16 multi-pod mesh.  The environment is
#   inherited by the --all children, so they stay on the CPU too.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
from pathlib import Path # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "launch_out" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective op in the optimized
    (post-SPMD) HLO.  Shapes in this module are already per-device shards, so
    the totals are per-chip traffic proxies (EXPERIMENTS.md §Roofline
    conventions)."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") or s.startswith("ROOT"):
            m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", s)
            if not m:
                continue
            type_str, op = m.group(1), m.group(2)
            # strip -start/-done suffixes (async collectives)
            base = op
            for suf in ("-start", "-done"):
                if base.endswith(suf):
                    base = base[: -len(suf)]
            if base in _COLLECTIVES and not op.endswith("-done"):
                out[base]["count"] += 1
                out[base]["bytes"] += _shape_bytes(type_str)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, moe_impl: str,
             variant: str = "base", extra: dict | None = None) -> dict:
    import jax
    from repro.launch.cells import SHAPES, build_cell, skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.configs import get_config

    cfg = get_config(arch)
    reason = skip_reason(cfg, shape)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    row = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "variant": variant, "moe_impl": moe_impl}
    if reason:
        row["status"] = "skipped"
        row["reason"] = reason
        return row

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        # build inside the mesh context: abstract tracing hits
        # with_sharding_constraint(PartitionSpec) which needs a mesh.
        cell = build_cell(arch, shape, mesh, moe_impl=moe_impl,
                          **(extra or {}))
        jitted = jax.jit(cell.step_fn,
                         in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    row["status"] = "ok"
    row["lower_s"] = round(t_lower, 2)
    row["compile_s"] = round(t_compile, 2)
    row["desc"] = cell.static_desc
    try:
        mem = compiled.memory_analysis()
        row["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:                                   # pragma: no cover
        row["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        row["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and (
                           "flops" in k or "bytes" in k or k == "utilization")}
    except Exception as e:                                   # pragma: no cover
        row["cost"] = {"error": str(e)}
    try:
        hlo = compiled.as_text()
        row["collectives"] = parse_collectives(hlo)   # raw (loop-uncorrected)
        row["hlo_lines"] = hlo.count("\n")
        from repro.launch.hlo_analysis import analyze_hlo
        row["hlo_corrected"] = analyze_hlo(hlo)       # loop-corrected, per-chip
    except Exception as e:                                   # pragma: no cover
        row["collectives"] = {"error": str(e)}
    # analytic model flops (MODEL_FLOPS = 6·N_active·D for train; 2·N·D fwd)
    n_active = cfg.active_param_count()
    d = cell.static_desc
    tokens = d["batch"] * (d["seq"] if d["kind"] != "decode" else 1)
    mult = 6.0 if d["kind"] == "train" else 2.0
    row["model_flops"] = mult * n_active * tokens
    row["n_params"] = cfg.param_count()
    row["n_params_active"] = n_active
    return row


def cell_filename(arch: str, shape: str, mesh_name: str, variant: str) -> Path:
    return OUT_DIR / f"{arch}__{shape}__{mesh_name}__{variant}.json"


def main() -> None:
    ap = argparse.ArgumentParser(description="Multi-pod dry-run driver")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-impl", default="dropping",
                    choices=["dense", "dropping", "ep_a2a"])
    ap.add_argument("--variant", default="base")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch×shape×mesh) cell via subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.launch.cells import ARCHS, SHAPES
        jobs = [(a, s, mp) for a in ARCHS for s in SHAPES
                for mp in (False, True)]
        done = failed = skipped = 0
        for arch, shape, mp in jobs:
            mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
            fn = cell_filename(arch, shape, mesh_name, args.variant)
            if fn.exists() and not args.force:
                done += 1
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape,
                   "--moe-impl", args.moe_impl, "--variant", args.variant]
            if mp:
                cmd.append("--multi-pod")
            print(f"[dryrun] {arch} × {shape} × {mesh_name} ...", flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout,
                                   capture_output=True, text=True)
                if r.returncode != 0:
                    failed += 1
                    fn.write_text(json.dumps({
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "variant": args.variant, "status": "error",
                        "error": r.stderr[-4000:]}, indent=1))
                    print(f"  FAILED: {r.stderr.strip().splitlines()[-1] if r.stderr else '?'}")
                else:
                    done += 1
            except subprocess.TimeoutExpired:
                failed += 1
                fn.write_text(json.dumps({
                    "arch": arch, "shape": shape, "mesh": mesh_name,
                    "variant": args.variant, "status": "timeout"}, indent=1))
                print("  TIMEOUT")
        print(f"[dryrun] complete: {done} ok, {failed} failed")
        return

    assert args.arch and args.shape, "--arch and --shape required"
    row = run_cell(args.arch, args.shape, args.multi_pod, args.moe_impl,
                   args.variant)
    mesh_name = row["mesh"]
    fn = cell_filename(args.arch, args.shape, mesh_name, args.variant)
    fn.write_text(json.dumps(row, indent=1))
    print(json.dumps({k: row[k] for k in row
                      if k not in ("collectives",)}, indent=1))
    if "collectives" in row:
        print("collectives:", json.dumps(row["collectives"], indent=1))


if __name__ == "__main__":
    main()
