#!/usr/bin/env python
"""Offline summary of a Chrome-trace JSON exported by the obs plane.

Stdlib-only CLI over the Perfetto-loadable trace that
``TraceRecorder.to_chrome_trace`` (and ``benchmarks/bench_cluster_routing
--trace``) writes:

    python tools/trace_summary.py trace_sample.json
    python tools/trace_summary.py trace_sample.json --top 5
    python tools/trace_summary.py trace_sample.json --request 42
    python tools/trace_summary.py trace_sample.json --slot 2

Reports the top-N slowest requests (arrival → finish) with their
wait / prefill / decode stage split, the per-stage aggregate breakdown,
and per-replica engine occupancy from the spans — both per span name and
grouped by stage (the engine's ``chunk`` / ``recompute`` spans are
prefill-stage work, ``attach`` is the radix prefix-KV copy; the profiler's
``engine.prefill`` / ``engine.chunk`` / ``engine.decode_step`` spans group
with them).  ``--slot``
prints one engine slot's lifecycle (every span and instant carrying that
slot), mirroring ``--request``.  CI runs this as a smoke check over the
quick-bench trace artifacts.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

# Span-name -> stage grouping, from the obs plane itself (a stdlib-only
# package).  Unknown span names group under "other".
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
from repro.obs.trace import SPAN_STAGES  # noqa: E402


def load_events(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    return [e for e in events if e.get("ph") in ("X", "i")]


def lifecycles(events: list[dict]) -> dict[int, dict[str, float]]:
    """request_id -> {kind: first-seen time (seconds)} for instant events."""
    out: dict[int, dict[str, float]] = defaultdict(dict)
    for e in events:
        if e.get("ph") != "i":
            continue
        rid = e.get("args", {}).get("request_id", e.get("tid"))
        if rid is None:
            continue
        kind = e["name"]
        t = e["ts"] / 1e6
        if kind not in out[rid] or t < out[rid][kind]:
            out[rid][kind] = t
    return dict(out)


def stage_split(ev: dict[str, float]) -> dict[str, float]:
    """wait/prefill/decode/total seconds for one request's event map
    (same boundaries as TraceRecorder.stage_breakdown)."""
    out = {"wait": 0.0, "prefill": 0.0, "decode": 0.0, "total": 0.0}
    arr = ev.get("arrival", ev.get("enqueue"))
    if arr is None:
        return out
    if "dispatch" in ev:
        out["wait"] = max(0.0, ev["dispatch"] - arr)
    if "first_token" in ev and "dispatch" in ev:
        out["prefill"] = max(0.0, ev["first_token"] - ev["dispatch"])
    if "finish" in ev and "first_token" in ev:
        out["decode"] = max(0.0, ev["finish"] - ev["first_token"])
    end = ev.get("finish", max(ev.values()))
    out["total"] = max(0.0, end - arr)
    return out


def engine_occupancy(events: list[dict]) -> dict[int, dict[str, float]]:
    """replica pid -> {span name: total busy seconds} from X-phase spans."""
    out: dict[int, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for e in events:
        if e.get("ph") == "X":
            out[e.get("pid", 0)][e["name"]] += e.get("dur", 0.0) / 1e6
    return {pid: dict(spans) for pid, spans in out.items()}


def stage_occupancy(events: list[dict]) -> dict[int, dict[str, float]]:
    """replica pid -> {stage: busy seconds}: spans folded through
    SPAN_STAGES so the engine's chunk/recompute/attach names land in the
    same stage taxonomy the DES reports."""
    out: dict[int, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for e in events:
        if e.get("ph") == "X":
            stage = SPAN_STAGES.get(e["name"], "other")
            out[e.get("pid", 0)][stage] += e.get("dur", 0.0) / 1e6
    return {pid: dict(stages) for pid, stages in out.items()}


def slot_events(events: list[dict], slot: int) -> list[dict]:
    """Every span/instant carrying ``args.slot == slot``, time-ordered —
    one engine slot's lifecycle (park → attach → chunk* → promote →
    preempt/finish cycles)."""
    out = [e for e in events
           if e.get("args", {}).get("slot") == slot]
    out.sort(key=lambda e: e.get("ts", 0.0))
    return out


def summarize(path: str, top: int = 10,
              request: int | None = None,
              slot: int | None = None) -> int:
    events = load_events(path)
    if not events:
        print(f"{path}: no trace events", file=sys.stderr)
        return 1
    lives = lifecycles(events)
    splits = {rid: stage_split(ev) for rid, ev in lives.items()}

    if slot is not None:
        evs = slot_events(events, slot)
        if not evs:
            print(f"slot {slot}: no events in trace window", file=sys.stderr)
            return 1
        print(f"slot {slot}: {len(evs)} events")
        for e in evs:
            t = e.get("ts", 0.0) / 1e6
            dur = e.get("dur", 0.0) / 1e6 if e.get("ph") == "X" else 0.0
            rid = e.get("args", {}).get("request_id", "-")
            tail = f" dur={dur:.4f}s" if dur else ""
            print(f"  t={t:9.4f}s  {e['name']:10s} request={rid}{tail}")
        busy = defaultdict(float)
        for e in evs:
            if e.get("ph") == "X":
                busy[SPAN_STAGES.get(e["name"], "other")] += \
                    e.get("dur", 0.0) / 1e6
        if busy:
            print("  busy: " + " ".join(f"{k}={v:.4f}s" for k, v in
                                        sorted(busy.items())))
        return 0

    if request is not None:
        ev = lives.get(request)
        if ev is None:
            print(f"request {request}: not in trace window", file=sys.stderr)
            return 1
        print(f"request {request}:")
        for kind, t in sorted(ev.items(), key=lambda kv: kv[1]):
            print(f"  t={t:9.4f}s  {kind}")
        br = splits[request]
        print(f"  stages: wait={br['wait']:.4f}s prefill={br['prefill']:.4f}s "
              f"decode={br['decode']:.4f}s total={br['total']:.4f}s")
        return 0

    n = len(splits)
    finished = sum(1 for ev in lives.values() if "finish" in ev)
    print(f"{path}: {len(events)} events, {n} requests in window "
          f"({finished} finished)")

    agg = {"wait": 0.0, "prefill": 0.0, "decode": 0.0, "total": 0.0}
    for br in splits.values():
        for k in agg:
            agg[k] += br[k]
    if agg["total"] > 0:
        print("\nper-stage share of request time (all requests in window):")
        for k in ("wait", "prefill", "decode"):
            print(f"  {k:8s} {agg[k]:9.3f}s  ({agg[k] / agg['total']:5.1%})")

    slowest = sorted(splits.items(), key=lambda kv: kv[1]["total"],
                     reverse=True)[:top]
    print(f"\ntop {len(slowest)} slowest requests (arrival → finish):")
    print(f"  {'request':>8s} {'total':>9s} {'wait':>9s} {'prefill':>9s} "
          f"{'decode':>9s}")
    for rid, br in slowest:
        print(f"  {rid:8d} {br['total']:8.4f}s {br['wait']:8.4f}s "
              f"{br['prefill']:8.4f}s {br['decode']:8.4f}s")

    occ = engine_occupancy(events)
    if occ:
        print("\nper-replica engine busy time (spans):")
        for pid in sorted(occ):
            spans = " ".join(f"{k}={v:.3f}s" for k, v in
                             sorted(occ[pid].items()))
            print(f"  replica {pid}: {spans}")
        st_occ = stage_occupancy(events)
        print("\nper-replica engine busy time (stages):")
        for pid in sorted(st_occ):
            stages = " ".join(f"{k}={v:.3f}s" for k, v in
                              sorted(st_occ[pid].items()))
            print(f"  replica {pid}: {stages}")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome-trace JSON (from --trace / "
                                  "dump_chrome_trace)")
    ap.add_argument("--top", type=int, default=10,
                    help="how many slowest requests to list (default 10)")
    ap.add_argument("--request", type=int, default=None,
                    help="print one request's full lifecycle instead")
    ap.add_argument("--slot", type=int, default=None,
                    help="print one engine slot's lifecycle instead")
    args = ap.parse_args(argv)
    return summarize(args.trace, top=args.top, request=args.request,
                     slot=args.slot)


if __name__ == "__main__":
    raise SystemExit(main())
