#!/usr/bin/env python3
"""Smoke run of the served path on a TPU: qwen3-4b at its published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four engines behind the router

One chip: ``serve()`` with EWSJF serves a dozen mixed requests once in each
engine mode (bucketed prefill; chunked prefill with radix prefix reuse),
then the engine's first-token logits are held against a float32 reference
forward over the same bfloat16 weights.  ``--chips 4``: an ``EngineFleet``
of four engines, engine i on chip i, behind ``EWSJFRouter`` and a
``PrefixDirectory``, serves the same requests as one engine on chip 0.
They must agree token for token, or, where bf16 rounding flips an id, both
ids must be near-ties under the float32 reference.

Weights are random, drawn from ``--seed``; nothing is downloaded.  It all
runs in this one process, since a chip belongs to one process.  Any failed
phase exits non-zero.  The last line, printed only when every phase passed,
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The lines before it are smoke facts (compiles, seconds, tokens, memory),
not measurements of speed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.cluster import EngineFleet, EWSJFRouter  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import Request  # noqa: E402
from repro.kvplane import PrefixDirectory  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.common import DtypePolicy  # noqa: E402
from repro.models.model import prefill  # noqa: E402
from repro.serving import EngineConfig, ServingEngine  # noqa: E402
from repro.serving.api import FULL_WIDTH_ENGINE, SCHEDULERS, serve  # noqa: E402

ARCH = "qwen3-4b"
SHARED_PREFIX = 256          # tokens the prefix-sharing requests have in common
CHUNK = 256                  # chunked-mode per-tick prefill budget
REF_LEN = 128                # prompt length of the two reference-checked requests
# (prompt_len, shares the prefix).  Request 0 alone arrives at t=0 and is one
# chunk long, so it is prefilled and publishes the shared blocks before the
# rest arrive; the sharers then copy those blocks instead of recomputing them.
MIX = ((SHARED_PREFIX, True), (16, False), (32, False), (48, False),
       (64, False), (96, False), (REF_LEN, False), (REF_LEN, False),
       (200, False), (320, True), (400, True), (480, True))
LATE = 0.01                  # arrival (s) of every request but the first

# Agreement limit for first-token logits, as a multiple of the reference
# logits' standard deviation (about 1 for these random weights).  bf16 keeps
# 8 significant bits; over 36 layers the engine's logits drifted from the
# float32 reference by at most 0.09 std (reduced-width qwen3 stacks on the
# CPU, 16k vocab).  0.25 leaves room for the full width and vocab, while a
# wrong mask, position or weight moves logits by a whole std.
LOGIT_TOL = 0.25


class CompileLog:
    """Backend compiles JAX reports, with the seconds each took."""

    def __init__(self):
        self.seen: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seen.append((str(kw.get("fun_name", "?")), secs))

    def report(self, phase: str) -> None:
        """Print the compiles since the last report: the step programs one
        by one, the small eager ones as a count."""
        big = [(n, s) for n, s in self.seen if s >= 0.5]
        small = [s for _, s in self.seen if s < 0.5]
        for name, secs in big:
            print(f"[{phase}] compiled {name} in {secs:.3f} s")
        print(f"[{phase}] compiled {len(small)} small programs in "
              f"{sum(small):.3f} s")
        self.seen.clear()


def model_config(smoke: bool):
    return get_smoke_config(ARCH) if smoke else get_config(ARCH)


def make_requests(seed: int, vocab: int) -> list[Request]:
    """The smoke traffic: ``MIX`` with prompts and output lengths (8 to 32
    tokens) drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    reqs = []
    for i, (n, shared) in enumerate(MIX):
        toks = rng.integers(0, vocab, n)
        if shared:
            toks[:SHARED_PREFIX] = prefix
        reqs.append(Request(prompt_len=n, request_id=i,
                            arrival_time=0.0 if i == 0 else LATE,
                            max_new_tokens=int(rng.integers(8, 33)),
                            prompt_tokens=toks.astype(np.int32)))
    return reqs


def engine_config(chunked: bool, **kw) -> EngineConfig:
    if chunked:
        kw.update(chunk_prefill_tokens=CHUNK, enable_prefix_cache=True)
    return EngineConfig(**FULL_WIDTH_ENGINE, **kw)


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def check_served(reqs: list[Request], outputs: dict, shed: int,
                 vocab: int) -> int:
    """Every request finished with all its tokens, each a vocab id, and
    nothing was shed.  Returns the tokens produced."""
    if shed:
        raise RuntimeError(f"{shed} requests shed")
    for r in reqs:
        toks = outputs.get(r.request_id)
        if toks is None or len(toks) != r.max_new_tokens:
            raise RuntimeError(f"request {r.request_id}: got "
                               f"{None if toks is None else len(toks)} of "
                               f"{r.max_new_tokens} tokens")
        if not all(0 <= t < vocab for t in toks):
            raise RuntimeError(f"request {r.request_id}: id out of vocab")
    return sum(len(outputs[r.request_id]) for r in reqs)


def first_token_logits(eng: ServingEngine, prompts: np.ndarray) -> np.ndarray:
    """The engine's own bucketed prefill program on equal-length prompts:
    (n, vocab) float32 logits of the first generated token."""
    n, length = prompts.shape
    bucket = next(b for b in eng.e.buckets if b >= length)
    tokens = np.zeros((n, bucket), np.int32)
    tokens[:, :length] = prompts
    lens = np.full((n,), length, np.int32)
    logits, _ = eng._get_prefill_jit(bucket, n)(
        eng.params, eng._put(tokens), eng._put(lens))
    return np.asarray(logits[:, 0])


def reference_logits(cfg, params, prompts: np.ndarray) -> np.ndarray:
    """Plain reference: ``models.model.prefill`` over the same bf16 weights,
    computing in float32 at the highest matmul precision.  The bf16 weights
    are upcast layer by layer inside the scan (a float32 copy of the whole
    model would not fit one chip); only the head is upcast whole, so the
    last hidden state is not rounded to bf16 before it."""
    ref_params = dict(params, head=params["head"].astype(jnp.float32))
    policy = DtypePolicy(jnp.bfloat16, jnp.float32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(
            lambda p, t: prefill(p, {"tokens": t}, cfg, policy=policy)[0]
        )(ref_params, prompts)
    return np.asarray(logits[:, 0])


def logits_agree(got: np.ndarray, ref: np.ndarray) -> tuple[bool, float,
                                                            float]:
    """(within tolerance, max |got - ref|, tolerance) for one logit row."""
    tol = LOGIT_TOL * float(ref.std())
    diff = float(np.abs(got - ref).max())
    return diff <= tol, diff, tol


def greedy_agrees(token: int, ref: np.ndarray) -> bool:
    """The greedy token is the reference argmax, or, where the reference's
    top two are closer than the tolerance (a tie bf16 cannot resolve), a
    token whose reference logit is within the tolerance of the top."""
    tol = LOGIT_TOL * float(ref.std())
    return token == int(ref.argmax()) or ref[token] >= ref.max() - tol


def serve_mode(phase: str, reqs: list[Request], chunked: bool, *,
               smoke: bool, seed: int, params=None) -> dict:
    t0 = time.perf_counter()
    res = serve(ARCH, reqs, scheduler="ewsjf", smoke=smoke, params=params,
                engine_config=engine_config(chunked), seed=seed)
    secs = time.perf_counter() - t0
    eng = res["engine"]
    tokens = check_served(reqs, eng.output_tokens, res["stats"]["shed"],
                          eng.cfg.vocab_size)
    print(f"[{phase}] served {len(res['finished'])} requests, {tokens} "
          f"tokens, in {secs:.3f} s wall; prefill batches "
          f"{eng.prefill_batches}, chunks {eng.chunks_run}, prefix tokens "
          f"copied {eng.prefix_saved_tokens}")
    return res


def phase_one_chip(seed: int, log: CompileLog, smoke: bool = False) -> None:
    """Both engine modes through ``serve()``, then the reference check."""
    cfg = model_config(smoke)
    t0 = time.perf_counter()
    bucketed = serve_mode("bucketed", make_requests(seed, cfg.vocab_size),
                          chunked=False, smoke=smoke, seed=seed)
    eng = bucketed["engine"]
    params = eng.params
    print(f"[bucketed] param bytes {tree_bytes(params)}, cache bytes "
          f"{tree_bytes(eng.caches)}")
    log.report("bucketed")

    reqs = make_requests(seed, cfg.vocab_size)
    ref_ids = [r.request_id for r in reqs if r.prompt_len == REF_LEN][:2]
    prompts = np.stack([reqs[i].prompt_tokens for i in ref_ids])
    got = first_token_logits(eng, prompts)
    ref = reference_logits(cfg, params, prompts)
    for row, rid in enumerate(ref_ids):
        ok, diff, tol = logits_agree(got[row], ref[row])
        served = eng.output_tokens[rid][0]
        top2 = np.sort(ref[row])[-2:]
        print(f"[reference] request {rid}: max |logit diff| {diff:.6f} "
              f"(limit {tol:.6f}); served token {served}, reference argmax "
              f"{int(ref[row].argmax())}, reference top-2 gap "
              f"{float(top2[1] - top2[0]):.6f}")
        if not ok:
            raise RuntimeError(f"request {rid}: logits off the reference")
        if not greedy_agrees(served, ref[row]):
            raise RuntimeError(f"request {rid}: greedy token {served} is not "
                               f"the reference's")
    log.report("reference")
    del bucketed, eng
    gc.collect()            # engines hold reference cycles through their jits

    chunked = serve_mode("chunked", reqs, chunked=True, smoke=smoke,
                         seed=seed, params=params)
    if chunked["engine"].prefix_saved_tokens <= 0:
        raise RuntimeError("no cached prefix block was copied")
    log.report("chunked")
    print(f"[one-chip] wall {time.perf_counter() - t0:.3f} s")


def bytes_in_use(dev) -> int:
    return dev.memory_stats()["bytes_in_use"]


def devices_of(tree) -> set:
    out: set = set()
    for x in jax.tree.leaves(tree):
        out |= x.devices()
    return out


def phase_fleet(seed: int, devices: list, log: CompileLog,
                smoke: bool = False) -> None:
    """Four engines, one per device, behind ``EWSJFRouter`` with a
    ``PrefixDirectory``, against one engine on ``devices[0]``."""
    cfg = model_config(smoke)
    t0 = time.perf_counter()
    single = serve_mode("single", make_requests(seed, cfg.vocab_size),
                        chunked=True, smoke=smoke, seed=seed)
    params = single["engine"].params
    want = dict(single["engine"].output_tokens)
    del single
    gc.collect()
    log.report("single")

    policy = None if smoke else DtypePolicy.serve()
    engines = [ServingEngine(cfg, params, SCHEDULERS["ewsjf"](),
                             engine_config(True, engine_id=i),
                             policy=policy, device=dev)
               for i, dev in enumerate(devices)]
    fleet = EngineFleet(engines, router=EWSJFRouter(),
                        directory=PrefixDirectory())
    reqs = make_requests(seed, cfg.vocab_size)
    t1 = time.perf_counter()
    res = fleet.serve(reqs)
    secs = time.perf_counter() - t1
    got = {}
    for e in engines:
        got.update(e.output_tokens)
    tokens = check_served(reqs, got, res["shed"], cfg.vocab_size)
    print(f"[fleet] served {res['finished']} requests, {tokens} tokens, in "
          f"{secs:.3f} s wall; per engine "
          f"{[s['finished'] for s in res['engines'].values()]} finished, "
          f"{[e.prefix_saved_tokens for e in engines]} prefix tokens copied, "
          f"{res['prefix_fetches']} prefix fetches")

    for i, (e, dev) in enumerate(zip(engines, devices)):
        placed = devices_of(e.params) | devices_of(e.caches)
        if placed != {dev}:
            raise RuntimeError(f"engine {i} sits on {placed}, not {dev}")
        in_use = bytes_in_use(dev)
        print(f"[fleet] engine {i} on {dev}: {in_use} bytes in use")
        if in_use <= 0:
            raise RuntimeError(f"{dev} shows no memory in use")

    same = [r.request_id for r in reqs if got[r.request_id] == want[r.request_id]]
    print(f"[fleet] {len(same)} of {len(reqs)} requests match the single "
          f"engine token for token")
    owner = {rid: e for e in engines for rid in e.output_tokens}
    diverged = []
    for r in reqs:
        if r.request_id in same:
            continue
        at = next(k for k, (a, b) in enumerate(
            zip(got[r.request_id], want[r.request_id])) if a != b)
        prompt = r.prompt_tokens[None]
        ok, diff, tol = logits_agree(
            first_token_logits(owner[r.request_id], prompt)[0],
            first_token_logits(engines[0], prompt)[0])
        print(f"[fleet] request {r.request_id} differs from token {at}; "
              f"first-token max |logit diff| of its engine against engine 0 "
              f"{diff:.6f} (limit {tol:.6f})")
        if not ok:
            raise RuntimeError(f"request {r.request_id}: fleet logits off "
                               f"the single engine's")
        diverged.append((r, at))
    del fleet, engines, owner
    gc.collect()

    # The same program gives the same logits on every chip, so a differing
    # id comes from the serving path: chunk boundaries and prefix copies
    # differ between the fleet and one engine, and bf16 rounds them
    # differently.  That is only a tie flipping if the float32 reference,
    # given the common tokens, puts both ids within the tolerance of its top.
    for r, at in diverged:
        a, b = got[r.request_id][at], want[r.request_id][at]
        seq = np.concatenate([r.prompt_tokens, got[r.request_id][:at]])
        ref = reference_logits(cfg, params, seq[None].astype(np.int32))[0]
        tol = LOGIT_TOL * float(ref.std())
        print(f"[fleet] request {r.request_id} token {at}: fleet {a}, single "
              f"engine {b}; reference logits below its top by "
              f"{float(ref.max() - ref[a]):.6f} and "
              f"{float(ref.max() - ref[b]):.6f} (limit {tol:.6f})")
        if not (greedy_agrees(a, ref) and greedy_agrees(b, ref)):
            raise RuntimeError(f"request {r.request_id}: token {at} differs "
                               f"and is no tie under the reference")
    log.report("fleet")
    print(f"[fleet] wall {time.perf_counter() - t0:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"jax {jax.__version__}; device_kind {dev.device_kind}; "
          f"{len(devices)} devices; compile cache {cache}")
    log = CompileLog()
    if args.chips == 4:
        phase_fleet(args.seed, devices[:4], log)
    else:
        phase_one_chip(args.seed, log)
    for d in devices[:args.chips]:
        print(f"{d}: peak_bytes_in_use {d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
