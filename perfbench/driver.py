"""Drive one cell through the program's serving engine.

The engine is built from ``serve()``'s own pieces (``SCHEDULERS``, the bf16
``DtypePolicy.serve()``, the configuration's ``EngineConfig``) around the
``ModelConfig`` and weights of the configuration's family module
(``perfbench.check.reference``).  The harness owns the clock: it adds each
request at its due time, with ``arrival_time`` set to that due time, and
calls ``ServingEngine.tick`` in between, so time to first token counts the
wait a long tick imposes.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import check, traffic
from perfbench.stats import Sent

HISTORY_ID = 1 << 40
WARM_ID = 1 << 41
HISTORY_OUTPUT_CAP = 16      # tokens; the history only teaches prompt lengths
DRAIN_S = 120.0              # after the window, at most this long to finish
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Compiles:
    """Programs JAX builds (compiled, or read from the persistent cache)."""

    count = 0
    _on = False

    @classmethod
    def listen(cls) -> None:
        if cls._on:
            return
        cls._on = True
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **kw: cls._hit(ev == BACKEND_COMPILE))
        jax.monitoring.register_event_listener(
            lambda ev, **kw: cls._hit(ev == CACHE_HIT))

    @classmethod
    def _hit(cls, yes: bool) -> None:
        if yes:
            cls.count += 1


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file, from its
    family module."""
    return check.reference(c).model_config(c)


def engine_config(c: dict):
    from repro.serving.engine import EngineConfig
    e = dict(c["engine"])
    e["buckets"] = tuple(e["buckets"])
    return EngineConfig(temperature=0.0, **e)


def prefill_shapes(c: dict, mix: dict) -> list[tuple[int, int]]:
    """Every (bucket, rows) prefill the traffic can reach: a batch's bucket
    is its longest prompt's, its rows at most the free slots, and its
    prompts add up to at most ``max_prefill_tokens`` (a lone prompt always
    goes)."""
    e = c["engine"]
    lens = sorted({n for cls in mix["classes"]
                   for n in range(cls["prompt"]["min"],
                                  cls["prompt"]["max"] + 1)})
    if lens[-1] > max(e["buckets"]):
        raise ValueError(f"prompts up to {lens[-1]} exceed the largest "
                         f"bucket {max(e['buckets'])}")
    out, lo = [], 0
    for b in e["buckets"]:
        inside = [n for n in lens if lo < n <= b]
        lo = b
        if not inside:
            continue
        for rows in range(1, e["max_slots"] + 1):
            if rows == 1 or inside[0] + (rows - 1) * lens[0] <= \
                    e["max_prefill_tokens"]:
                out.append((b, rows))
    return out


@dataclass
class Window:
    """What the window did, on the engine's clock."""

    open: float = 0.0
    close: float = 0.0
    sent: list = field(default_factory=list)          # [Sent]
    lag: list = field(default_factory=list)           # seconds late, per add
    tokens: int = 0                                   # sampled in the window
    gaps: list = field(default_factory=list)          # inter-token seconds
    batches: list = field(default_factory=list)       # [(t, [prompt lens])]
    padded: int = 0
    real: int = 0
    compiles: int = 0
    ticks: list = field(default_factory=list)         # [(t, [(pos, left)])]
    tick_s: list = field(default_factory=list)        # host seconds per tick
    gc_s: list = field(default_factory=list)          # seconds per collection
    traced: Optional[tuple] = None                    # (open, close)
    trace_dir: Optional[str] = None
    served: dict = field(default_factory=dict)        # rid -> [token ids]
    prompts: dict = field(default_factory=dict)       # rid -> prompt ids
    failed: int = 0
    waiting_at_close: int = 0                         # queued at the close


class Cell:
    """One configuration under one traffic mix at the cell's rate."""

    def __init__(self, cfg: dict, mix: dict, params: dict, seed: int,
                 scheduler: Optional[str] = None):
        self.cfg, self.mix, self.params, self.seed = cfg, mix, params, seed
        self.family = check.reference(cfg)
        self.scheduler = scheduler or params.get("scheduler", "ewsjf")
        self.rate = float(params["rate_rps"])
        self.eng = None

    # ---- set-up --------------------------------------------------------

    def build(self, dtype=jnp.bfloat16) -> None:
        """Weights on the device from the seed, and the engine around them."""
        from repro.models.common import DtypePolicy
        from repro.serving.api import SCHEDULERS
        from repro.serving.engine import ServingEngine
        Compiles.listen()
        w = self.family.program_params(self.seed, self.cfg, dtype)
        policy = (DtypePolicy.serve() if dtype == jnp.bfloat16 else
                  DtypePolicy(dtype, dtype, jnp.float32))
        self.eng = ServingEngine(self.family.model_config(self.cfg), w,
                                 SCHEDULERS[self.scheduler](),
                                 engine_config(self.cfg), policy=policy)
        jax.block_until_ready(self.eng.params)

    def warm(self) -> None:
        """Run every prefill shape the traffic can reach and the decode step
        once, through the engine's own admission path, with a FIFO queue
        so each batch is exactly the shape wanted."""
        from repro.core import FCFSScheduler, Request
        eng, real = self.eng, self.eng.sched
        eng.sched = FCFSScheduler()
        rid = WARM_ID
        for bucket, rows in prefill_shapes(self.cfg, self.mix):
            for i in range(rows):
                n = bucket if i == 0 else 1
                eng.add_request(Request(prompt_len=n, max_new_tokens=1,
                                        request_id=rid,
                                        prompt_tokens=np.zeros(n, np.int32)))
                rid += 1
            eng.tick()
            if eng.sched.waiting():
                raise RuntimeError(f"warm-up batch ({bucket}, {rows}) was "
                                   "not admitted whole")
        steps = eng.e.decode_steps_per_tick
        eng.add_request(Request(prompt_len=1, max_new_tokens=2 * steps + 1,
                                request_id=rid,
                                prompt_tokens=np.zeros(1, np.int32)))
        while eng.has_work():
            eng.tick()
        eng.sched = real

    def history(self) -> None:
        """Give the scheduler ``history_requests`` of the mix (seed stream
        1) before the window, so the window sees its learned partition."""
        from repro.core import Request
        n = int(self.params.get("history_requests", 0))
        if n == 0:
            return
        eng = self.eng
        plan = traffic.generate(self.mix, rate=n, seconds=1.0, seed=self.seed,
                                vocab=self.cfg["vocab_size"], stream=1,
                                first_id=HISTORY_ID)
        now = eng.now()
        for p in plan:
            eng.add_request(Request(
                prompt_len=len(p.prompt), arrival_time=now,
                max_new_tokens=min(p.max_new_tokens, HISTORY_OUTPUT_CAP),
                request_id=p.rid, prompt_tokens=p.prompt))
        while eng.has_work():
            eng.tick()

    # ---- the measured window -------------------------------------------

    def run_window(self, seconds: float, trace: bool = False) -> Window:
        """Open loop for ``seconds``.  With ``trace`` a profiler trace
        records the whole window; it starts before the window opens and is
        written out after the close, so neither stalls the window."""
        from repro.core import Request
        eng = self.eng
        plan = traffic.generate(self.mix, self.rate, seconds, self.seed,
                                self.cfg["vocab_size"], stream=0)
        w = Window()
        if trace:
            import tempfile
            w.trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        ann = jax.profiler.TraceAnnotation
        tracing = None
        w.tokens = eng.tokens_out
        n_gaps, n_disp = len(eng.decode_gaps), len(eng.dispatch_log)
        padded, real = eng.padded_tokens, eng.real_tokens
        compiles = Compiles.count
        if trace:
            jax.profiler.start_trace(w.trace_dir)
            tracing = ann("bench.traced")
            tracing.__enter__()
        gc_timer = GcTimer(w.gc_s)
        gc.callbacks.append(gc_timer)
        w.open = eng.now()
        end = w.open + seconds
        i = 0
        reqs = {}
        while True:
            now = eng.now()
            if now >= end:
                break
            if i < len(plan) and w.open + plan[i].offset <= now:
                with ann("bench.ingest"):
                    while i < len(plan) and w.open + plan[i].offset <= now:
                        p = plan[i]
                        due = w.open + p.offset
                        r = Request(prompt_len=len(p.prompt), arrival_time=due,
                                    max_new_tokens=p.max_new_tokens,
                                    request_id=p.rid, prompt_tokens=p.prompt)
                        reqs[p.rid] = r
                        w.sent.append(Sent(p.rid, p.cls, due, len(p.prompt),
                                           p.max_new_tokens))
                        w.prompts[p.rid] = p.prompt
                        eng.add_request(r)
                        w.lag.append(eng.now() - due)
                        i += 1
            if eng.has_work():
                slots = self._slots(reqs) if trace else None
                t0 = time.perf_counter()
                with ann("bench.tick"):
                    eng.tick()
                w.tick_s.append(time.perf_counter() - t0)
                if trace:
                    w.ticks.append(slots())
            else:
                nxt = w.open + plan[i].offset if i < len(plan) else end
                with ann("bench.idle"):
                    time.sleep(max(min(nxt, end) - eng.now(), 0.0))
        w.close = eng.now()
        gc.callbacks.remove(gc_timer)
        w.waiting_at_close = eng.sched.waiting()
        if tracing is not None:
            w.traced = (w.open, w.close)
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
        w.compiles = Compiles.count - compiles
        w.tokens = eng.tokens_out - w.tokens
        w.gaps = list(eng.decode_gaps[n_gaps:])
        w.padded = eng.padded_tokens - padded
        w.real = eng.real_tokens - real
        lens = {s.rid: s.prompt_len for s in w.sent}
        for t, rid in eng.dispatch_log[n_disp:]:
            if w.batches and w.batches[-1][0] == t:
                w.batches[-1][1].append(lens[rid])
            else:
                w.batches.append((t, [lens[rid]]))
        self._drain(w, reqs, n_disp)
        return w

    def _slots(self, reqs: dict):
        """Before a tick: note each active slot's cache position and tokens
        left.  The returned call, made after the tick, adds the slots the
        tick admitted (from ``dispatch_log``) and gives the tick's record
        for :func:`decode_positions`."""
        eng = self.eng
        t = eng.now()
        held = [(int(eng.slot_pos[s]), st.budget_left)
                for s, st in eng.slot_state.items()]
        n = len(eng.dispatch_log)

        def after():
            admitted = [(reqs[rid].prompt_len, reqs[rid].max_new_tokens - 1)
                        for _, rid in eng.dispatch_log[n:]]
            return t, held + [a for a in admitted if a[1] > 0]
        return after

    def _drain(self, w: Window, reqs: dict, n_disp: int) -> None:
        """Finish what the window sent, then read each request's times."""
        eng = self.eng
        limit = eng.now() + DRAIN_S
        while eng.has_work() and eng.now() < limit:
            eng.tick()
        first_dispatch = {}
        for t, rid in eng.dispatch_log[n_disp:]:
            first_dispatch.setdefault(rid, t)
        for s in w.sent:
            r = reqs[s.rid]
            s.dispatched = first_dispatch.get(s.rid)
            s.first_token = r.first_token_time
            s.finished = r.finish_time
            if r.finish_time is None or r.terminal is None or \
                    r.terminal.value != "finished":
                w.failed += 1
            else:
                w.served[s.rid] = list(eng.output_tokens[s.rid])

    def free(self) -> None:
        """Drop the engine and its device state."""
        self.eng = None
        gc.collect()


class GcTimer:
    """A ``gc.callbacks`` entry that notes how long each collection took."""

    def __init__(self, out: list):
        self.out, self.t0 = out, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            self.out.append(time.perf_counter() - self.t0)


def decode_positions(ticks: list, steps_per_tick: int, s_max: int):
    """Per decode step, the cache position each active slot's new token
    takes, from the ticks' records.  A slot decodes until its tokens run
    out or its cache is full (``ServingEngine._decode_tick``); a tick stops
    after ``steps_per_tick`` steps or once no slot is left."""
    out = []
    for t, slots in ticks:
        runs = [(p, min(left, s_max - 1 - p)) for p, left in slots]
        for j in range(min(steps_per_tick, max((m for _, m in runs),
                                               default=0))):
            out.append((t, [p + j for p, m in runs if j < m]))
    return out


def peak_bytes(chips: int) -> int:
    """The peak bytes in use on the fullest chip the cell uses."""
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
