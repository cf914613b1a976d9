"""Continuous-batching serving engine (real JAX execution on CPU/TPU).

The execution model is the TPU adaptation of vLLM (DESIGN.md §3):

  * **slot-based decode** — one compiled ``decode_fn`` over a fixed
    (max_slots, 1) batch; active sequences own slots, per-slot cache
    positions (vectorized cache_pos) let sequences of different lengths
    share the step;
  * **bucketed prefill** — one compiled ``prefill_fn`` per token-bucket
    edge; EWSJF's homogeneous queues keep the padding waste of each
    prefill batch low (measured by benchmarks/bench_padding.py);
  * **paged accounting** — BlockPool mirrors vLLM admission/preemption
    semantics (prompt must fit in free pages; decode growth can preempt
    LIFO, in recompute mode);
  * the **admission policy is pluggable** — any core.scheduler.BaseScheduler
    (FCFS / SJF / EWSJF) drives admission; the engine is the paper's
    "execution-level" layer, the scheduler the paper's contribution.

Right-padded prompts are safe for attention/ring caches (pads are causally
masked and progressively overwritten); recurrent state (ssm/rglru) would be
contaminated, so those families run with exact-length prefill
(``pad_prompts=False``).

Two opt-in execution features converge the engine with the cluster planes
(docs/ENGINE.md):

* **chunked prefill** (``chunk_prefill_tokens``) — prompts prefill in
  budgeted chunks through a per-slot ``chunk_step``, interleaved with
  ``_decode_tick`` so a long prompt no longer stalls every decoding
  sequence for its whole prefill (decode TBT stays bounded by the chunk
  budget, the same per-tick token budget the DES ``BatchBuilder`` charges);
* **engine-side radix prefix reuse** (``enable_prefix_cache``) — a
  ``kvplane.RadixPrefixIndex`` runs against the engine's own ``BlockPool``;
  real prefills match their chained block hashes, copy the cached prefix KV
  into the slot, and prefill only the uncached suffix (at its true offset,
  via the same chunked path).  Prefix paths are pinned in-flight and
  unpinned on finish/preempt; evicted nodes drop their host-side KV through
  the index's ``on_evict`` hook.

Both features off ⇒ the legacy bucketed-batch path runs bit-identically.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.batch_builder import BatchBudget
from ..core.cost_model import CostModel
from ..core.scheduler import BaseScheduler
from ..core.types import Request, RequestState, TerminalState

if TYPE_CHECKING:   # runtime import is deferred: kvplane.radix imports
    from ..kvplane.radix import RadixPrefixIndex   # serving.kv_cache, and a
                                                   # module-level import here
                                                   # would close the cycle
from ..models.common import DtypePolicy
from ..models.model import (_embed_inputs, _unembed, chunk_step, decode_step,
                            init_decode_caches, pad_prefill_caches)
from ..models.common import rms_norm
from ..models.transformer import MoECtx, stack_forward, supports_chunked_decode
from .kv_cache import BlockPool, SlotAllocator
from .sampler import sample_tokens


@dataclass
class EngineConfig:
    """Sizing + feature knobs of one engine (docs/ENGINE.md for the full
    calibration table and the mapping onto the DES ``EngineParams``)."""

    max_slots: int = 8
    s_max: int = 512
    block_size: int = 16
    kv_pool_tokens: int = 4096
    buckets: tuple = (32, 64, 128, 256, 512)
    max_prefill_tokens: int = 1024
    temperature: float = 0.0
    time_scale: float = 0.0          # 0 => all arrivals at t=0
    decode_steps_per_tick: int = 4
    pad_prompts: Optional[bool] = None   # None => auto by family
    moe_impl: str = "dropping"
    seed: int = 0
    # Real-engine convergence features (both default-off: the legacy
    # bucketed-batch prefill path then runs bit-identically).
    chunk_prefill_tokens: Optional[int] = None  # per-tick chunk budget; None=off
    enable_prefix_cache: bool = False           # engine-side radix KV reuse
    prefix_cache_blocks: Optional[int] = None   # radix pool-share cap (None=all)
    # Fleet identity: the pid lane this engine's trace events land on (and
    # the key heartbeats carry).  Default 0 matches the single-engine trace
    # layout that predates multi-engine observability.
    engine_id: int = 0


@dataclass
class _SlotState:
    req: Request
    seq_id: int
    budget_left: int
    pin_node: object = None         # pinned radix path (prefix-cache mode)
    cap_tokens: int = 0             # KV token capacity allocated (chunked mode)


@dataclass
class _PrefillState:
    """A slot mid-chunked-prefill: admitted, holding pool blocks and its
    pinned prefix path, cursor at ``pos`` prompt tokens resident."""

    req: Request
    seq_id: int
    pos: int                        # prompt tokens already in the slot cache
    pin_node: object = None
    cap_tokens: int = 0
    t_dispatch: float = 0.0


class ServingEngine:
    """Continuous-batching executor over a real JAX model (module docstring
    for the execution model).  Construct with a model config + params, a
    ``core.scheduler`` policy, and an ``EngineConfig``; drive with ``run``
    (batch) or ``add_request`` + the internal ticks (streaming).  Optional
    collaborators mirror the cluster planes: ``admission`` (SLO ingress),
    ``policy_store`` (strategic sync), ``obs`` (observability).

    ``device`` commits the engine's params, caches, PRNG key and per-step
    inputs to one device, so several engines in one process each drive
    their own chip; ``None`` leaves placement to JAX's default device."""

    def __init__(self, cfg: ModelConfig, params, scheduler: BaseScheduler,
                 ecfg: EngineConfig | None = None,
                 policy: DtypePolicy | None = None,
                 admission=None, policy_store=None,
                 replica_key: Optional[int] = None,
                 obs=None, cost_model: Optional[CostModel] = None,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.device = device
        self.params = jax.device_put(params, device)
        self.sched = scheduler
        self.e = ecfg or EngineConfig()
        self.policy = policy or DtypePolicy(jnp.float32, jnp.float32,
                                            jnp.float32)
        if self.e.pad_prompts is None:
            self.e.pad_prompts = cfg.family not in ("ssm", "hybrid")
        self.moe_ctx = MoECtx(impl=self.e.moe_impl)
        self.pool = BlockPool(self.e.kv_pool_tokens // self.e.block_size,
                              self.e.block_size)
        self.slots = SlotAllocator(self.e.max_slots)
        # Chunked-prefill / prefix-reuse mode: every admission goes through
        # the per-slot chunk path (suffix prefill at an offset needs it).
        self._chunked = (bool(self.e.chunk_prefill_tokens)
                         or self.e.enable_prefix_cache)
        if self._chunked and not supports_chunked_decode(cfg):
            raise ValueError(
                f"chunked prefill / prefix cache unsupported for family "
                f"{cfg.family!r} (ring/recurrent/encoder-only stacks)")
        self._chunk_budget = (self.e.chunk_prefill_tokens
                              or self.e.max_prefill_tokens)
        self.radix: Optional[RadixPrefixIndex] = None
        self._node_kv: dict[int, dict] = {}   # radix node_id -> host KV block
        if self.e.enable_prefix_cache:
            from ..kvplane.radix import RadixPrefixIndex
            self.radix = RadixPrefixIndex(
                self.pool, self.e.block_size,
                capacity_blocks=self.e.prefix_cache_blocks)
            self.radix.on_evict = self._on_radix_evict
        self._prefilling: dict[int, _PrefillState] = {}  # admission order
        self._chunk_jits: dict = {}
        self.chunks_run = 0
        self.chunk_tokens = 0
        self.prefix_saved_tokens = 0
        self.interleaved_ticks = 0   # decode ticks run while a prefill was up
        with jax.default_device(device):
            caches = init_decode_caches(cfg, self.e.max_slots, self.e.s_max,
                                        dtype=self.policy.compute)
            key = jax.random.PRNGKey(self.e.seed)
        self.caches = jax.device_put(caches, device)
        self._key = jax.device_put(key, device)
        self.slot_pos = np.zeros(self.e.max_slots, dtype=np.int32)
        self.slot_state: dict[int, _SlotState] = {}
        self.last_tokens = np.zeros((self.e.max_slots, 1), dtype=np.int32)
        # Replay/telemetry instrumentation (pure recording — never read by
        # scheduling): dispatch order for the DES-equivalence harness, and
        # wall-clock inter-token gaps (the chunked-prefill TBT-bound bench).
        self.dispatch_log: list[tuple] = []          # (now, request_id)
        self.decode_gaps: list[float] = []
        self._slot_last_tok = np.full(self.e.max_slots, -1.0)
        self.output_tokens: dict[int, list[int]] = {}  # rid -> sampled ids
        # Replica-facing admission hook (cluster.AdmissionController or any
        # object with .admit(req, now, est_delay) -> decision.admitted).
        self.admission = admission
        # Observability plane (obs.Observability or None) — same null-safe
        # contract as the cluster simulator: every emission is guarded, so
        # obs=None costs one attribute check per site.
        self.obs = obs
        # Cost-calibration plane: the analytic roofline whose predictions
        # the attached CostCalibrator (obs.calib) scores against measured
        # step walls.  Auto-created when the obs bundle carries a
        # calibrator so ``Observability.enabled(calibration=True)`` needs
        # no extra wiring; without a calibrator the engine stays free of
        # any cost-model coupling.
        if cost_model is None and obs is not None and \
                getattr(obs, "calib", None) is not None:
            cost_model = CostModel()
        self.cost = cost_model
        if obs is not None and admission is not None:
            admission.obs = obs
            if hasattr(admission, "_classify"):
                obs.classify = admission._classify
        # Fleet strategic plane (cluster.PolicyStore): engines sharing one
        # store publish their scheduler's strategic observations and adopt
        # the merged global policy — same publish→merge→broadcast loop as
        # the cluster simulator, keyed by ``replica_key`` (store-issued
        # unique key when not given, so co-located engines never collide).
        self.policy_store = policy_store
        if replica_key is None and policy_store is not None:
            replica_key = policy_store.issue_party_key()
        self.replica_key = replica_key
        self.shed: list[Request] = []
        self.readmitted = 0
        # Fleet lifecycle flags (cluster.engine_fleet): an engine that
        # failed is never ticked again; a draining one finishes in-flight
        # slots but receives no new dispatches.
        self.alive = True
        self.draining = False
        self._prefill_tok_rate = 0.0     # EWMA tokens/s, for delay estimates
        self.finished: list[Request] = []
        self.tokens_out = 0              # every sampled token (heartbeats)
        self.preemptions = 0
        self._decode_compiled = False    # first decode tick includes JIT
        self.prefill_batches = 0
        self.padded_tokens = 0
        self.real_tokens = 0
        self._decode_jit = jax.jit(self._decode_fn)
        self._prefill_jits: dict = {}
        self._t0 = time.monotonic()

    # ---- compiled steps --------------------------------------------------

    def _put(self, x):
        """Host input -> device array on the engine's device.  Always a
        copy: the engine rewrites its host buffers in place after dispatch."""
        return jax.device_put(x, self.device, may_alias=False)

    def _decode_fn(self, params, tokens, caches, pos):
        logits, new_caches = decode_step(params, tokens, caches, pos,
                                         self.cfg, self.moe_ctx,
                                         policy=self.policy)
        return logits, new_caches

    def _prefill_fn(self, params, tokens, true_lens):
        """Bucketed prefill returning per-row logits at true_lens-1 and the
        (padded) caches."""
        batch = {"tokens": tokens} if self.cfg.input_mode == "tokens" else \
            {"embeddings": jnp.take(params["embed"], tokens, axis=0)
             .astype(self.policy.compute)}
        x = _embed_inputs(params, batch, self.cfg, self.policy.compute)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        h, caches, _ = stack_forward(params["blocks"], x, self.cfg, positions,
                                     self.moe_ctx, want_cache=True)
        h = rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        h_last = h[jnp.arange(B), true_lens - 1]
        w = _unembed(params, self.cfg)
        logits = (h_last[:, None, :].astype(w.dtype) @ w).astype(jnp.float32)
        return logits, caches

    def _get_prefill_jit(self, bucket: int, n: int):
        key = (bucket, n)
        if key not in self._prefill_jits:
            self._prefill_jits[key] = jax.jit(self._prefill_fn)
        return self._prefill_jits[key]

    def _chunk_fn(self, params, tokens, slot_caches, pos0):
        """One prefill chunk for a single slot (B=1): C tokens written at
        absolute positions pos0..pos0+C-1, logits at the chunk's last
        position.  ``pos0`` is traced, so one compilation per chunk width
        serves every offset (chunk cursors and radix-prefix offsets)."""
        return chunk_step(params, tokens, slot_caches, pos0, self.cfg,
                          self.moe_ctx, policy=self.policy)

    def _get_chunk_jit(self, width: int):
        if width not in self._chunk_jits:
            self._chunk_jits[width] = jax.jit(self._chunk_fn)
        return self._chunk_jits[width]

    # ---- slot-cache plumbing ---------------------------------------------

    def _map_into_caches(self, src, flat, stacked) -> None:
        """Merge a source cache pytree into the engine caches leafwise:
        ``flat(dst, src)`` on head/tail entries (batch axis 0), ``stacked``
        on the scan group (period dim leads, batch axis 1)."""
        new = dict(self.caches)
        new["head"] = [jax.tree.map(flat, d, s)
                       for d, s in zip(self.caches["head"], src["head"])]
        if "stack" in self.caches:
            new["stack"] = jax.tree.map(stacked, self.caches["stack"],
                                        src["stack"])
        new["tail"] = [jax.tree.map(flat, d, s)
                       for d, s in zip(self.caches["tail"], src["tail"])]
        self.caches = new

    def _slice_slot(self, slot: int):
        """View of one slot's caches as a B=1 pytree (chunk_step input)."""
        def flat(t):
            return t[slot:slot + 1]

        def stacked(t):
            return t[:, slot:slot + 1]

        out = {"head": [jax.tree.map(flat, c) for c in self.caches["head"]],
               "tail": [jax.tree.map(flat, c) for c in self.caches["tail"]]}
        if "stack" in self.caches:
            out["stack"] = jax.tree.map(stacked, self.caches["stack"])
        return out

    def _extract_block(self, slot: int, block_idx: int) -> dict:
        """Host-side (numpy) copy of one KV block's rows from a slot —
        what the radix node stores so later requests can re-attach it."""
        lo = block_idx * self.e.block_size
        hi = lo + self.e.block_size

        def flat(t):
            return np.asarray(t[slot, lo:hi])

        def stacked(t):
            return np.asarray(t[:, slot, lo:hi])

        out = {"head": [jax.tree.map(flat, c) for c in self.caches["head"]],
               "tail": [jax.tree.map(flat, c) for c in self.caches["tail"]]}
        if "stack" in self.caches:
            out["stack"] = jax.tree.map(stacked, self.caches["stack"])
        return out

    def _write_block(self, slot: int, block_idx: int, block_kv: dict) -> None:
        """Copy one cached KV block (host numpy rows) into a slot's span —
        the radix attach: cached prefix blocks land without recompute."""
        lo = block_idx * self.e.block_size

        def flat_at(dst, src):
            return dst.at[slot, lo:lo + src.shape[0]].set(
                self._put(src).astype(dst.dtype))

        def stacked_at(dst, src):
            return dst.at[:, slot, lo:lo + src.shape[1]].set(
                self._put(src).astype(dst.dtype))

        new = dict(self.caches)
        new["head"] = [jax.tree.map(flat_at, d, s)
                       for d, s in zip(self.caches["head"], block_kv["head"])]
        if "stack" in self.caches:
            new["stack"] = jax.tree.map(stacked_at, self.caches["stack"],
                                        block_kv["stack"])
        new["tail"] = [jax.tree.map(flat_at, d, s)
                       for d, s in zip(self.caches["tail"], block_kv["tail"])]
        self.caches = new

    # ---- time ------------------------------------------------------------

    def now(self) -> float:
        """Engine wall clock: monotonic seconds since construction, scaled
        by ``time_scale`` when set (so trace timestamps can be replayed
        faster than real time)."""
        if self.e.time_scale <= 0:
            return time.monotonic() - self._t0
        return (time.monotonic() - self._t0) * self.e.time_scale

    # ---- main loop ---------------------------------------------------------

    def _est_queue_delay(self, now: float) -> float:
        """Best-effort TTFT-delay estimate from the current backlog and the
        measured prefill token rate (0 until the first batch completes)."""
        if self._prefill_tok_rate <= 0:
            return 0.0
        waiting = self.sched.snapshot(now).waiting_tokens
        return waiting / self._prefill_tok_rate

    def _stamp_prefix(self, req: Request) -> None:
        """Chunked/prefix mode: materialize prompt tokens up front (the
        chunk cursor needs them before dispatch), hash them, and stamp the
        queue-side ``cached_len`` *estimate* from a read-only radix probe —
        the same submit-time stamp the cluster router applies, so EWSJF
        queues and scores this engine's requests on effective length.  The
        authoritative resolution happens at dispatch (``_attach_prefix``)."""
        if req.prompt_tokens is None:
            rng = np.random.default_rng(req.request_id)
            req.prompt_tokens = rng.integers(
                0, self.cfg.vocab_size, size=(req.prompt_len,)).astype(np.int32)
        else:
            req.prompt_tokens = np.asarray(req.prompt_tokens, dtype=np.int32)
        if self.radix is None:
            return
        if req.prompt_hashes is None:
            from ..kvplane.radix import chain_block_hashes
            req.prompt_hashes = chain_block_hashes(req.prompt_tokens.tolist(),
                                                   self.e.block_size)
        blocks = self.radix.match(req.prompt_hashes, touch=False).blocks
        req.cached_len = min(blocks * self.e.block_size,
                             int(req.prompt_len) - 1)
        if self.obs is not None:
            self.obs.event("probe", self.now(), request_id=req.request_id,
                           replica_id=self.e.engine_id,
                           data={"blocks": blocks,
                                 "cached_est": int(req.cached_len)})
            self.obs.inc("radix_probe_total",
                         {"hit": "true" if blocks else "false"})

    def add_request(self, req: Request) -> None:
        """Ingress one request: stamp its prefix estimate (chunked/prefix
        mode), pass it through the admission controller when present
        (shed / defer / admit), and submit admitted requests to the
        scheduler queue."""
        now = self.now()
        if self._chunked:
            self._stamp_prefix(req)
        if self.obs is not None:
            self.obs.event("arrival", now, request_id=req.request_id,
                           replica_id=self.e.engine_id)
            self.obs.inc("requests_arrived_total",
                         {"slo_class": self.obs.classify(req)})
        if self.admission is not None:
            dec = self.admission.admit(req, now, self._est_queue_delay(now))
            if not dec.admitted:
                # "defer" parks the request in the controller's bounded
                # re-admission queue (admission v2); it is re-offered by
                # _pump_retries until its deadline passes.
                if dec.reason != "defer":
                    req.state = RequestState.FAILED
                    req.finish_time = now
                    if req.terminal is None:    # duck-typed admission hooks
                        req.terminal = TerminalState.SHED
                    self.shed.append(req)
                return
        self.sched.submit(req, now=now)
        if self.obs is not None:
            self.obs.event("enqueue", now, request_id=req.request_id,
                           replica_id=self.e.engine_id)

    def _pump_retries(self, now: float) -> None:
        if self.admission is None or not self.admission.retry_pending():
            return
        due, expired = self.admission.due_retries(now)
        self.shed.extend(expired)
        for req in due:
            dec = self.admission.admit(req, now, self._est_queue_delay(now),
                                       retry=True)
            if dec.admitted:
                self.readmitted += 1
                self.sched.submit(req, now=now)
            elif dec.reason != "defer":
                req.state = RequestState.FAILED
                req.finish_time = now
                if req.terminal is None:
                    req.terminal = TerminalState.SHED
                self.shed.append(req)

    def run(self, requests: list[Request], max_steps: int = 100_000) -> list[Request]:
        """Serve every request to completion; returns finished requests."""
        pending = sorted(requests, key=lambda r: r.arrival_time)
        pi = 0
        n_total = len(pending)
        for step in range(max_steps):
            now = self.now()
            while pi < n_total and pending[pi].arrival_time <= now:
                self.add_request(pending[pi])
                pi += 1
            if len(self.finished) + len(self.shed) >= n_total:
                break
            self._pump_retries(now)
            if hasattr(self.sched, "maybe_reoptimize"):
                self.sched.maybe_reoptimize(now)
            self._maybe_sync_policy(now)
            self._admit(now)
            self._prefill_chunk_tick(now)
            if (not self.slot_state and not self._prefilling
                    and self.sched.waiting() == 0 and pi < n_total):
                continue
            self._decode_tick()
        return self.finished

    def tick(self) -> None:
        """One engine iteration — exactly the body of ``run``'s loop, for
        external drivers (``cluster.engine_fleet.EngineFleet``) that own
        arrival ingestion and interleave many engines on one clock.  A dead
        engine never ticks; a draining one runs its in-flight slots dry but
        admits nothing new (its queue was drained back to the router)."""
        if not self.alive:
            return
        now = self.now()
        self._pump_retries(now)
        if hasattr(self.sched, "maybe_reoptimize"):
            self.sched.maybe_reoptimize(now)
        self._maybe_sync_policy(now)
        if not self.draining:
            self._admit(now)
        self._prefill_chunk_tick(now)
        self._decode_tick()
        if self.draining and not self.has_work():
            self.alive = False

    def has_work(self) -> bool:
        """Anything decoding, mid-prefill, or queued."""
        return bool(self.slot_state or self._prefilling
                    or self.sched.waiting())

    # ---- fleet lifecycle (failure / drain) --------------------------------

    def fail(self) -> list[Request]:
        """Hard failure: every in-flight and queued request is orphaned and
        returned for fleet-level re-routing (recompute recovery — the KV,
        the radix cache, and the host block store die with the engine).
        Mirrors ``ReplicaModel.fail`` so the cluster control plane treats
        both backends identically."""
        self.alive = False
        orphans = [st.req for st in self._prefilling.values()]
        orphans += [st.req for st in self.slot_state.values()]
        orphans += self.sched.drain()
        self._prefilling.clear()
        self.slot_state.clear()
        self.slots = SlotAllocator(self.e.max_slots)
        self._slot_last_tok[:] = -1.0
        self.pool = BlockPool(self.e.kv_pool_tokens // self.e.block_size,
                              self.e.block_size)
        self._node_kv.clear()
        if self.radix is not None:
            from ..kvplane.radix import RadixPrefixIndex
            self.radix = RadixPrefixIndex(
                self.pool, self.e.block_size,
                capacity_blocks=self.e.prefix_cache_blocks)
            self.radix.on_evict = self._on_radix_evict
        for req in orphans:
            req.state = RequestState.PREEMPTED
            req.preemptions += 1
            req.generated = 0
            req.first_token_time = None
            req.cached_len = 0          # its cached prefix is gone too
            req.prefix_fetch = None
            self.output_tokens.pop(req.request_id, None)
        return orphans

    def start_drain(self) -> list[Request]:
        """Graceful drain: stop admitting, let slots finish (``tick`` flips
        ``alive`` off once the last one does), give queued work back for
        re-routing.  Pins unwind naturally as slots finish."""
        self.draining = True
        queued = self.sched.drain()
        for req in queued:
            req.state = RequestState.WAITING
            req.cached_len = 0          # destination re-probes its own radix
            req.prefix_fetch = None
        if not self.has_work():
            self.alive = False
        return queued

    # ---- host-KV handoff (fleet prefix plane) -----------------------------

    def export_prefix_blocks(self, hashes, want: int) -> list[dict]:
        """Source side of a fleet host-KV handoff: the host (numpy) KV
        blocks of the longest locally cached prefix of ``hashes``, root
        first, capped at ``want`` blocks and truncated at the first block
        whose KV content is not host-resident (so the shipped set is always
        a closed prefix an importer can attach)."""
        if self.radix is None or not hashes or want <= 0:
            return []
        m = self.radix.match(hashes[:want], self.now())
        path: list = []
        node = m.node
        while node is not None and node.depth > 0:
            path.append(node)
            node = node.parent
        path.reverse()
        out: list[dict] = []
        for nd in path:
            kv = self._node_kv.get(nd.node_id)
            if kv is None:
                break
            out.append(kv)
        return out

    def import_prefix_blocks(self, hashes, blocks_kv: list[dict]) -> int:
        """Destination side of a fleet host-KV handoff: insert the chain
        into the local radix (allocating real pool blocks — the pool stays
        the single accountant) and attach the shipped host KV to the newly
        resident nodes.  Pool pressure may stop the insert early; only
        blocks that actually landed count.  Returns blocks landed."""
        if self.radix is None or not blocks_kv:
            return 0
        now = self.now()
        node, _ = self.radix.insert(hashes[:len(blocks_kv)], now)
        path: list = []
        while node is not None and node.depth > 0:
            path.append(node)
            node = node.parent
        path.reverse()
        landed = 0
        for i, nd in enumerate(path):
            if i >= len(blocks_kv):
                break
            if nd.node_id not in self._node_kv:
                self._node_kv[nd.node_id] = blocks_kv[i]
            landed += 1
        return landed

    def _maybe_sync_policy(self, now: float) -> None:
        """Strategic-plane round against a shared ``cluster.PolicyStore``
        (``store.sync``): publish on this engine's own per-party cadence,
        merge on the store-wide cadence, adopt whenever a newer epoch
        exists — engines sharing one store each keep their own clock, so
        none is starved by another's merges.  Never blocks serving."""
        if self.policy_store is not None:
            self.policy_store.sync(self.sched, self.replica_key, now)

    # ---- admission + prefill ----------------------------------------------

    def _admit(self, now: float) -> None:
        free = len(self.slots.free)
        if free == 0 or self.sched.waiting() == 0:
            return
        budget = BatchBudget(max_requests=free,
                             max_tokens=self.e.max_prefill_tokens,
                             kv_blocks_free=self.pool.free_blocks,
                             block_size=self.e.block_size)
        plan = self.sched.tick(now, budget)
        if not plan.requests:
            return
        if self._chunked:
            self._admit_chunked(plan.requests, now)
            return
        reqs = [r for r in plan.requests if r.prompt_len <= self.e.s_max - 1]
        if not reqs:
            return
        n = len(reqs)
        max_len = max(r.prompt_len for r in reqs)
        bucket = next((b for b in self.e.buckets if b >= max_len),
                      self.e.buckets[-1])
        if not self.e.pad_prompts:
            bucket = max_len
        tokens = np.zeros((n, bucket), dtype=np.int32)
        lens = np.zeros((n,), dtype=np.int32)
        rng = np.random.default_rng(sum(r.request_id for r in reqs))
        for i, r in enumerate(reqs):
            if r.prompt_tokens is None:
                r.prompt_tokens = rng.integers(
                    0, self.cfg.vocab_size, size=(r.prompt_len,)
                ).astype(np.int32)
            tokens[i, : r.prompt_len] = r.prompt_tokens
            lens[i] = r.prompt_len
        self.prefill_batches += 1
        self.padded_tokens += bucket * n
        self.real_tokens += int(lens.sum())
        fresh_jit = (bucket, n) not in self._prefill_jits
        fn = self._get_prefill_jit(bucket, n)
        t_pf0 = self.now()
        logits, caches = fn(self.params, self._put(tokens), self._put(lens))
        caches = pad_prefill_caches(caches, self.cfg, self.e.s_max)
        self._key, sk = jax.random.split(self._key)
        first = np.asarray(sample_tokens(logits, sk,
                                         temperature=self.e.temperature))
        t_first = self.now()
        # observed prefill rate feeds the admission delay estimator; skip
        # first-call-per-shape timings — they include JIT compilation and
        # would poison the estimate into spurious shedding
        if not fresh_jit:
            rate = int(lens.sum()) / max(t_first - t_pf0, 1e-6)
            self._prefill_tok_rate = (rate if self._prefill_tok_rate <= 0 else
                                      0.7 * self._prefill_tok_rate + 0.3 * rate)
        if self.obs is not None:
            self.obs.event("prefill", t_pf0, dur=max(t_first - t_pf0, 0.0),
                           replica_id=self.e.engine_id,
                           data={"batch": n, "bucket": bucket,
                                 "tokens": int(lens.sum())})
            self.obs.inc("engine_compile_cache_total",
                         {"kind": "prefill",
                          "hit": "false" if fresh_jit else "true"})
            # Calibration sample: batch prefill is prefill-shaped work.
            # First-call-per-shape walls include XLA compilation and would
            # poison the fit the same way they would the rate EWMA — skip.
            if self.cost is not None and not fresh_jit:
                self.obs.calibrate(
                    "prefill_chunk",
                    self.cost.prefill_step_time(int(lens.sum()),
                                                float(lens.mean())),
                    max(t_first - t_pf0, 1e-9))
        for i, r in enumerate(reqs):
            self.pool.allocate(r.request_id, r.prompt_len)
            slot = self.slots.acquire(r.request_id)
            assert slot is not None
            self._write_slot(slot, caches, i)
            r.state = RequestState.RUNNING_DECODE
            r.first_token_time = t_first
            self.dispatch_log.append((t_pf0, r.request_id))
            self._slot_last_tok[slot] = t_first
            if self.obs is not None:
                wait = max(0.0, t_pf0 - r.arrival_time)
                self.obs.event("dispatch", t_pf0, request_id=r.request_id,
                               replica_id=self.e.engine_id,
                               data={"wait": round(wait, 6)})
                self.obs.observe("sched_dispatch_wait_seconds", wait,
                                 {"slo_class": self.obs.classify(r)})
                self.obs.event("first_token", t_first,
                               request_id=r.request_id,
                               replica_id=self.e.engine_id)
            r.generated = 1
            self.tokens_out += 1
            self.output_tokens[r.request_id] = [int(first[i, 0])]
            self.slot_pos[slot] = r.prompt_len
            self.last_tokens[slot, 0] = first[i, 0]
            self.slot_state[slot] = _SlotState(
                req=r, seq_id=r.request_id,
                budget_left=r.max_new_tokens - 1)
            if r.max_new_tokens <= 1:
                self._finish_slot(slot)

    def _write_slot(self, slot: int, prefill_caches, row: int) -> None:
        """Copy row ``row`` of a prefill cache pytree into the decode slot.
        Walks the {head, stack, tail} structure: stacked entries carry a
        leading period dim (batch axis 1), flat entries batch at axis 0."""
        def flat(dst, src):
            return dst.at[slot].set(src[row].astype(dst.dtype))

        def stacked(dst, src):
            return dst.at[:, slot].set(src[:, row].astype(dst.dtype))

        self._map_into_caches(prefill_caches, flat, stacked)

    # ---- chunked admission + prefill (convergence mode) -------------------

    def _on_radix_evict(self, node_id: int) -> None:
        """Radix eviction hook: drop the node's host-side KV block and
        record the eviction (capacity-pressure telemetry)."""
        self._node_kv.pop(node_id, None)
        if self.obs is not None:
            self.obs.event("evict", self.now(),
                           replica_id=self.e.engine_id,
                           data={"node": node_id})
            self.obs.inc("radix_evict_total")

    def _attach_prefix(self, r: Request, slot: int, now: float
                       ) -> tuple[int, int, object]:
        """Authoritative prefix resolution for one dispatched request —
        the engine-side mirror of the cluster replica's ``_prefix_attach``:
        match the radix, copy every matched block whose KV content is
        host-resident into the slot caches, then insert + pin the request's
        *full* prompt path (blocks computed this pass are about to exist;
        their content lands at prefill completion).  Returns
        ``(cached_tokens, resident_blocks, pin_node)``."""
        if self.radix is None or not r.prompt_hashes:
            r.cached_len = 0
            return 0, 0, None
        bs = self.e.block_size
        hashes = r.prompt_hashes
        m = self.radix.match(hashes, now)
        path: list = []
        node = m.node
        while node is not None and node.depth > 0:
            path.append(node)
            node = node.parent
        path.reverse()
        # Usable = contiguous matched blocks with host KV content, capped so
        # at least one suffix token remains to produce the first logit.
        max_blocks = (int(r.prompt_len) - 1) // bs
        usable = 0
        for nd in path[:max_blocks]:
            if nd.node_id not in self._node_kv:
                break
            usable += 1
        full_blocks = int(r.prompt_len) // bs
        pin_node, _ = self.radix.insert(hashes[:full_blocks], now)
        self.radix.pin(pin_node)
        resident = pin_node.depth if pin_node is not None else 0
        t_a0 = self.now() if self.obs is not None else 0.0
        for i in range(usable):
            self._write_block(slot, i, self._node_kv[path[i].node_id])
        cached_tokens = usable * bs
        r.cached_len = cached_tokens
        self.prefix_saved_tokens += cached_tokens
        if self.obs is not None:
            self.obs.inc("radix_insert_total")
            if usable:
                t_a1 = self.now()
                copied = sum(a.nbytes for a in jax.tree.leaves(
                    self._node_kv[path[0].node_id])) * usable
                self.obs.event("attach", t_a0, request_id=r.request_id,
                               replica_id=self.e.engine_id,
                               dur=max(t_a1 - t_a0, 0.0),
                               data={"slot": slot, "blocks": usable,
                                     "tokens": cached_tokens,
                                     "bytes": int(copied)})
                self.obs.observe("radix_attach_copy_bytes", float(copied))
                if self.cost is not None:
                    self.obs.calibrate(
                        "attach_copy",
                        self.cost.attach_copy_time(cached_tokens),
                        max(t_a1 - t_a0, 1e-9))
        return cached_tokens, resident, pin_node

    def _admit_chunked(self, reqs: list, now: float) -> None:
        """Admit dispatched requests into slots as chunk-prefill jobs: take
        a slot, resolve + attach the cached prefix, allocate the private
        (uncached) KV up front, and park the request in ``_prefilling`` —
        ``_prefill_chunk_tick`` then advances cursors under the chunk
        budget, interleaved with decode."""
        bs = self.e.block_size
        for r in reqs:
            if r.prompt_len > self.e.s_max - 1:
                continue                 # same oversize filter as legacy
            slot = self.slots.acquire(r.request_id)
            assert slot is not None      # budget.max_requests == free slots
            r.state = RequestState.RUNNING_PREFILL
            # Park the slot's decode cursor at the scratch position: the
            # global decode step runs over *all* slot rows, and its cache
            # write for this row must not land inside the prompt span being
            # chunk-prefilled.  s_max-1 is causally masked for every live
            # sequence until its own final step overwrites it.
            self.slot_pos[slot] = self.e.s_max - 1
            self.last_tokens[slot, 0] = 0
            cached, resident, pin_node = self._attach_prefix(r, slot, now)
            # Private allocation = prompt KV minus radix-resident blocks
            # (replica accounting: unchecked — admission was guarded on the
            # *estimate*; transient overdraw is reclaimed by decode-time
            # preemption).
            private = max(int(r.prompt_len) - resident * bs, 0)
            self.pool.allocate_unchecked(r.request_id, private)
            cap = resident * bs + self.pool.blocks_for(private) * bs
            self._prefilling[slot] = _PrefillState(
                req=r, seq_id=r.request_id, pos=cached,
                pin_node=pin_node, cap_tokens=cap, t_dispatch=now)
            self.dispatch_log.append((now, r.request_id))
            if self.obs is not None:
                wait = max(0.0, now - r.arrival_time)
                self.obs.event("dispatch", now, request_id=r.request_id,
                               replica_id=self.e.engine_id,
                               data={"wait": round(wait, 6),
                                     "cached_tokens": cached})
                self.obs.event("park", now, request_id=r.request_id,
                               replica_id=self.e.engine_id,
                               data={"slot": slot,
                                     "cap_tokens": cap})
                self.obs.observe("sched_dispatch_wait_seconds", wait,
                                 {"slo_class": self.obs.classify(r)})

    def _prefill_chunk_tick(self, now: float) -> None:
        """Advance every in-flight chunked prefill under the per-tick token
        budget (admission order — FIFO across slots), promoting completed
        prompts to decode.  One tick spends at most ``chunk_prefill_tokens``
        (or ``max_prefill_tokens`` in pure prefix-reuse mode) prefill
        tokens, so decoding sequences wait at most one chunk per tick —
        this is the TBT bound the chunked-prefill bench measures."""
        if not self._prefilling:
            return
        left = self._chunk_budget
        completed: list[tuple[int, object]] = []
        for slot in list(self._prefilling):
            if left <= 0:
                break
            st = self._prefilling[slot]
            r = st.req
            pos0 = st.pos
            width = min(int(r.prompt_len) - st.pos, left)
            left -= width
            toks = np.asarray(r.prompt_tokens[st.pos:st.pos + width],
                              dtype=np.int32)[None]
            fresh_jit = width not in self._chunk_jits
            fn = self._get_chunk_jit(width)
            t0 = self.now()
            logits, new_sl = fn(self.params, self._put(toks),
                                self._slice_slot(slot),
                                self._put(np.int32(st.pos)))
            self._write_slot(slot, new_sl, 0)
            st.pos += width
            t1 = self.now()
            self.chunks_run += 1
            self.chunk_tokens += width
            self.real_tokens += width
            self.padded_tokens += width      # chunk path pads nothing
            if not fresh_jit:
                rate = width / max(t1 - t0, 1e-6)
                self._prefill_tok_rate = (
                    rate if self._prefill_tok_rate <= 0 else
                    0.7 * self._prefill_tok_rate + 0.3 * rate)
            if self.obs is not None:
                # A chunk re-running a preempted request's prompt is the
                # DES's "recompute" stage; first-pass chunks are "chunk".
                # Both group under "prefill" via trace.SPAN_STAGES.
                kind = "recompute" if r.preemptions > 0 else "chunk"
                self.obs.event(kind, t0, request_id=r.request_id,
                               replica_id=self.e.engine_id,
                               dur=max(t1 - t0, 0.0),
                               data={"slot": slot, "batch": 1,
                                     "suffix_tokens": width,
                                     "cached_tokens": int(r.cached_len),
                                     "chunk": width, "pos": pos0})
                self.obs.observe("engine_chunk_width_tokens", float(width))
                self.obs.inc("engine_compile_cache_total",
                             {"kind": "chunk",
                              "hit": "false" if fresh_jit else "true"})
                # Calibration sample: roofline prediction for prefilling a
                # prompt to pos0+width with pos0 tokens already resident —
                # exactly this chunk's suffix work.  Fresh-JIT walls
                # include compilation and are skipped.
                if self.cost is not None and not fresh_jit:
                    self.obs.calibrate(
                        "prefill_chunk",
                        self.cost.prefill_cost(pos0 + width, cached=pos0),
                        max(t1 - t0, 1e-9))
            if st.pos >= int(r.prompt_len):
                completed.append((slot, logits))
        for slot, logits in completed:
            self._promote_slot(slot, logits)

    def _promote_slot(self, slot: int, logits) -> None:
        """Chunked prefill finished: publish computed prefix blocks to the
        radix host store, sample the first token, move the slot to decode."""
        st = self._prefilling.pop(slot)
        r = st.req
        if self.radix is not None and st.pin_node is not None:
            path: list = []
            node = st.pin_node
            while node is not None and node.depth > 0:
                path.append(node)
                node = node.parent
            path.reverse()
            for i, nd in enumerate(path):
                if nd.node_id not in self._node_kv:
                    self._node_kv[nd.node_id] = self._extract_block(slot, i)
        self._key, sk = jax.random.split(self._key)
        first = np.asarray(sample_tokens(logits, sk,
                                         temperature=self.e.temperature))
        t = self.now()
        r.state = RequestState.RUNNING_DECODE
        r.first_token_time = t
        r.generated = 1
        if self.obs is not None:
            self.obs.event("promote", t, request_id=r.request_id,
                           replica_id=self.e.engine_id,
                           data={"slot": slot,
                                 "prompt_len": int(r.prompt_len)})
            self.obs.event("first_token", t, request_id=r.request_id,
                           replica_id=self.e.engine_id)
        self.tokens_out += 1
        self.output_tokens[r.request_id] = [int(first[0, 0])]
        self.slot_pos[slot] = int(r.prompt_len)
        self.last_tokens[slot, 0] = first[0, 0]
        self._slot_last_tok[slot] = t
        self.slot_state[slot] = _SlotState(
            req=r, seq_id=st.seq_id, budget_left=r.max_new_tokens - 1,
            pin_node=st.pin_node, cap_tokens=st.cap_tokens)
        if r.max_new_tokens <= 1:
            self._finish_slot(slot)

    # ---- decode -------------------------------------------------------------

    def _grow_chunked(self, slot: int, st: _SlotState) -> None:
        """Per-slot KV growth in chunked/prefix mode: capacity is tracked in
        ``cap_tokens`` (radix-resident + private blocks); one private block
        is appended when the next token would exceed it.  Under pressure the
        radix sheds a cold cached block first (running sequences outrank the
        prefix cache), then LIFO recompute preemption applies as in legacy."""
        total = int(self.slot_pos[slot]) + 1
        if total <= st.cap_tokens:
            return
        if self.pool.free_blocks < 1 and self.radix is not None:
            self.radix.evict(1)
        if self.pool.free_blocks >= 1 or len(self.slot_state) <= 1:
            self.pool.allocate_unchecked(st.seq_id, self.e.block_size)
            st.cap_tokens += self.e.block_size
        else:
            self._preempt_slot(slot)

    def _decode_tick(self) -> None:
        if not self.slot_state:
            return
        if self._prefilling:
            self.interleaved_ticks += 1
        t_tick0 = self.now()
        steps = 0
        # Tick-start batch composition, for the decode calibration sample
        # (the batch can shrink mid-tick as slots finish; the prediction
        # uses the composition the tick started with).
        batch0 = len(self.slot_state)
        kv0 = int(sum(int(self.slot_pos[s]) for s in self.slot_state))
        for _ in range(self.e.decode_steps_per_tick):
            if not self.slot_state:
                break
            # paged growth accounting (+ LIFO recompute preemption)
            for slot in sorted(self.slot_state, reverse=True):
                st = self.slot_state[slot]
                if self._chunked:
                    self._grow_chunked(slot, st)
                elif not self.pool.grow(st.seq_id,
                                        int(self.slot_pos[slot]) + 1):
                    if len(self.slot_state) > 1:
                        self._preempt_slot(slot)
                    # else: single sequence — let it run (pool undersized)
            toks = self._put(self.last_tokens)
            pos = self._put(self.slot_pos)
            logits, self.caches = self._decode_jit(self.params, toks,
                                                   self.caches, pos)
            self._key, sk = jax.random.split(self._key)
            nxt = np.asarray(sample_tokens(logits, sk,
                                           temperature=self.e.temperature))
            t = self.now()
            steps += 1
            done = []
            for slot, st in self.slot_state.items():
                self.slot_pos[slot] += 1
                self.last_tokens[slot, 0] = nxt[slot, 0]
                self.tokens_out += 1
                self.output_tokens.setdefault(
                    st.req.request_id, []).append(int(nxt[slot, 0]))
                st.req.generated += 1
                st.budget_left -= 1
                if self._slot_last_tok[slot] >= 0:
                    self.decode_gaps.append(t - self._slot_last_tok[slot])
                self._slot_last_tok[slot] = t
                if st.budget_left <= 0 or self.slot_pos[slot] >= self.e.s_max - 1:
                    done.append(slot)
            for slot in done:
                self._finish_slot(slot)
        if self.obs is not None and steps:
            t_end = self.now()
            self.obs.event("decode", t_tick0, dur=max(t_end - t_tick0, 0.0),
                           replica_id=self.e.engine_id,
                           data={"batch": batch0, "steps": steps})
            self.obs.gauge("kv_occupancy", v=self.pool.utilization)
            self.obs.gauge("engine_slots_active",
                           v=float(len(self.slot_state)))
            self.obs.inc("engine_compile_cache_total",
                         {"kind": "decode",
                          "hit": "true" if self._decode_compiled
                          else "false"})
            # Per-step calibration sample against the tick-start batch.
            # The first tick's wall includes decode_fn compilation — skip
            # it, like every other fresh-JIT timing in this file.
            if (self.cost is not None and self._decode_compiled
                    and batch0 > 0):
                self.obs.calibrate(
                    "decode_step",
                    self.cost.decode_step_time(batch0, kv0),
                    max((t_end - t_tick0) / steps, 1e-9))
        if steps:
            self._decode_compiled = True

    def _preempt_slot(self, slot: int, cause: str = "kv_pressure") -> None:
        st = self.slot_state.pop(slot)
        self.pool.free(st.seq_id)
        if self.radix is not None and st.pin_node is not None:
            self.radix.unpin(st.pin_node)
        self.slots.release(slot)
        self._slot_last_tok[slot] = -1.0
        req = st.req
        req.state = RequestState.PREEMPTED
        req.preemptions += 1
        req.generated = 0
        req.first_token_time = None
        self.output_tokens.pop(req.request_id, None)   # recompute restarts
        self.preemptions += 1
        self.sched.submit(req, now=self.now())
        if self.obs is not None:
            self.obs.event("preempt", self.now(),
                           request_id=req.request_id,
                           replica_id=self.e.engine_id,
                           data={"slot": slot, "cause": cause})
            self.obs.inc("preemptions_total", {"kind": cause})

    def _finish_slot(self, slot: int) -> None:
        st = self.slot_state.pop(slot, None)
        req = st.req if st else None
        if req is None:
            return
        self.pool.free(st.seq_id)
        if self.radix is not None and st.pin_node is not None:
            self.radix.unpin(st.pin_node)
        self.slots.release(slot)
        self._slot_last_tok[slot] = -1.0
        req.state = RequestState.FINISHED
        req.finish_time = self.now()
        req.terminal = TerminalState.FINISHED
        self.finished.append(req)
        self.sched.on_finish(req, req.finish_time)
        if self.obs is not None:
            self.obs.finish(req, req.finish_time,
                            replica_id=self.e.engine_id)

    # ---- stats ---------------------------------------------------------------

    def slo_report(self, classify=None) -> dict:
        """Per-class TTFT/TBT/E2E percentiles for this engine's finished
        requests, through the one shared code path
        (:func:`repro.obs.slo.slo_or_fallback`): the live registry when an
        obs bundle is wired, an identical recomputation from
        ``self.finished`` otherwise — the same contract as
        ``ClusterSimResult.slo_report``, so engine- and DES-backed benches
        never mix percentile implementations."""
        from ..obs.slo import slo_or_fallback
        metrics = self.obs.metrics if self.obs is not None else None
        return slo_or_fallback(metrics, self.finished, classify)

    def heartbeat(self) -> dict:
        """Liveness + load beacon for fleet health monitoring
        (``cluster.health.HealthMonitor.observe_engine_heartbeat``): engine
        identity, clock, KV/slot occupancy, backlog, and progress counters.
        When an obs bundle is wired the beacon reuses its metrics snapshot
        so the health plane and the metrics plane can never disagree."""
        hb = {
            "engine_id": self.e.engine_id,
            "t": self.now(),
            "kv_occupancy": self.pool.utilization,
            "slots_active": len(self.slot_state),
            "prefilling": len(self._prefilling),
            "waiting": self.sched.waiting(),
            "finished": len(self.finished),
            "tokens_out": self.tokens_out,
        }
        if self.obs is not None and self.obs.metrics is not None:
            hb["metrics"] = self.obs.metrics.snapshot()
        return hb

    def stats(self) -> dict:
        """Run summary: throughput, terminal accounting, padding waste,
        chunked-prefill / prefix-reuse counters, radix stats, and the
        decode inter-token-gap (TBT) percentiles."""
        elapsed = self.now()
        toks = sum(r.generated for r in self.finished)
        # unified terminal accounting (Request.terminal stamps)
        terminal: dict[str, int] = {}
        for r in self.finished + self.shed:
            if r.terminal is not None:
                terminal[r.terminal.value] = terminal.get(
                    r.terminal.value, 0) + 1
        return {
            "finished": len(self.finished),
            "shed": len(self.shed),
            "terminal": terminal,
            "slo": self.slo_report(),
            "readmitted": self.readmitted,
            "admission": (self.admission.stats()
                          if self.admission is not None else {}),
            "elapsed_s": elapsed,
            "tok_per_s": toks / max(elapsed, 1e-9),
            "req_per_s": len(self.finished) / max(elapsed, 1e-9),
            "preemptions": self.preemptions,
            "prefill_batches": self.prefill_batches,
            "padding_waste": (1.0 - self.real_tokens
                              / max(self.padded_tokens, 1)),
            "chunks": self.chunks_run,
            "chunk_tokens": self.chunk_tokens,
            "interleaved_ticks": self.interleaved_ticks,
            "prefix_saved_tokens": self.prefix_saved_tokens,
            "radix": (self.radix.stats() if self.radix is not None else {}),
            "decode_tbt_p95": (float(np.percentile(self.decode_gaps, 95))
                               if self.decode_gaps else 0.0),
            "decode_tbt_max": (float(max(self.decode_gaps))
                               if self.decode_gaps else 0.0),
        }
