"""Serving-engine integration: real JAX execution, EWSJF vs FCFS, paging."""

import numpy as np
import jax
import pytest

pytestmark = pytest.mark.slow  # real JAX serving-engine execution

from repro.configs import get_smoke_config
from repro.core import (EWSJFConfig, EWSJFScheduler, FCFSScheduler, Request)
from repro.models import init_params
from repro.serving import EngineConfig, ServingEngine


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("qwen3-4b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def mixed_requests(n=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        short = rng.random() < 0.7
        ln = int(rng.integers(8, 24)) if short else int(rng.integers(100, 200))
        out.append(Request(prompt_len=ln, arrival_time=0.0,
                           max_new_tokens=int(rng.integers(2, 5))))
    return out


def test_engine_serves_all(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, FCFSScheduler(),
                        EngineConfig(max_slots=4, s_max=256,
                                     kv_pool_tokens=2048,
                                     buckets=(32, 64, 128, 256)))
    fin = eng.run(mixed_requests(12), max_steps=2000)
    assert len(fin) == 12
    for r in fin:
        assert r.generated >= 1
        assert r.ttft is not None and r.ttft >= 0


def test_engine_ewsjf_reduces_padding(model):
    cfg, params = model
    stats = {}
    for name, sched in [("fcfs", FCFSScheduler()),
                        ("ewsjf", EWSJFScheduler(EWSJFConfig(
                            min_history=8, reopt_interval=0.2)))]:
        eng = ServingEngine(cfg, params, sched,
                            EngineConfig(max_slots=4, s_max=256,
                                         kv_pool_tokens=4096,
                                         buckets=(32, 64, 128, 256)))
        eng.run(mixed_requests(32, seed=1), max_steps=4000)
        stats[name] = eng.stats()
    assert stats["ewsjf"]["padding_waste"] < stats["fcfs"]["padding_waste"] - 0.1


@pytest.mark.parametrize("chunked", [False, True])
def test_engine_device_commits_state_and_keeps_tokens(model, chunked):
    """An engine given a device keeps params, caches and PRNG key committed
    there through serving, and samples the tokens the default placement
    samples."""
    cfg, params = model
    dev = jax.devices()[-1]
    outs = {}
    for device in (None, dev):
        reqs = mixed_requests(8, seed=3)
        for i, r in enumerate(reqs):
            r.prompt_tokens = ((np.arange(r.prompt_len) * 5 + i)
                               % cfg.vocab_size).astype(np.int32)
        eng = ServingEngine(cfg, params, FCFSScheduler(),
                            EngineConfig(max_slots=4, s_max=256,
                                         kv_pool_tokens=4096,
                                         buckets=(32, 64, 128, 256),
                                         chunk_prefill_tokens=(
                                             64 if chunked else None),
                                         enable_prefix_cache=chunked),
                            device=device)
        built = jax.tree.leaves((eng.params, eng.caches, eng._key))
        eng.run(reqs, max_steps=2000)
        outs[device] = [eng.output_tokens[r.request_id] for r in reqs]
        served = jax.tree.leaves((eng.params, eng.caches, eng._key))
        if device is not None:
            for x in built + served:
                assert x.committed and x.devices() == {dev}
    assert outs[None] == outs[dev]


def test_engine_decode_donates_caches_in_place(model):
    """The decode step donates the engine's caches: its input leaves are
    deleted, every step counts as in place (the obs counter agrees), and
    the greedy tokens equal a loop over ``decode_step`` without donation
    from the same state."""
    import jax.numpy as jnp
    from repro.models import decode_step
    from repro.obs import Observability
    cfg, params = model
    steps = 5
    obs = Observability.enabled()
    eng = ServingEngine(cfg, params, FCFSScheduler(),
                        EngineConfig(max_slots=4, s_max=64,
                                     kv_pool_tokens=1024, buckets=(32,),
                                     decode_steps_per_tick=steps), obs=obs)
    for i, n in enumerate((5, 17, 30)):
        eng.add_request(Request(
            prompt_len=n, arrival_time=0.0, max_new_tokens=steps + 2,
            prompt_tokens=((np.arange(n) * 3 + i) % cfg.vocab_size)
            .astype(np.int32)))
    eng._admit(eng.now())
    assert len(eng.slot_state) == 3
    held = jax.tree.leaves(eng.caches)
    caches = jax.tree.map(jnp.copy, eng.caches)
    toks, pos = eng.last_tokens.copy(), eng.slot_pos.copy()
    eng._decode_tick()

    assert all(x.is_deleted() for x in held)
    assert (eng.decode_in_place, eng.decode_copied) == (steps, 0)
    assert eng.stats()["decode_in_place"] == steps
    count = lambda donated: obs.metrics.counter_value(  # noqa: E731
        "engine_decode_in_place_total", {"donated": donated})
    assert (count("true"), count("false")) == (steps, 0)

    step = jax.jit(lambda p, t, c, q: decode_step(
        p, t, c, q, cfg, eng.moe_ctx, policy=eng.policy))
    want = []
    for _ in range(steps):
        logits, caches = step(eng.params, toks, caches, pos)
        toks = np.asarray(jnp.argmax(logits[:, 0], axis=-1),
                          np.int32)[:, None]
        want.append(toks[:, 0])
        pos = pos + 1
    for slot, st in eng.slot_state.items():
        assert eng.output_tokens[st.req.request_id][1:] == \
            [int(w[slot]) for w in want]


def test_engine_outputs_independent_of_scheduler(model):
    """Greedy decoding: each request's tokens must not depend on the
    admission order (isolation of slots + per-row positions)."""
    cfg, params = model
    outs = {}
    for name, sched in [("fcfs", FCFSScheduler()),
                        ("ewsjf", EWSJFScheduler(EWSJFConfig(min_history=8)))]:
        reqs = mixed_requests(10, seed=2)
        for i, r in enumerate(reqs):
            r.prompt_tokens = (np.arange(r.prompt_len) * 7 + i) % cfg.vocab_size
            r.prompt_tokens = r.prompt_tokens.astype(np.int32)
        eng = ServingEngine(cfg, params, sched,
                            EngineConfig(max_slots=4, s_max=256,
                                         kv_pool_tokens=4096,
                                         buckets=(32, 64, 128, 256)))
        fin = eng.run(reqs, max_steps=2000)
        outs[name] = {r.prompt_len: r.generated for r in fin}
    assert outs["fcfs"] == outs["ewsjf"]


def test_engine_admission_hook_sheds(model):
    """Replica-facing admission: once the prefill-rate estimator is primed,
    an over-budget sheddable request is refused at ingress."""
    from repro.cluster import AdmissionController, SLOClass
    cfg, params = model
    classes = (SLOClass("interactive", ttft_target=1e9, deadline=None,
                        priority=2, sheddable=False),
               SLOClass("standard", ttft_target=5.0, deadline=None),
               SLOClass("batch", ttft_target=1e-12, deadline=None))
    adm = AdmissionController(
        classes=classes,
        classify=lambda r: "batch" if r.prompt_len > 64 else "interactive")
    eng = ServingEngine(cfg, params, FCFSScheduler(),
                        EngineConfig(max_slots=4, s_max=256,
                                     kv_pool_tokens=4096,
                                     buckets=(32, 64, 128, 256)),
                        admission=adm)
    # prime the rate estimator: same prompt length twice over full slots so
    # the second batch reuses the compiled shape (fresh-JIT timings are
    # excluded from the rate — they'd count compilation as serving time)
    prime = [Request(prompt_len=16, arrival_time=0.0, max_new_tokens=2)
             for _ in range(8)]
    eng.run(prime, max_steps=2000)
    assert eng._prefill_tok_rate > 0
    # now a long sheddable request with backlogged queue gets refused
    eng.sched.submit(Request(prompt_len=200, arrival_time=0.0,
                             max_new_tokens=2), now=eng.now())
    long_req = Request(prompt_len=200, arrival_time=0.0, max_new_tokens=2)
    eng.add_request(long_req)
    assert long_req in eng.shed
    assert adm.stats()["shed"]["batch"] == 1
    # non-sheddable interactive traffic is still admitted
    short_req = Request(prompt_len=16, arrival_time=0.0, max_new_tokens=2)
    eng.add_request(short_req)
    assert short_req not in eng.shed
    assert eng.stats()["shed"] == 1


def test_engine_preemption_requeues(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, FCFSScheduler(),
                        EngineConfig(max_slots=4, s_max=256,
                                     kv_pool_tokens=256,   # tiny pool
                                     buckets=(32, 64, 128)))
    reqs = [Request(prompt_len=60, arrival_time=0.0, max_new_tokens=8)
            for _ in range(4)]
    fin = eng.run(reqs, max_steps=2000)
    assert len(fin) == 4                      # everything still completes


def test_engine_preempt_and_retry_pump_no_leak():
    """Admission retry pump × KV-pressure preemption × prefix cache: a
    deferred request re-admitted by ``_pump_retries`` while other slots are
    being preempted must not leak BlockPool blocks or double-charge its
    cached prefix."""
    from repro.cluster import AdmissionConfig, AdmissionController, SLOClass

    cfg = get_smoke_config("llama2-13b")     # dense => prefix cache allowed
    params = init_params(jax.random.PRNGKey(0), cfg)
    classes = (SLOClass("interactive", ttft_target=1e9, deadline=None,
                        priority=2, sheddable=False),
               SLOClass("batch", ttft_target=1e-12, deadline=None))
    adm = AdmissionController(
        classes=classes,
        classify=lambda r: "batch" if r.request_id == 777 else "interactive",
        config=AdmissionConfig(retry_capacity=8, retry_backoff=0.01,
                               retry_ttl=1e6))
    eng = ServingEngine(cfg, params, FCFSScheduler(),
                        EngineConfig(max_slots=4, s_max=256,
                                     kv_pool_tokens=256,   # pressure
                                     enable_prefix_cache=True,
                                     prefix_cache_blocks=8),
                        admission=adm)
    # prime the prefill-rate estimator (reused chunk width => rate recorded)
    prime = [Request(request_id=1000 + i, prompt_len=16, arrival_time=0.0,
                     max_new_tokens=2) for i in range(8)]
    eng.run(prime, max_steps=2000)
    assert eng._prefill_tok_rate > 0
    # backlog the queue, then offer a sheddable request: est delay exceeds
    # its (absurd) TTFT target, so it parks in the retry queue.  All
    # backlog prompts share a 64-token prefix so it stays hot in the
    # (capacity-capped) radix until the deferred request re-admits.
    pfx = np.random.default_rng(42).integers(
        0, cfg.vocab_size, size=(64,)).astype(np.int32)
    def with_prefix(rid):
        sfx = np.random.default_rng(rid).integers(
            0, cfg.vocab_size, size=(36,)).astype(np.int32)
        return np.concatenate([pfx, sfx])
    backlog = [Request(request_id=2000 + i, prompt_len=100, arrival_time=0.0,
                       max_new_tokens=24, prompt_tokens=with_prefix(2000 + i))
               for i in range(8)]
    for r in backlog:
        eng.add_request(r)
    deferred = Request(request_id=777, prompt_len=100, arrival_time=0.0,
                       max_new_tokens=4, prompt_tokens=with_prefix(777))
    eng.add_request(deferred)
    assert deferred not in eng.shed
    assert adm.retry_pending() == 1
    # drive the loop manually: retries re-offered as the backlog drains.
    # Once decode is underway, force one preemption (deterministic — the
    # 256-token pool alone may be absorbed by radix eviction relief): the
    # victim must requeue, re-attach its prefix, and finish cleanly.
    forced = False
    for i in range(3000):
        now = eng.now()
        eng._pump_retries(now)
        eng._admit(now)
        eng._prefill_chunk_tick(now)
        if not forced and i >= 5 and eng.slot_state:
            eng._preempt_slot(max(eng.slot_state))
            forced = True
        eng._decode_tick()
        if len(eng.finished) >= 8 + 8 + 1:
            break
    assert deferred in eng.finished
    assert eng.readmitted == 1
    assert adm.stats()["readmitted"]["batch"] == 1
    # its prefix (shared with backlog[0]) was attached from cache, stamped
    # at block granularity and strictly below prompt_len
    assert 0 < deferred.cached_len < deferred.prompt_len
    assert forced and eng.preemptions >= 1
    assert len(eng.finished) == 8 + 8 + 1      # prime + backlog + deferred
    # no leaked sequence allocations: only radix tenancy remains; no
    # stranded in-flight pins
    assert {k: v for k, v in eng.pool.allocs.items()
            if not isinstance(k, tuple)} == {}
    eng.radix.check_invariants()
    assert all(n.pins == 0 for n in eng.radix._nodes.values())
