"""CPU tests of the chip benchmark's harness (``perfbench/``).

The generator, the exact statistics, the trace reduction (on a trace
recorded on the chip), the operation and byte counts, the weights' layout,
and the plain reference against the engine at a tiny size.  Then whole runs
driven past the harness's look for a chip, once sound and once with the
timed path broken underneath, to see ``correct`` follow.  Last, the family
lookup: the dense family's numbers pinned to those it gave before it moved
into its module, and a family that is one module and nothing else.
"""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import check, driver, roofline, stats, tracefile, traffic, weights
from perfbench import run as bench
from perfbench.rundata import METRICS, RunData, reader
from perfbench.references import dense

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
TINY = json.loads((HERE / "tiny-dense.json").read_text())
TINY_MIX = json.loads((HERE / "tiny-mix.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXED = json.loads((ROOT / "perfbench/traffic/mixed.json").read_text())


def config(name: str) -> dict:
    return json.loads((ROOT / f"perfbench/configs/{name}.json").read_text())


# ---- traffic -------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 33 + 1])
def test_mixed_is_deterministic_per_seed(seed):
    a = traffic.generate(MIXED, 3.0, 45.0, seed, 151936)
    b = traffic.generate(MIXED, 3.0, 45.0, seed, 151936)
    assert [(p.cls, p.offset, p.max_new_tokens) for p in a] == \
        [(p.cls, p.offset, p.max_new_tokens) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


def test_mixed_keeps_class_shares_clips_and_work_across_seeds():
    plans = [traffic.generate(MIXED, 3.0, 45.0, s, 151936) for s in (1, 2)]
    for plan in plans:
        assert len(plan) == 135
        assert Counter(p.cls for p in plan) == {"short": 108, "long": 27}
        for p in plan:
            spec = next(c for c in MIXED["classes"] if c["name"] == p.cls)
            assert spec["prompt"]["min"] <= len(p.prompt) <= \
                spec["prompt"]["max"]
            assert spec["output"]["min"] <= p.max_new_tokens <= \
                spec["output"]["max"]
            assert p.prompt.min() >= 0 and p.prompt.max() < 151936
        offs = [p.offset for p in plan]
        assert offs == sorted(offs) and 0.0 == offs[0] and offs[-1] < 45.0
    # Same work at the same times: only the token ids follow the seed.
    arrivals = lambda plan: [(p.cls, p.offset, len(p.prompt),  # noqa: E731
                              p.max_new_tokens) for p in plan]
    assert arrivals(plans[0]) == arrivals(plans[1])
    assert not any(np.array_equal(p.prompt, q.prompt)
                   for p, q in zip(*plans))
    # The schedule seed draws the order of the same requests and gaps.
    other = traffic.generate(dict(MIXED, schedule_seed=1), 3.0, 45.0, 1,
                             151936)
    assert arrivals(other) != arrivals(plans[0])
    key = lambda plan: sorted((p.cls, len(p.prompt), p.max_new_tokens)  # noqa: E731
                              for p in plan)
    assert key(other) == key(plans[0])
    gaps = lambda plan: sorted(np.diff(  # noqa: E731
        [p.offset for p in plan] + [45.0]).round(9))
    assert gaps(other) == gaps(plans[0])


def test_history_stream_differs_from_window_stream():
    a = traffic.generate(MIXED, 16, 1.0, 5, 1000, stream=0)
    b = traffic.generate(MIXED, 16, 1.0, 5, 1000, stream=1)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


# ---- statistics ----------------------------------------------------------

@pytest.mark.parametrize("q,want", [(50, 5.0), (90, 9.0), (99, 10.0),
                                    (100, 10.0), (1, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([10, 3, 1, 2, 4, 5, 6, 7, 8, 9], q) == want


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None


def test_window_accounting_counts_waiting_requests_at_their_wait():
    close = 10.0
    sent = [stats.Sent(0, "short", 1.0, 16, 8, dispatched=1.5,
                       first_token=2.0, finished=3.0),
            stats.Sent(1, "long", 2.0, 300, 8, dispatched=9.0,
                       first_token=9.5, finished=12.0),
            # first token after the close: counts at its wait at the close
            stats.Sent(2, "short", 8.0, 16, 8, dispatched=10.5,
                       first_token=10.6, finished=11.0),
            # never dispatched in the window
            stats.Sent(3, "short", 9.0, 16, 8)]
    assert stats.ttfts(sent, close) == [1.0, 7.5, 2.0, 1.0]
    assert stats.ttfts(sent, close, "short") == [1.0, 2.0, 1.0]
    assert stats.queue_waits(sent, close, "short") == [0.5, 2.0, 1.0]
    w = driver.Window(open=0.0, close=close, sent=sent)
    run = RunData(TINY, w, 1.0, 0, "TPU v5 lite")
    assert reader("ttft_short_mean_s")(run) == pytest.approx(4.0 / 3)
    assert reader("ttft_mean_s")(run) == pytest.approx(11.5 / 4)
    assert reader("sched.queue_wait_short_p90_s")(run) == 2.0


# ---- trace reduction -----------------------------------------------------

def test_interval_arithmetic():
    evs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 45, 60)]
    assert tracefile.merged(evs, 0, 50) == [(0, 20), (30, 40), (45, 50)]
    assert tracefile.busy_ns(evs, 0, 50) == 35
    assert tracefile.gaps(evs, 0, 50) == [(20, 30), (40, 45)]
    assert tracefile.gaps(evs, -5, 70) == [(-5, 0), (20, 30), (40, 45),
                                           (60, 70)]
    assert tracefile.program_name("jit__decode_fn(17)") == "jit__decode_fn"
    assert tracefile.program_name("jit__prefill_fn.3") == "jit__prefill_fn"


def test_busy_between_matches_a_full_pass():
    evs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 45, 60)]
    busy = tracefile.Busy(evs, 0, 50)
    for a in range(-5, 56, 5):
        for b in range(a, 56, 5):
            assert busy.between(a, b) == tracefile.busy_ns(evs, max(a, 0),
                                                          min(b, 50))


@pytest.fixture(scope="module")
def chip_trace():
    path = HERE / "data" / "qwen3-4b.mixed.trace.json.gz"
    return tracefile.load(path)


def test_recorded_trace_reduces(chip_trace):
    t0, t1 = tracefile.window(chip_trace)
    assert t1 > t0
    busy = tracefile.busy_ns(chip_trace.ops[0], t0, t1)
    idle = sum(e - s for s, e in tracefile.gaps(chip_trace.ops[0], t0, t1))
    assert busy + idle == t1 - t0
    progs = tracefile.program_seconds(chip_trace.modules[0], t0, t1)
    assert "jit__decode_fn" in progs
    # Programs never overlap on one chip, so their time fits in busy time.
    assert sum(progs.values()) <= (t1 - t0) / 1e9
    busy_at = tracefile.Busy(chip_trace.ops[0], t0, t1)
    mid = (t0 + t1) // 2
    assert busy_at.between(t0, mid) == \
        tracefile.busy_ns(chip_trace.ops[0], t0, mid)
    by_span = tracefile.idle_by_host_span(chip_trace)
    assert abs(sum(by_span.values()) - idle / 1e9) < 1e-6
    assert set(by_span) <= {"bench.tick", "bench.ingest", "bench.idle",
                            "none"}


def test_decode_positions_follow_each_slot_until_it_runs_out():
    # Tick 1: a slot at position 10 with 2 tokens left, one at 1020 with
    # 9 left (its cache fills after 3 steps), one at 5 with 9 left.
    ticks = [(1.0, [(10, 2), (1020, 9), (5, 9)]), (2.0, [(9, 1)]),
             (3.0, [])]
    assert driver.decode_positions(ticks, 4, 1024) == [
        (1.0, [10, 1020, 5]), (1.0, [11, 1021, 6]), (1.0, [1022, 7]),
        (1.0, [8]), (2.0, [9])]


def test_a_tick_record_adds_what_the_tick_admitted():
    cell = driver.Cell(TINY, TINY_MIX, {"rate_rps": 5.0}, 3)

    class Eng:
        slot_pos = {0: 40}
        slot_state = {0: type("St", (), {"budget_left": 7})()}
        dispatch_log = [(0.5, 1)]

        def now(self):
            return 2.5

    cell.eng = Eng()
    reqs = {2: type("R", (), {"prompt_len": 30, "max_new_tokens": 12})(),
            3: type("R", (), {"prompt_len": 9, "max_new_tokens": 1})()}
    after = cell._slots(reqs)
    cell.eng.dispatch_log += [(2.6, 2), (2.6, 3)]
    assert after() == (2.5, [(40, 7), (30, 11)])


# ---- operations and bytes ------------------------------------------------

def test_counts_qwen3_4b_by_hand():
    c = config("qwen3-4b")
    # per layer: q 2560x4096 + k,v 2x2560x1024 + o 4096x2560 + mlp 3x2560x9728
    assert roofline.layer_matmul_params(c) == 100_925_440
    # 36 layers + the unembedding 2560 x 151936 (the tied table), 2 bytes
    assert roofline.weight_bytes(c) == 2 * (36 * 100_925_440 + 388_956_160)
    assert roofline.kv_bytes_per_token(c) == 36 * 2 * 8 * 128 * 2 == 147_456
    # one slot writing position 99: weights once, 100 positions of KV
    assert roofline.decode_bytes(c, [99]) == 8_044_544_000 + 100 * 147_456
    assert roofline.decode_flops(c, [99]) == \
        2 * 4_022_272_000 + 4 * 36 * 32 * 128 * 100
    # a 100-token prompt: matmuls, causal attention (5050 pairs), logits once
    assert roofline.prefill_flops(c, [100]) == \
        2 * 36 * 100_925_440 * 100 + 4 * 36 * 32 * 128 * 5050 \
        + 2 * 388_956_160
    peak = roofline.peaks("TPU v5 lite")
    # decode is bound by bytes: 8.06 GB at 819 GB/s
    assert roofline.bound_seconds(roofline.decode_flops(c, [99]),
                                  roofline.decode_bytes(c, [99]), peak) == \
        pytest.approx(8_059_289_600 / 819e9)


def test_counts_h2o_danube_by_hand():
    c = config("h2o-danube-1.8b")
    # per layer: q 2560x2560 + k,v 2x2560x640 + o 2560x2560 + mlp 3x2560x6912
    assert roofline.layer_matmul_params(c) == 69_468_160
    assert roofline.weight_bytes(c) == 2 * (24 * 69_468_160 + 81_920_000)
    assert roofline.kv_bytes_per_token(c) == 61_440
    assert roofline.decode_bytes(c, [0, 9]) == \
        3_498_311_680 + (1 + 10) * 61_440
    assert roofline.prefill_bytes(c, [16, 32]) == 3_498_311_680 + 48 * 61_440


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ---- configurations and manifest ----------------------------------------

@pytest.mark.parametrize("name", ["qwen3-4b", "h2o-danube-1.8b"])
def test_config_matches_the_registered_widths(name):
    from repro.configs import get_config
    c, reg = config(name), get_config(name)
    mine = driver.model_config(c)
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "head_dim", "qk_norm", "rope_theta"):
        assert getattr(mine, f) == getattr(reg, f), f
    assert weights.param_count(c) * 2 == c["bytes"]["params"]
    assert roofline.kv_bytes_per_token(c) == c["bytes"]["kv_per_token"]
    e = c["engine"]
    assert e["max_slots"] * e["s_max"] * c["bytes"]["kv_per_token"] == \
        c["bytes"]["kv_cache"]


def test_manifest_names_files_that_exist():
    for cfg in MANIFEST["configs"]:
        assert (ROOT / cfg["file"]).is_file()
    for w in MANIFEST["workloads"]:
        assert (ROOT / f"perfbench/cells/{w['name']}.json").is_file()
        assert (ROOT / f"perfbench/traffic/{w['traffic']}.json").is_file()
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert (METRICS / f"{m['name']}.py").is_file(), m["name"]
        assert callable(reader(m["name"]))


@pytest.mark.parametrize("name", ["qwen3-4b", "h2o-danube-1.8b"])
def test_every_reachable_prefill_shape_is_warmed(name):
    c = config(name)
    shapes = driver.prefill_shapes(c, MIXED)
    slots = c["engine"]["max_slots"]
    assert shapes == [(b, n) for b in (64, 128, 256, 512)
                      for n in range(1, slots + 1)]


def test_weights_have_the_program_layout():
    from repro.models import init_params
    mine = jax.eval_shape(lambda: weights.program_params(3, TINY))
    theirs = jax.eval_shape(lambda k: init_params(
        k, driver.model_config(TINY), jnp.bfloat16), jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_weights_follow_the_seed_and_the_reference_draws_them_alike():
    a = weights.program_params(2 ** 32 + 3, TINY)
    b = weights.program_params(2 ** 32 + 3, TINY)
    c = weights.program_params(3, TINY)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    one = dense.layer(weights.root_key(2 ** 32 + 3), TINY, 1, jnp.bfloat16)
    assert np.array_equal(one["wq"],
                          a["blocks"]["stack"]["slot_0"]["mixer"]["wq"][1])


def test_tied_weights_fold_the_programs_embedding_scale():
    cfg = dict(TINY, tie_word_embeddings=True, vocab_size=4096)
    key, scale = weights.root_key(9), 8.0          # sqrt(hidden_size 64)
    table = weights.embed(key, cfg, jnp.bfloat16)
    ref = weights.reference_embed(key, cfg, jnp.bfloat16)
    assert np.array_equal(ref, table.astype(jnp.float32) * scale)
    assert float(ref.std()) == pytest.approx(weights.EMBED_STD, rel=0.02)
    offset, head = weights.reference_readout(key, cfg, jnp.bfloat16)
    assert np.array_equal(head, ref.T)
    stored = weights.final_norm(key, cfg, jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose((1 + offset) * scale, 1 + stored, rtol=1e-6)
    assert float(offset.std()) == pytest.approx(weights.NORM_STD, rel=0.3)


# ---- reference against the engine, and whole runs ------------------------

def tiny_run(monkeypatch, seed=5, seconds=2.0, fault=None, cfg=TINY):
    """A whole run of the tiny cell on the CPU; ``fault`` breaks the timed
    path underneath before the engine is built."""
    if fault is not None:
        fault(monkeypatch)
    monkeypatch.setattr(bench, "compile_cache", lambda: None)
    monkeypatch.setattr(bench, "require_chips", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    params = {"rate_rps": 5.0, "history_requests": 16,
              "limits": {"logit_gap": 0.25}}
    w = {"name": "qwen3-4b.mixed", "chips": 1}
    return bench.run_cell(MANIFEST, w, cfg, TINY_MIX, params, seed, seconds,
                          trace=False)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_reference_agrees_with_the_engine_and_the_run_is_correct(
        monkeypatch, capsys, tied):
    res = tiny_run(monkeypatch, cfg=dict(TINY, tie_word_embeddings=tied))
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(res))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 10
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] < 0.1
    assert err.strip().splitlines()[-2].startswith("logit_gap")
    assert set(res["metrics"]) == {
        m["name"] for m in bench.metric_names(MANIFEST, "qwen3-4b.mixed", False)}
    assert "programs built inside the window: 0" in err


def _altered_token(monkeypatch):
    import repro.serving.engine as engine_mod
    sample = engine_mod.sample_tokens

    def off_by_one(logits, key, **kw):
        return (sample(logits, key, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "sample_tokens", off_by_one)


def _stale_cache(monkeypatch):
    from repro.serving.engine import ServingEngine
    step = ServingEngine._decode_fn

    def unchanged(self, params, tokens, caches, pos):
        logits, _ = step(self, params, tokens, caches, pos)
        return logits, caches

    monkeypatch.setattr(ServingEngine, "_decode_fn", unchanged)


@pytest.mark.parametrize("fault", [_altered_token, _stale_cache],
                         ids=["token_altered", "decode_state_unchanged"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    res = tiny_run(monkeypatch, fault=fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > 0.25


def test_lower_precision_control_is_not_correct():
    """The float8 twin of the reference, put in the program's place, fails
    the cell's limit (readings at full width on the chip are in PERF.md)."""
    cell = driver.Cell(TINY, TINY_MIX, {"rate_rps": 5.0,
                                        "history_requests": 16}, 11)
    cell.build()
    cell.warm()
    cell.history()
    win = cell.run_window(2.0)
    cell.free()
    out = check.compare(TINY, 11, win.prompts, win.served, 128, control=True)
    limit = json.loads((ROOT / "perfbench/cells/qwen3-4b.mixed.json")
                       .read_text())["limits"]["logit_gap"]
    assert out["logit_gap"] < limit < out["control_logit_gap"]


def test_no_chip_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench.require_chips(1)
    assert e.value.code == 3


# ---- families: the lookup, and the dense family pinned --------------------

# Read before the dense family's code moved out of driver.py, weights.py and
# roofline.py into perfbench/references/dense.py; the move changed no
# arithmetic, so every number stays to the bit.
POSITIONS = {"[0]": [0], "[99]": [99], "[0, 511, 1023]": [0, 511, 1023]}
LENS = {"[16]": [16], "[100, 512]": [100, 512]}
PINNED_COUNTS = {
    "qwen3-4b": {
        "weight_bytes": 8044544000, "kv_bytes_per_token": 147456,
        "param_count": 4022468096,
        "decode_flops [0]": 8045133824, "decode_bytes [0]": 8044691456,
        "decode_flops [99]": 8103526400, "decode_bytes [99]": 8059289600,
        "decode_flops [0, 511, 1023]": 25040191488,
        "decode_bytes [0, 511, 1023]": 8271183872,
        "prefill_flops [16]": 117124235264,
        "prefill_bytes [16]": 8046903296,
        "prefill_flops [100, 512]": 4529173430272,
        "prefill_bytes [100, 512]": 8134787072},
    "h2o-danube-1.8b": {
        "weight_bytes": 3498311680, "kv_bytes_per_token": 61440,
        "param_count": 1831201280,
        "decode_flops [0]": 3498557440, "decode_bytes [0]": 3498373120,
        "decode_flops [99]": 3522887680, "decode_bytes [99]": 3504455680,
        "decode_flops [0, 511, 1023]": 10872668160,
        "decode_bytes [0, 511, 1023]": 3592744960,
        "prefill_flops [16]": 53548810240,
        "prefill_bytes [16]": 3499294720,
        "prefill_flops [100, 512]": 2074540605440,
        "prefill_bytes [100, 512]": 3535912960},
}
PINNED_MODEL_CONFIG = {
    "qwen3-4b": {
        'name': 'qwen3-4b', 'family': 'dense', 'n_layers': 36, 'd_model': 2560,
        'n_heads': 32, 'n_kv_heads': 8, 'd_ff': 9728, 'vocab_size': 151936,
        'head_dim': 128, 'attn_kind': 'full', 'window': 0,
        'local_global_period': 0, 'qk_norm': True, 'rope_theta': 1000000.0,
        'causal': True, 'use_mla': False, 'kv_lora_rank': 0,
        'rope_head_dim': 64, 'v_head_dim': 128, 'n_experts': 0,
        'n_shared_experts': 0, 'moe_top_k': 2, 'moe_d_ff': 0,
        'capacity_factor': 1.25, 'first_dense_layers': 0, 'ssm_state': 0,
        'ssm_head_dim': 64, 'd_inner': 0, 'ssm_chunk': 256, 'conv_width': 4,
        'ssm_groups': 1, 'pattern': ('attn',), 'rnn_width': 2560,
        'input_mode': 'tokens', 'is_encoder_only': False,
        'tie_embeddings': True, 'subquadratic': False, 'max_seq_len': 1024,
        'norm_eps': 1e-06, 'logit_softcap': 0.0},
    "h2o-danube-1.8b": {
        'name': 'h2o-danube-1.8b', 'family': 'dense', 'n_layers': 24,
        'd_model': 2560, 'n_heads': 32, 'n_kv_heads': 8, 'd_ff': 6912,
        'vocab_size': 32000, 'head_dim': 80, 'attn_kind': 'swa',
        'window': 4096, 'local_global_period': 0, 'qk_norm': False,
        'rope_theta': 10000.0, 'causal': True, 'use_mla': False,
        'kv_lora_rank': 0, 'rope_head_dim': 64, 'v_head_dim': 80,
        'n_experts': 0, 'n_shared_experts': 0, 'moe_top_k': 2, 'moe_d_ff': 0,
        'capacity_factor': 1.25, 'first_dense_layers': 0, 'ssm_state': 0,
        'ssm_head_dim': 64, 'd_inner': 0, 'ssm_chunk': 256, 'conv_width': 4,
        'ssm_groups': 1, 'pattern': ('attn',), 'rnn_width': 2560,
        'input_mode': 'tokens', 'is_encoder_only': False,
        'tie_embeddings': False, 'subquadratic': False, 'max_seq_len': 1024,
        'norm_eps': 1e-05, 'logit_softcap': 0.0},
}
PINNED_TINY_DIGEST = {
    False: "218fbcff58ac760d26d5d977eead4cea038a12443340a0b2c638efa5ca20fbd4",
    True: "a5077d924b8a6952f3f04eb7398eea35b7b3b3c93cc394bda608a4c01df317b6"}
PINNED_READERS = {"kernel.decode_roofline": 62.334815852737776,
                  "kernel.prefill_roofline": 82.28281231559622,
                  "step.mfu": 6.578337786434969}
ROOFLINE_READERS = sorted(PINNED_READERS)


def counts(cfg: dict) -> dict:
    """The six counts and the parameters, keyed as in PINNED_COUNTS."""
    out = {"weight_bytes": roofline.weight_bytes(cfg),
           "kv_bytes_per_token": roofline.kv_bytes_per_token(cfg),
           "param_count": weights.param_count(cfg)}
    for k, p in POSITIONS.items():
        out[f"decode_flops {k}"] = roofline.decode_flops(cfg, p)
        out[f"decode_bytes {k}"] = roofline.decode_bytes(cfg, p)
    for k, n in LENS.items():
        out[f"prefill_flops {k}"] = roofline.prefill_flops(cfg, n)
        out[f"prefill_bytes {k}"] = roofline.prefill_bytes(cfg, n)
    return out


@pytest.mark.parametrize("name,count", [
    (n, k) for n in PINNED_COUNTS for k in PINNED_COUNTS[n]])
def test_dense_counts_are_pinned(name, count):
    got = counts(config(name))[count]
    assert type(got) is int and got == PINNED_COUNTS[name][count]


@pytest.mark.parametrize("name", sorted(PINNED_MODEL_CONFIG))
def test_dense_model_config_is_pinned(name):
    import dataclasses
    assert dataclasses.asdict(driver.model_config(config(name))) == \
        PINNED_MODEL_CONFIG[name]


def _digest(tree) -> str:
    import hashlib
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_tiny_dense_weights_are_pinned(tied):
    cfg = dict(TINY, tie_word_embeddings=tied)
    assert _digest(weights.program_params(2 ** 32 + 3, cfg)) == \
        PINNED_TINY_DIGEST[tied]


def recorded_run(cfg: dict, trace) -> RunData:
    """The recorded chip trace (device programs only) under a fixed,
    made-up record of the window: 12 ticks of 4 decode steps over 8 slots
    spread through the cache, and 4 prefill batches."""
    ticks = [(0.05 + 0.075 * k, [(96 * j + 4 * k, 4) for j in range(8)])
             for k in range(12)]
    batches = [(0.1, [16]), (0.3, [100, 512]), (0.6, [48, 48, 48]),
               (0.9, [300])]
    w = driver.Window(open=0.0, close=1.0, ticks=ticks, batches=batches,
                      traced=(0.0, 1.0))
    return RunData(cfg, w, 1.0, 0, "TPU v5 lite", trace)


@pytest.mark.parametrize("metric", ROOFLINE_READERS)
def test_roofline_readers_are_pinned_on_the_recorded_trace(metric,
                                                           chip_trace):
    run = recorded_run(config("qwen3-4b"), chip_trace)
    assert reader(metric)(run) == PINNED_READERS[metric]


def _twin_family(seen: list):
    """A family module made in the test: tiny-dense's program and reference
    at another draw of the weights (the seed plus one, on both sides), and
    twice dense's counts."""
    import dataclasses
    import types
    mod = types.ModuleType("perfbench.references.twin")

    def note(name, fn):
        def call(*a, **kw):
            seen.append(name)
            return fn(*a, **kw)
        setattr(mod, name, call)

    note("model_config", lambda c: dataclasses.replace(
        dense.model_config(c), name="twin"))
    note("program_params", lambda seed, c, dtype=jnp.bfloat16:
         dense.program_params(seed + 1, c, dtype))
    note("hidden", lambda seed, c, tokens, mode="f32":
         dense.hidden(seed + 1, c, tokens, mode))
    note("readout", lambda seed, c, h, targets, mode="f32":
         dense.readout(seed + 1, c, h, targets, mode))
    for name in ("param_count", "weight_bytes", "kv_bytes_per_token",
                 "decode_flops", "decode_bytes", "prefill_flops",
                 "prefill_bytes"):
        note(name, lambda *a, f=getattr(dense, name): 2 * f(*a))
    return mod


def test_a_family_is_its_module_alone(monkeypatch, chip_trace):
    """A module found only by the lookup drives the engine's build, the
    check and the roofline readers: no other harness file knows it."""
    import sys
    seen = []
    monkeypatch.setitem(sys.modules, "perfbench.references.twin",
                        _twin_family(seen))
    res = tiny_run(monkeypatch, cfg=dict(TINY, reference="twin"))
    # Sound only if the engine and the reference drew the same (twin's)
    # weights: dense's own draw at this seed is another model.
    assert res["correct"] and res["checks"]["logit_gap"]["value"] < 0.1
    assert seen[:2] == ["model_config", "program_params"] or \
        seen[:2] == ["program_params", "model_config"]
    assert {"hidden", "readout"} <= set(seen[2:])
    for name in ("qwen3-4b", "h2o-danube-1.8b"):
        twin = dict(config(name), reference="twin")
        assert counts(twin) == {k: 2 * v for k, v in
                                PINNED_COUNTS[name].items()}
    run = recorded_run(dict(config("qwen3-4b"), reference="twin"),
                       chip_trace)
    for m in ROOFLINE_READERS:
        assert reader(m)(run) == 2 * PINNED_READERS[m], m


def test_an_unknown_family_fails_at_once(monkeypatch):
    built = []
    monkeypatch.setattr(driver.Cell, "build",
                        lambda self, *a: built.append(self))
    with pytest.raises(ModuleNotFoundError, match="no_such_family"):
        tiny_run(monkeypatch, cfg=dict(TINY, reference="no_such_family"))
    assert built == []
