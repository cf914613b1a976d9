"""CPU tests of the program's profiler spans and of their reader
(``perfbench/spans.py``): a tiny engine traced by ``jax.profiler``, each
reading on a synthetic trace with known intervals, and the idle split on
the trace recorded on the chip."""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import spans, tracefile

HERE = Path(__file__).resolve().parent
ALL = (0, 2 ** 62)


# ---- a tiny engine under the profiler -----------------------------------

def _requests(cfg, n, seed):
    from repro.core import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        pl = 24 + 8 * (i % 3)
        out.append(Request(request_id=i, arrival_time=0.0, prompt_len=pl,
                           max_new_tokens=5,
                           prompt_tokens=rng.integers(
                               0, cfg.vocab_size, size=(pl,)).astype(np.int32)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two engines, bucketed under EWSJF and chunked under FCFS, ticked to
    completion under one profiler session; returns (spans, decode steps
    the engines' obs rings counted, engines)."""
    from repro.configs import get_smoke_config
    from repro.core import EWSJFConfig, EWSJFScheduler, FCFSScheduler
    from repro.models import init_params
    from repro.obs import Observability
    from repro.serving import EngineConfig, ServingEngine
    cfg = get_smoke_config("llama2-13b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    engines = [
        ServingEngine(cfg, params, EWSJFScheduler(EWSJFConfig(
            min_history=4, trial_interval=0.0)),
            EngineConfig(max_slots=2, s_max=64, buckets=(32, 64)),
            obs=Observability.enabled()),
        ServingEngine(cfg, params, FCFSScheduler(),
                      EngineConfig(max_slots=2, s_max=64,
                                   chunk_prefill_tokens=16, engine_id=1),
                      obs=Observability.enabled())]
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        for k, eng in enumerate(engines):
            for r in _requests(cfg, 6, k):
                eng.add_request(r)
            while eng.has_work():
                eng.tick()
    steps = sum(e[5]["steps"] for eng in engines
                for e in eng.obs.trace.events if e[1] == "decode")
    return spans.load(sorted(d.rglob("*.xplane.pb"))[-1]), steps, engines


def test_every_span_is_recorded_with_its_args(traced):
    sp, _, _ = traced
    names = {n for n, *_ in sp}
    assert names == {"engine.tick", "sched.tick", "sched.repartition",
                     "sched.meta_trial", "engine.prefill",
                     "engine.write_slot", "engine.chunk",
                     "engine.decode_step", "engine.decode_dispatch",
                     "engine.sample"}
    args = {n: a for n, _, _, a in sp}
    assert set(args["engine.tick"]) == {"engine", "active", "waiting"}
    assert {a["engine"] for n, _, _, a in sp if n == "engine.tick"} == {0, 1}
    assert set(args["sched.tick"]) == {"waiting", "free"}
    assert set(args["sched.repartition"]) == {"history"}
    assert set(args["engine.prefill"]) == {"rows", "bucket", "tokens"}
    assert set(args["engine.write_slot"]) == {"slot"}
    assert set(args["engine.chunk"]) == {"slot", "width"}
    assert set(args["engine.decode_step"]) == {"active"}
    assert all(s <= e for _, s, e, _ in sp)


@pytest.mark.parametrize("outer,inner", [
    ("engine.decode_step", "engine.decode_dispatch"),
    ("engine.decode_step", "engine.sample"),
    ("engine.prefill", "engine.write_slot"),
    ("engine.tick", "sched.tick"),
    ("engine.tick", "engine.prefill"),
    ("engine.tick", "engine.chunk"),
    ("engine.tick", "engine.decode_step"),
    ("engine.tick", "sched.repartition"),
    ("engine.tick", "sched.meta_trial"),
])
def test_spans_nest_as_the_engine_calls_them(traced, outer, inner):
    sp, _, _ = traced
    kids = spans.named(sp, inner, *ALL)
    held = spans.inside(spans.named(sp, outer, *ALL), kids)
    if outer == "engine.prefill":
        # write_slot also runs inside each chunk of the chunked engine
        held += spans.inside(spans.named(sp, "engine.chunk", *ALL), kids)
    assert sorted(k for ks in held for k in ks) == kids


def test_one_decode_step_span_per_step_counted(traced):
    sp, steps, _ = traced
    assert steps > 0
    for name in ("engine.decode_step", "engine.decode_dispatch",
                 "engine.sample"):
        assert len(spans.named(sp, name, *ALL)) == steps


def test_spans_leave_the_obs_ring_as_it_was(traced):
    _, _, engines = traced
    for eng in engines:
        kinds = {e[1] for e in eng.obs.trace.events}
        assert not any(k.startswith(spans.PREFIXES) for k in kinds)


# ---- the readings on a synthetic trace -----------------------------------

US = 1000          # the synthetic times below are in microseconds


def _synthetic():
    """One harness tick [10, 500) holding an engine tick with a scheduler
    tick, a prefill (one slot write) and two decode steps."""
    t = lambda *iv: tuple(x * US for x in iv)  # noqa: E731
    ops = [("prefill", *t(70, 110)), ("scatter", *t(125, 128)),
           ("decode", *t(225, 270)), ("decode", *t(318, 400)),
           ("x", *t(470, 482)), ("y", *t(490, 495))]
    modules = [("jit__prefill_fn(1)", *t(70, 110)),
               ("jit__decode_fn(2)", *t(225, 270)),
               ("jit__decode_fn(2)", *t(318, 400))]
    host = [("bench.traced", *t(0, 1000)), ("bench.tick", *t(10, 500))]
    sp = [("engine.tick", *t(20, 480), {}), ("sched.tick", *t(30, 40), {}),
          ("engine.prefill", *t(50, 150), {}),
          ("engine.write_slot", *t(120, 130), {}),
          ("engine.decode_step", *t(200, 300), {}),
          ("engine.decode_dispatch", *t(200, 230), {}),
          ("engine.sample", *t(240, 300), {}),
          ("engine.decode_step", *t(300, 420), {}),
          ("engine.decode_dispatch", *t(300, 320), {}),
          ("engine.sample", *t(330, 420), {})]
    return tracefile.Trace([ops], [modules], host), sp


@pytest.mark.parametrize("name,want_us", [
    ("engine.decode_host_ms", (40 + 30) / 2),   # step less its sample
    ("engine.readback_ms", (30 + 20) / 2),      # run end to sample end
    ("engine.prefill_idle_ms", 100 - 40 - 3),   # span less device busy
    ("sched.tick_ms", 10),
])
def test_each_reading_on_known_intervals(name, want_us):
    tr, sp = _synthetic()
    assert spans.READINGS[name](tr, sp) == pytest.approx(want_us / 1e3)


def test_readings_count_only_spans_starting_in_the_window():
    tr, sp = _synthetic()
    tr.host[0] = ("bench.traced", 0, 250 * US)
    assert spans.decode_host_ms(tr, sp) == pytest.approx(40 / 1e3)
    assert spans.sched_tick_ms(tr, sp[2:]) is None


def test_a_run_may_start_before_its_step_on_the_trace_clock():
    tr, sp = _synthetic()
    tr.modules[0][1:] = [("jit__decode_fn(2)", 195 * US, 270 * US),
                         ("jit__decode_fn(2)", 296 * US, 400 * US)]
    assert spans.readback_ms(tr, sp) == pytest.approx((30 + 20) / 2 / 1e3)
    assert spans.decode_steps_ms(tr, sp)["launch_ms"] == pytest.approx(
        ((195 - 230) + (296 - 320)) / 2 / 1e3)


def test_window_stops_where_the_device_trace_stops():
    tr, sp = _synthetic()
    assert spans.window(tr, sp) == (0, 1000 * US)
    lost = sp + [("engine.decode_step", 600 * US, 700 * US, {}),
                 ("engine.sample", 620 * US, 700 * US, {})]
    assert spans.window(tr, lost) == (0, 495 * US)
    assert spans.decode_host_ms(tr, lost) == pytest.approx(35 / 1e3)
    got = spans.idle_by_span(tr, lost)
    assert "none" not in got
    assert sum(got.values()) == pytest.approx((495 - 187) * US / 1e9)


def test_decode_step_pieces_on_known_intervals():
    tr, sp = _synthetic()
    got = spans.decode_steps_ms(tr, sp)
    assert got["idle_median_ms"] == pytest.approx(48 / 1e3)
    assert got["launch_ms"] == pytest.approx((-5 - 2) / 2 / 1e3)


def test_idle_goes_to_the_innermost_span():
    tr, sp = _synthetic()
    got = spans.idle_by_span(tr, sp)
    want = {"sched.tick": 70, "engine.prefill": 15, "engine.tick": 97 + 70,
            "engine.sample": 48, "bench.tick": 8, "none": 505}
    assert got == pytest.approx({k: v * US / 1e9 for k, v in want.items()})
    busy = tracefile.busy_ns(tr.ops[0], 0, 1000 * US) / 1e9
    assert sum(got.values()) + busy == pytest.approx(1000 * US / 1e9)


def test_idle_split_without_program_spans_is_todays():
    tr, _ = _synthetic()
    assert spans.idle_by_span(tr, []) == tracefile.idle_by_host_span(tr)
    chip = tracefile.load(HERE / "data" / "qwen3-4b.mixed.trace.json.gz")
    assert spans.idle_by_span(chip, []) == tracefile.idle_by_host_span(chip)


def test_longest_tick_split_by_innermost_span():
    tr, sp = _synthetic()
    got = spans.longest_tick(tr, sp)
    assert got["seconds"] == pytest.approx(490 * US / 1e9)
    want = {"bench.tick": 30, "engine.tick": 130, "sched.tick": 10,
            "engine.prefill": 90, "engine.write_slot": 10,
            "engine.decode_step": 20, "engine.decode_dispatch": 50,
            "engine.sample": 150}
    assert got["by_span"] == pytest.approx(
        {k: v * US / 1e9 for k, v in want.items()})


def test_innermost_pieces_cover_each_outer_span_once():
    pieces = spans.innermost([("a", 0, 10), ("b", 2, 4), ("c", 4, 6),
                              ("d", 8, 12), ("e", 20, 25)])
    assert pieces == [("a", 0, 2), ("b", 2, 4), ("c", 4, 6), ("a", 6, 8),
                      ("d", 8, 10), ("e", 20, 25)]
