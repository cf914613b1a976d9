"""Engine-side observability + calibration plane (obs/calibration.py,
serving/engine.py instrumentation, tools/calib_report.py).

Fast sections test the calibration layer, the fitted cost model, the
heartbeat fold, the trace taxonomy, and the offline tools on synthetic
data; the slow sections run the real JAX engine and check the obs=None
bit-identity contract, span causality, and end-to-end calibrator
convergence."""

import copy
import importlib.util
import json
import math
import pathlib

import numpy as np
import pytest

from repro.core.cost_model import CalibratedCostModel, CostModel
from repro.core.types import Request
from repro.cluster.health import HealthConfig, HealthMonitor
from repro.obs import (ATTACH_COPY, DECODE_STEP, PREFILL_CHUNK,
                       CostCalibrator, MetricsRegistry, Observability,
                       PredictorCalibration, TraceRecorder, record_finish,
                       slo_from_requests, slo_or_fallback, slo_report)
from repro.obs.trace import LIFECYCLE_KINDS, SPAN_STAGES

_TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# CostCalibrator: streaming fits, residuals, drift
# ---------------------------------------------------------------------------

class TestCostCalibrator:
    def test_converges_on_known_affine(self):
        """Synthetic step times y = 3.2 x + 0.01: the fit must recover
        scale and offset and leave post-fit residuals pinned at 1."""
        cal = CostCalibrator(min_samples=4)
        for i in range(1, 101):
            x = 1e-3 * i
            cal.observe(DECODE_STEP, x, 3.2 * x + 0.01)
        corr = cal.correction()[DECODE_STEP]
        assert corr["scale"] == pytest.approx(3.2, rel=1e-6)
        assert corr["offset"] == pytest.approx(0.01, rel=1e-6)
        assert corr["n"] == 100
        res = cal.residuals(DECODE_STEP)
        assert res["p50"] == pytest.approx(1.0, abs=1e-6)
        assert res["p90"] == pytest.approx(1.0, abs=1e-6)

    def test_min_samples_excludes_underobserved(self):
        cal = CostCalibrator(min_samples=8)
        for i in range(1, 5):
            cal.observe(ATTACH_COPY, 1e-3 * i, 2e-3 * i)
        assert ATTACH_COPY not in cal.correction()
        assert cal.samples(ATTACH_COPY) == 4

    def test_nonpositive_inputs_dropped(self):
        cal = CostCalibrator()
        cal.observe(PREFILL_CHUNK, 0.0, 1.0)
        cal.observe(PREFILL_CHUNK, 1.0, -1.0)
        cal.observe(PREFILL_CHUNK, -1.0, 1.0)
        assert cal.samples(PREFILL_CHUNK) == 0
        assert cal.dropped == 3

    def test_single_sample_ratio_fallback(self):
        cal = CostCalibrator(min_samples=1)
        cal.observe(DECODE_STEP, 2.0, 5.0)
        corr = cal.correction()[DECODE_STEP]
        assert corr["scale"] == pytest.approx(2.5)
        assert corr["offset"] == 0.0

    def test_drift_detection(self):
        """A regime change (scale 1 → 2 in the recent window) must flip
        ``drifting``; a stationary stream must not."""
        cal = CostCalibrator(drift_window=32, drift_threshold=0.3,
                             min_samples=4)
        for i in range(1, 201):
            x = 1e-3 * (1 + i % 17)
            cal.observe(PREFILL_CHUNK, x, 1.0 * x)
        assert not cal.drift(PREFILL_CHUNK)["drifting"]
        for i in range(1, 33):
            x = 1e-3 * (1 + i % 17)
            cal.observe(PREFILL_CHUNK, x, 2.0 * x)
        d = cal.drift(PREFILL_CHUNK)
        assert d["drifting"]
        assert d["drift_ratio"] > 1.3
        worst = cal.worst_drift()
        assert worst and worst[0][0] == PREFILL_CHUNK

    def test_empty_and_unknown_class_views(self):
        cal = CostCalibrator()
        assert cal.correction() == {}
        assert cal.residuals("nope") == {"n": 0}
        assert cal.drift("nope") == {"n": 0, "drifting": False}
        assert cal.worst_drift() == []
        from repro.obs.calibration import _StreamingFit
        assert _StreamingFit().fit() == (1.0, 0.0)

    def test_report_and_snapshot_shapes(self):
        cal = CostCalibrator(min_samples=2)
        for i in range(1, 10):
            cal.observe(DECODE_STEP, 1e-3 * i, 2e-3 * i)
        rep = cal.report()
        assert set(rep) == {DECODE_STEP}
        assert {"n", "scale", "offset", "raw_ratio", "residual",
                "drift"} <= set(rep[DECODE_STEP])
        snap = cal.snapshot()
        json.dumps(snap)          # JSON-able
        assert snap["correction"][DECODE_STEP]["scale"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# CalibratedCostModel: fitted correction consumer
# ---------------------------------------------------------------------------

class TestCalibratedCostModel:
    def test_applies_fit_per_class(self):
        base = CostModel()
        corr = {"decode_step": {"scale": 3.0, "offset": 0.004, "n": 50},
                "prefill_chunk": {"scale": 0.5, "offset": 0.0, "n": 50}}
        cal = CalibratedCostModel.from_fit(base, corr)
        raw_d = base.decode_step_time(4, 2048)
        assert cal.decode_step_time(4, 2048) == pytest.approx(
            3.0 * raw_d + 0.004)
        raw_p = base.prefill_cost(512, cached=128)
        assert cal.prefill_cost(512, cached=128) == pytest.approx(0.5 * raw_p)
        assert cal.c_prefill(256) == pytest.approx(0.5 * base.c_prefill(256))

    def test_missing_class_passes_through(self):
        base = CostModel()
        cal = CalibratedCostModel.from_fit(base, {})
        assert cal.attach_copy_time(256) == base.attach_copy_time(256)
        assert cal.decode_step_time(2, 100) == base.decode_step_time(2, 100)

    def test_correction_floor_never_negative(self):
        base = CostModel()
        cal = CalibratedCostModel.from_fit(
            base, {"attach_copy": {"scale": 0.1, "offset": -1.0, "n": 20}})
        assert cal.attach_copy_time(16) == 1e-12

    def test_attach_copy_time_scales_linearly(self):
        base = CostModel()
        assert base.attach_copy_time(512) == pytest.approx(
            2.0 * base.attach_copy_time(256))


# ---------------------------------------------------------------------------
# PredictorCalibration: predicted-vs-actual length views
# ---------------------------------------------------------------------------

def _finished(rid, pred, actual, session=None, plen=64):
    r = Request(request_id=rid, prompt_len=plen)
    r.predicted_output = pred
    r.generated = actual
    r.session_id = session
    return r


class TestPredictorCalibration:
    def test_perfect_predictions(self):
        pc = PredictorCalibration()
        for i in range(20):
            pc.observe(_finished(i, 32.0, 32))
        assert pc.ece() == pytest.approx(0.0)
        assert pc.coverage() == 1.0
        assert pc.bias() == pytest.approx(0.0)

    def test_curve_matches_ground_truth(self):
        """Two predicted-length bins with known means: the curve rows must
        reproduce them and the ECE the exact weighted relative gap."""
        pc = PredictorCalibration()
        for i in range(10):
            pc.observe(_finished(i, 8.0, 10))        # bin [8,16): 20% under
        for i in range(10, 20):
            pc.observe(_finished(i, 64.0, 32))       # bin [64,128): 2x over
        rows = {r["lo"]: r for r in pc.curve()}
        assert rows[8.0]["mean_predicted"] == pytest.approx(8.0)
        assert rows[8.0]["mean_actual"] == pytest.approx(10.0)
        assert rows[64.0]["mean_actual"] == pytest.approx(32.0)
        expected = 0.5 * (2.0 / 10.0) + 0.5 * (32.0 / 32.0)
        assert pc.ece() == pytest.approx(expected)
        assert pc.coverage() == pytest.approx(0.5)

    def test_abstentions_tracked_not_scored(self):
        pc = PredictorCalibration()
        r = Request(request_id=1, prompt_len=10)
        r.generated = 5                   # no predicted_output stamp
        pc.observe(r)
        assert pc.abstained == 1 and pc.observed == 0
        assert pc.ece() == 0.0

    def test_worst_keys_ranked_by_bias(self):
        pc = PredictorCalibration(min_key_n=2)
        for i in range(4):
            pc.observe(_finished(i, 64.0, 16, session="bad"))   # 4x over
        for i in range(4, 8):
            pc.observe(_finished(i, 18.0, 16, session="good"))
        worst = pc.worst_keys()
        assert worst[0]["key"] == "session=bad"
        assert worst[0]["bias"] == pytest.approx(math.log(4.0))
        assert pc.key_bias("session=good") == pytest.approx(
            math.log(18.0 / 16.0))

    def test_degenerate_observations_ignored(self):
        pc = PredictorCalibration()
        pc.observe(_finished(0, 0.0, 5))       # non-positive prediction
        pc.observe(_finished(1, 8.0, 0))       # nothing generated
        assert pc.observed == 0 and pc.abstained == 0
        assert pc.key_bias("session=unseen") is None
        assert pc.coverage() == 0.0 and pc.bias() == 0.0
        assert pc.curve() == [] and pc.worst_keys() == []

    def test_key_space_bounded(self):
        pc = PredictorCalibration(max_keys=8)
        for i in range(50):
            pc.observe(_finished(i, 16.0, 16, session=f"s{i}"))
        assert len(pc._keys) == 8
        assert pc.observed == 50          # global stats still fold overflow


# ---------------------------------------------------------------------------
# Observability wiring: calib slots, finish() feed, snapshot payloads
# ---------------------------------------------------------------------------

class TestObservabilityCalibration:
    def test_enabled_with_calibration_attaches_both(self):
        obs = Observability.enabled(calibration=True)
        assert obs.calib is not None and obs.pred_calib is not None
        obs2 = Observability.enabled()
        assert obs2.calib is None and obs2.pred_calib is None

    def test_calibrate_routes_and_noops(self):
        obs = Observability.enabled(calibration=True)
        obs.calibrate(DECODE_STEP, 0.01, 0.02)
        assert obs.calib.samples(DECODE_STEP) == 1
        Observability.enabled().calibrate(DECODE_STEP, 0.01, 0.02)  # no-op

    def test_finish_feeds_predictor_calibration(self):
        obs = Observability.enabled(calibration=True)
        r = _finished(7, 16.0, 16)
        r.arrival_time, r.first_token_time, r.finish_time = 0.0, 0.5, 1.0
        obs.finish(r, 1.0)
        assert obs.pred_calib.observed == 1
        snap = obs.snapshot()
        assert "calibration" in snap and "predictor_calibration" in snap
        json.dumps(snap)


# ---------------------------------------------------------------------------
# HealthMonitor: engine heartbeats
# ---------------------------------------------------------------------------

class TestEngineHeartbeat:
    def test_heartbeat_folds_into_kv_view_and_liveness(self):
        hm = HealthMonitor(HealthConfig(heartbeat_timeout=5.0, kv_alpha=0.5))
        hm.observe_engine_heartbeat(
            {"engine_id": 3, "t": 1.0, "kv_occupancy": 0.4})
        hm.observe_engine_heartbeat(
            {"engine_id": 3, "t": 2.0, "kv_occupancy": 0.8})
        assert hm.kv_ewma[3] == pytest.approx(0.6)     # 0.4 then EWMA to 0.6
        assert hm.kv_peak[3] == pytest.approx(0.8)
        assert hm.engine_alive(3, 6.9)
        assert not hm.engine_alive(3, 7.1)
        assert not hm.engine_alive(99, 2.0)            # never reported
        assert hm.engine_beacon[3]["kv_occupancy"] == 0.8


# ---------------------------------------------------------------------------
# Trace taxonomy: stage map, slot tracks, lifecycle kinds
# ---------------------------------------------------------------------------

class TestTraceTaxonomy:
    def test_span_stage_map(self):
        assert SPAN_STAGES["chunk"] == "prefill"
        assert SPAN_STAGES["recompute"] == "prefill"
        assert SPAN_STAGES["attach"] == "attach"
        assert "park" in LIFECYCLE_KINDS and "promote" in LIFECYCLE_KINDS

    def test_engine_spans_land_on_slot_tracks(self):
        tr = TraceRecorder()
        tr.emit("chunk", 1.0, request_id=5, replica_id=0, dur=0.1,
                data={"slot": 2})
        tr.emit("decode", 1.2, replica_id=0, dur=0.05, data={"batch": 4})
        tr.emit("promote", 1.3, request_id=5, replica_id=0,
                data={"slot": 2})
        evs = tr.to_chrome_trace()["traceEvents"]
        chunk = next(e for e in evs if e["name"] == "chunk")
        decode = next(e for e in evs if e["name"] == "decode")
        promote = next(e for e in evs if e["name"] == "promote")
        assert chunk["ph"] == "X" and chunk["tid"] == 2
        assert decode["tid"] == 0                  # batch span: track 0
        assert promote["ph"] == "i" and promote["tid"] == 5


# ---------------------------------------------------------------------------
# One slo_report code path for both backends
# ---------------------------------------------------------------------------

class TestSloOnePath:
    def _reqs(self, n=12):
        out = []
        for i in range(n):
            r = Request(request_id=i, prompt_len=50 + i)
            r.arrival_time = float(i)
            r.first_token_time = r.arrival_time + 0.1 * (i + 1)
            r.finish_time = r.first_token_time + 0.5
            r.generated = 5
            out.append(r)
        return out

    def test_fallback_equals_requests_path(self):
        reqs = self._reqs()
        assert slo_or_fallback(None, reqs) == slo_from_requests(reqs)

    def test_registry_path_wins_when_present(self):
        reqs = self._reqs()
        reg = MetricsRegistry()
        for r in reqs:
            record_finish(reg, r, "interactive")
        assert slo_or_fallback(reg, []) == slo_report(reg)


# ---------------------------------------------------------------------------
# Offline tools on synthetic traces / payloads
# ---------------------------------------------------------------------------

def _synthetic_trace():
    return {"traceEvents": [
        {"name": "arrival", "ph": "i", "ts": 0.0, "pid": 0, "tid": 1,
         "args": {"request_id": 1}},
        {"name": "dispatch", "ph": "i", "ts": 1e5, "pid": 0, "tid": 1,
         "args": {"request_id": 1}},
        {"name": "park", "ph": "i", "ts": 1e5, "pid": 0, "tid": 1,
         "args": {"request_id": 1, "slot": 0}},
        {"name": "attach", "ph": "X", "ts": 1.1e5, "dur": 2e4, "pid": 0,
         "tid": 0, "args": {"request_id": 1, "slot": 0}},
        {"name": "chunk", "ph": "X", "ts": 1.4e5, "dur": 5e4, "pid": 0,
         "tid": 0, "args": {"request_id": 1, "slot": 0}},
        {"name": "recompute", "ph": "X", "ts": 2e5, "dur": 3e4, "pid": 0,
         "tid": 0, "args": {"request_id": 1, "slot": 0}},
        {"name": "promote", "ph": "i", "ts": 2.4e5, "pid": 0, "tid": 1,
         "args": {"request_id": 1, "slot": 0}},
        {"name": "first_token", "ph": "i", "ts": 2.4e5, "pid": 0, "tid": 1,
         "args": {"request_id": 1}},
        {"name": "decode", "ph": "X", "ts": 2.5e5, "dur": 4e4, "pid": 0,
         "tid": 0, "args": {"batch": 2}},
        {"name": "finish", "ph": "i", "ts": 3e5, "pid": 0, "tid": 1,
         "args": {"request_id": 1}},
    ]}


class TestTraceSummaryTool:
    def test_stage_occupancy_groups_engine_spans(self, tmp_path):
        ts = _load_tool("trace_summary")
        events = _synthetic_trace()["traceEvents"]
        occ = ts.stage_occupancy(events)
        assert occ[0]["prefill"] == pytest.approx(0.08)  # chunk + recompute
        assert occ[0]["attach"] == pytest.approx(0.02)
        assert occ[0]["decode"] == pytest.approx(0.04)

    def test_slot_view_and_summary_exit_codes(self, tmp_path, capsys):
        ts = _load_tool("trace_summary")
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(_synthetic_trace()))
        assert ts.summarize(str(path), slot=0) == 0
        out = capsys.readouterr().out
        assert "park" in out and "attach" in out and "promote" in out
        assert ts.summarize(str(path), slot=7) == 1      # empty slot
        assert ts.summarize(str(path), request=1) == 0
        assert ts.summarize(str(path)) == 0
        out = capsys.readouterr().out
        assert "stages" in out

    def test_stage_occupancy_groups_profiler_spans(self):
        """The tool reads the stage map from the obs plane, so the
        profiler's engine spans group with the ring's own."""
        ts = _load_tool("trace_summary")
        assert ts.SPAN_STAGES is SPAN_STAGES
        events = [
            {"name": "engine.prefill", "ph": "X", "ts": 0.0, "dur": 3e4,
             "pid": 0, "tid": 0},
            {"name": "engine.chunk", "ph": "X", "ts": 4e4, "dur": 2e4,
             "pid": 0, "tid": 0},
            {"name": "engine.decode_step", "ph": "X", "ts": 7e4,
             "dur": 5e4, "pid": 0, "tid": 0},
            {"name": "engine.sample", "ph": "X", "ts": 9e4, "dur": 1e4,
             "pid": 0, "tid": 0}]
        occ = ts.stage_occupancy(events)
        assert occ[0] == pytest.approx({"prefill": 0.05, "decode": 0.05,
                                        "other": 0.01})

    def test_slot_events_time_ordered(self):
        ts = _load_tool("trace_summary")
        events = list(reversed(_synthetic_trace()["traceEvents"]))
        evs = ts.slot_events(events, 0)
        assert [e["name"] for e in evs] == [
            "park", "attach", "chunk", "recompute", "promote"]


class TestCalibReportTool:
    def _payload(self):
        cal = CostCalibrator(min_samples=2)
        for i in range(1, 20):
            cal.observe(DECODE_STEP, 1e-3 * i, 2e-3 * i + 1e-4)
        pc = PredictorCalibration()
        for i in range(10):
            pc.observe(_finished(i, 16.0, 14))
        return {"cost_calibration": cal.snapshot(),
                "predictor_calibration": pc.snapshot()}

    def test_derive_and_render(self, tmp_path, capsys):
        cr = _load_tool("calib_report")
        view = cr.derive(self._payload())
        row = next(r for r in view["classes"]
                   if r["op_class"] == DECODE_STEP)
        assert row["scale"] == pytest.approx(2.0, rel=1e-3)
        assert row["residual_p50"] == pytest.approx(1.0, abs=1e-6)
        assert view["predictor"]["ece"] > 0
        cr.render(view)
        out = capsys.readouterr().out
        assert "decode_step" in out and "length predictor" in out

    def test_cli_roundtrip(self, tmp_path, capsys):
        cr = _load_tool("calib_report")
        path = tmp_path / "calib.json"
        path.write_text(json.dumps(self._payload()))
        assert cr.main([str(path)]) == 0
        capsys.readouterr()
        assert cr.main([str(path), "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["classes"][0]["op_class"] == DECODE_STEP
        empty = tmp_path / "empty.json"
        empty.write_text("{}")
        assert cr.main([str(empty)]) == 1


# ===========================================================================
# Slow: real JAX engine
# ===========================================================================

slow = pytest.mark.slow


@pytest.fixture(scope="module")
def model():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import init_params
    cfg = get_smoke_config("llama2-13b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _requests(cfg, n=6, seed=0, max_new=6, prefix_tokens=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=(prefix_tokens,)).astype(np.int32)
    out = []
    for i in range(n):
        pl = 64 + 16 * (i % 3)
        toks = rng.integers(0, cfg.vocab_size, size=(pl,)).astype(np.int32)
        if prefix_tokens:
            toks[:prefix_tokens] = shared
        r = Request(request_id=i, arrival_time=0.0, prompt_len=pl,
                    max_new_tokens=max_new, prompt_tokens=toks)
        r.predicted_output = float(max_new)
        out.append(r)
    return out


def _engine(cfg, params, obs=None, chunk=32, radix=False):
    from repro.core import FCFSScheduler
    from repro.serving import EngineConfig, ServingEngine
    ecfg = EngineConfig(max_slots=4, s_max=256, kv_pool_tokens=16384,
                        chunk_prefill_tokens=chunk,
                        enable_prefix_cache=radix)
    return ServingEngine(cfg, params, FCFSScheduler(), ecfg, obs=obs)


@slow
class TestEngineBitIdentity:
    @pytest.mark.parametrize("chunk,radix", [(32, False), (None, True),
                                             (32, True)])
    def test_sampled_tokens_identical_obs_on_off(self, model, chunk, radix):
        """The bit-identity contract on the real engine: a fully enabled
        calibration obs bundle must not move a single sampled token id,
        in chunked, radix, and chunked+radix modes."""
        cfg, params = model
        base = _requests(cfg, n=5, seed=3, prefix_tokens=48 if radix else 0)
        eng_off = _engine(cfg, params, None, chunk, radix)
        eng_off.run(copy.deepcopy(base), max_steps=4000)
        eng_on = _engine(cfg, params, Observability.enabled(calibration=True),
                         chunk, radix)
        eng_on.run(copy.deepcopy(base), max_steps=4000)
        assert eng_off.output_tokens == eng_on.output_tokens
        assert len(eng_on.finished) == len(base)


@slow
class TestEngineTraceAndCalibration:
    def test_span_causality_and_slot_tracks(self, model):
        """Chunk spans nest inside dispatch → first_token; the attach span
        precedes the slot's promote; engine spans carry slot tracks."""
        cfg, params = model
        obs = Observability.enabled(calibration=True)
        eng = _engine(cfg, params, obs, chunk=32, radix=True)
        eng.run(_requests(cfg, n=5, seed=1, prefix_tokens=48),
                max_steps=4000)
        assert len(eng.finished) == 5
        for rid in range(5):
            evs = obs.trace.request_events(rid)
            by_kind = {}
            for e in evs:
                by_kind.setdefault(e.kind, []).append(e)
            t_disp = by_kind["dispatch"][0].t
            t_first = by_kind["first_token"][0].t
            chunks = by_kind.get("chunk", []) + by_kind.get("recompute", [])
            assert chunks, f"request {rid}: no chunk spans"
            for c in chunks:
                assert t_disp <= c.t and c.t + c.dur <= t_first + 1e-6
                assert "slot" in c.data
            assert by_kind["promote"][0].t <= t_first + 1e-9
            if "attach" in by_kind:
                assert by_kind["attach"][0].t <= by_kind["promote"][0].t
        # Later dispatches against the published prefix must have attached.
        kinds = {e[1] for e in obs.trace.events}
        assert "attach" in kinds and "park" in kinds

    def test_calibrator_converges_on_real_engine(self, model):
        """After a real run the prefill/decode fits must have samples and
        post-fit residual medians in a sane band around 1."""
        cfg, params = model
        obs = Observability.enabled(calibration=True)
        eng = _engine(cfg, params, obs, chunk=32, radix=True)
        eng.run(_requests(cfg, n=6, seed=2, max_new=8, prefix_tokens=48),
                max_steps=4000)
        for op in (PREFILL_CHUNK, DECODE_STEP):
            assert obs.calib.samples(op) > 0, op
        res = obs.calib.residuals(PREFILL_CHUNK)
        assert res["n"] > 0 and 0.5 <= res["p50"] <= 2.0
        assert obs.pred_calib.observed == 6
        # Metrics plane: chunk widths + compile cache counters recorded.
        snap = obs.metrics.snapshot()
        assert "engine_compile_cache_total" in snap["counters"]
        assert "radix_probe_total" in snap["counters"]
        assert "engine_chunk_width_tokens" in snap["histograms"]

    @pytest.mark.parametrize("chunk", [None, 32], ids=["bucketed", "chunked"])
    def test_compile_cache_counter_counts_program_builds(self, model, chunk):
        """The first call of a shape builds its program (hit="false"); the
        same shape again runs what was built (hit="true")."""
        cfg, params = model
        obs = Observability.enabled()
        eng = _engine(cfg, params, obs, chunk=chunk)
        kind = "chunk" if chunk else "prefill"
        counted = lambda kind, hit: obs.metrics.counter_value(  # noqa: E731
            "engine_compile_cache_total", {"kind": kind, "hit": hit})
        reqs = _requests(cfg, n=4, seed=6)
        hits = []
        for r in (reqs[0], reqs[3]):      # one shape, one request at a time
            eng.add_request(r)
            while eng.has_work():
                eng.tick()
            assert counted(kind, "false") == 1
            assert counted("decode", "false") == 1
            hits.append((counted(kind, "true"), counted("decode", "true")))
        assert hits[1][0] > hits[0][0] and hits[1][1] > hits[0][1]
        if chunk is None:
            assert hits[0][0] == 0

    def test_heartbeat_feeds_health_monitor(self, model):
        cfg, params = model
        obs = Observability.enabled()
        eng = _engine(cfg, params, obs, chunk=32, radix=False)
        eng.run(_requests(cfg, n=3, seed=4), max_steps=4000)
        hb = eng.heartbeat()
        assert hb["finished"] == 3 and hb["tokens_out"] == 3 * 6
        assert "metrics" in hb
        hm = HealthMonitor()
        hm.observe_engine_heartbeat(hb)
        assert hm.engine_alive(hb["engine_id"], hb["t"] + 1.0)
        assert hm.kv_ewma[hb["engine_id"]] == pytest.approx(
            hb["kv_occupancy"])

    def test_engine_slo_report_one_code_path(self, model):
        """Engine slo_report must return per-class percentiles both with a
        live registry and via the request-side fallback, and the two must
        agree on counts for the same run."""
        cfg, params = model
        obs = Observability.enabled()
        eng = _engine(cfg, params, obs, chunk=32)
        eng.run(_requests(cfg, n=4, seed=5), max_steps=4000)
        live = eng.slo_report()
        recomputed = slo_from_requests(eng.finished, obs.classify)
        assert live["_all"]["ttft"]["n"] == recomputed["_all"]["ttft"]["n"]
        eng2 = _engine(cfg, params, None, chunk=32)
        eng2.run(_requests(cfg, n=4, seed=5), max_steps=4000)
        rep = eng2.slo_report()
        assert rep and rep["_all"]["ttft"]["n"] == 4
        assert eng2.stats()["slo"] == rep
