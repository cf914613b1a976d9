"""End-to-end behaviour tests for the paper's system (deliverable c):
the paper's headline claims exercised through the full stack."""

import copy

import pytest

pytestmark = pytest.mark.slow  # full-stack system claims (mesh dry-runs, long sims)

from repro.core import (CostModel, EngineParams, EWSJFConfig, EWSJFScheduler,
                        FCFSScheduler, ServingSimulator, SJFScheduler,
                        WorkloadSpec)
from repro.core.cost_model import LLAMA2_13B_COST


def cm():
    return CostModel(model=LLAMA2_13B_COST, n_chips=4, mfu=0.15, hbm_eff=0.7)


def ep(**kw):
    base = dict(max_num_seqs=256, kv_pool_tokens=131072, bucket_pad=False,
                ttft_timeout=90.0)
    base.update(kw)
    return EngineParams(**base)


def ewsjf(**kw):
    base = dict(min_history=64, reopt_interval=30.0, trial_interval=60.0)
    base.update(kw)
    return EWSJFScheduler(EWSJFConfig(**base), cm())


class TestPaperHeadlines:
    """SS6 / abstract claims at reduced scale (exact tables: benchmarks/)."""

    @pytest.fixture(scope="class")
    def overload(self):
        return WorkloadSpec(n_requests=1200, arrival_rate=40.0,
                            seed=0).generate()

    def test_throughput_gain_over_30pct(self, overload):
        f = ServingSimulator(FCFSScheduler(), cm(), ep()).run(
            copy.deepcopy(overload))
        e = ServingSimulator(ewsjf(), cm(), ep()).run(copy.deepcopy(overload))
        assert e.tok_per_s / f.tok_per_s > 1.30

    def test_ttft_4x_improvement(self, overload):
        f = ServingSimulator(FCFSScheduler(), cm(), ep()).run(
            copy.deepcopy(overload))
        e = ServingSimulator(ewsjf(), cm(), ep()).run(copy.deepcopy(overload))
        assert (f.ttft_stats()["short"]["mean"]
                / e.ttft_stats()["short"]["mean"] > 4.0)

    def test_refined_beats_or_matches_coarse_kmeans(self, overload):
        from repro.core import kmeans_partition
        res = {}
        for name, part in [("k5", lambda l: kmeans_partition(l, 5)),
                           ("refined", None)]:
            s = EWSJFScheduler(EWSJFConfig(min_history=64, max_queues=32),
                               cm(), partitioner=part)
            res[name] = ServingSimulator(s, cm(), ep()).run(
                copy.deepcopy(overload)).tok_per_s
        assert res["refined"] > res["k5"] * 0.95

    def test_meta_optimizer_improves_reward_online(self):
        """The strategic loop's Bayesian trials must not degrade the system:
        reward of the best-found Theta >= first-trial reward."""
        wl = WorkloadSpec(n_requests=1500, arrival_rate=40.0, seed=3)
        s = ewsjf(trial_interval=15.0)
        ServingSimulator(s, cm(), ep()).run(wl.generate())
        rewards = [t.reward for t in s.meta_opt.trials]
        if len(rewards) >= 3:
            assert max(rewards) >= rewards[0] - 1e-9


class TestDryRunSmoke:
    """build_cell lowers+compiles on a small multi-device mesh (the full
    production sweep lives in launch/dryrun.py; results in EXPERIMENTS.md)."""

    def test_smoke_cells_compile_on_8_devices(self):
        import subprocess, sys, textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax
            from repro.launch.cells import build_cell
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(model_parallel=4)     # (data 2, model 4)
            with mesh:
                for arch, shape in [("qwen3-4b", "train_4k"),
                                    ("qwen3-4b", "decode_32k"),
                                    ("recurrentgemma-9b", "decode_32k"),
                                    ("phi3.5-moe-42b-a6.6b", "train_4k")]:
                    cell = build_cell(arch, shape, mesh, smoke=True)
                    jitted = jax.jit(cell.step_fn,
                                     in_shardings=cell.in_shardings,
                                     out_shardings=cell.out_shardings,
                                     donate_argnums=cell.donate_argnums)
                    compiled = jitted.lower(*cell.args).compile()
                    assert compiled.cost_analysis() is not None
                    print("OK", arch, shape)
        """)
        import os
        from pathlib import Path
        src = str(Path(__file__).resolve().parents[1] / "src")
        env = dict(os.environ)
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=420, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("OK") == 4
