"""Compile-only checks of the served path for one described TPU v5e chip.

The engine's three step programs are compiled for qwen3-4b at full width in
bfloat16, at the sizes ``chip_smoke.py`` serves, from ``jax.eval_shape``
shapes (nothing is allocated).  The TPU compiler refuses a program that does
not fit the chip's HBM, so these guard every later change at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this file.
"""

from __future__ import annotations

import functools
import os
import types
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import init_params
from repro.models.common import DtypePolicy
from repro.models.model import init_decode_caches
from repro.models.transformer import MoECtx
from repro.serving.api import FULL_WIDTH_ENGINE
from repro.serving.engine import ServingEngine

V5E_HBM_BYTES = 15.75e9      # what the compiler reports as one chip's HBM

# The full-width engine sizing serve() and chip_smoke.py use.  Prefill is
# compiled at its largest shape: every slot admitted in one batch, padded
# to the top bucket.  256 is chip_smoke.py's chunk budget.
SLOTS, S_MAX = FULL_WIDTH_ENGINE["max_slots"], FULL_WIDTH_ENGINE["s_max"]
PREFILL_N, PREFILL_BUCKET = SLOTS, max(FULL_WIDTH_ENGINE["buckets"])
CHUNK_WIDTH = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def engine_steps():
    """The engine's own step functions, bound to the full-width config and
    the bf16 serving policy (no engine is built: that would allocate)."""
    cfg = get_config("qwen3-4b")
    ns = types.SimpleNamespace(cfg=cfg, moe_ctx=MoECtx(impl="dropping"),
                               policy=DtypePolicy.serve())
    return cfg, {name: functools.partial(getattr(ServingEngine, name), ns)
                 for name in ("_decode_fn", "_prefill_fn", "_chunk_fn")}


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compiled_bytes(fn, *args) -> int:
    """Arguments + outputs + temporaries of ``fn`` compiled for ``args``;
    raises what the chip's compiler raises (out of HBM among it)."""
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("step", ["decode", "prefill", "chunk"])
def test_qwen3_4b_step_fits_one_v5e(step, one_chip, no_persistent_cache,
                                    engine_steps):
    cfg, fns = engine_steps
    params = _on(jax.eval_shape(
        functools.partial(init_params, cfg=cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0)), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    if step == "decode":
        caches = _on(jax.eval_shape(
            lambda: init_decode_caches(cfg, SLOTS, S_MAX, jnp.bfloat16)),
            one_chip)
        args = (params, i32((SLOTS, 1)), caches, i32((SLOTS,)))
        fn = fns["_decode_fn"]
    elif step == "prefill":
        args = (params, i32((PREFILL_N, PREFILL_BUCKET)), i32((PREFILL_N,)))
        fn = fns["_prefill_fn"]
    else:
        caches = _on(jax.eval_shape(
            lambda: init_decode_caches(cfg, 1, S_MAX, jnp.bfloat16)),
            one_chip)
        args = (params, i32((1, CHUNK_WIDTH)), caches, i32(()))
        fn = fns["_chunk_fn"]
    assert 0 < _compiled_bytes(fn, *args) < V5E_HBM_BYTES


def test_compile_cache_dir_prefers_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir() == str(root / ".jax_cache")


def test_enable_compile_cache_sets_only_the_default(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        compile_cache.enable_compile_cache()
        assert (jax.config.jax_compilation_cache_dir
                == compile_cache.cache_dir())
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
