"""Compile-only checks of the served path for one described TPU v5e chip.

The engine's three step programs are compiled for qwen3-4b at full width in
bfloat16, at the sizes ``chip_smoke.py`` serves, from ``jax.eval_shape``
shapes (nothing is allocated).  The TPU compiler refuses a program that does
not fit the chip's HBM, so these guard every later change at no chip time.
The decode program of each benchmark configuration is also checked to
update its donated cache in place.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this file.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
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
ROOT = Path(__file__).resolve().parents[1]
BENCH_CONFIGS = ["qwen3-4b", "h2o-danube-1.8b"]

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


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\(")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "f32": 4, "s32": 4, "u32": 4}
# Results that hold no new data: views, control flow, the loop's tuples.
_NO_DATA = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
            "while", "conditional", "call"}


def unfused_arrays(hlo: str):
    """(computation, instruction, opcode, dtype, dims) of every array an
    instruction outside a fused computation produces (tuple results give
    one entry per element), from a compiled module's text."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    where, skip = None, False
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            where = head.group(1)
            skip = where in fused or where.startswith("%fused")
            continue
        inst = None if skip else _INSTRUCTION.match(line)
        if inst is None or inst.group(3) in _NO_DATA:
            continue
        name, shape, op = inst.groups()
        for dtype, dims in _ARRAY.findall(shape):
            yield (where, name, op, dtype,
                   tuple(int(d) for d in dims.split(",") if d))


def _squeezed(dims) -> tuple:
    return tuple(sorted(d for d in dims if d != 1))


@pytest.mark.parametrize("name", BENCH_CONFIGS)
def test_decode_updates_the_donated_cache_in_place(name, one_chip,
                                                   no_persistent_cache):
    """A benchmark configuration's decode program, jitted as
    ``ServingEngine`` jits it (caches donated) at the file's slots and
    ``s_max``: the whole cache is aliased to the output, temporaries stay
    under 1% of it, and no instruction outside a fused computation produces
    an array of one layer's keys (or values) or more, but the row writes
    (dynamic-update-slice or scatter) into the carried cache itself."""
    from perfbench import driver, weights
    c = json.loads((ROOT / f"perfbench/configs/{name}.json").read_text())
    cfg, e = driver.model_config(c), c["engine"]
    ns = types.SimpleNamespace(cfg=cfg, moe_ctx=MoECtx(impl="dropping"),
                               policy=DtypePolicy.serve())
    params = _on(jax.eval_shape(lambda: weights.program_params(0, c)),
                 one_chip)
    caches = _on(jax.eval_shape(lambda: init_decode_caches(
        cfg, e["max_slots"], e["s_max"], jnp.bfloat16)), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    fn = functools.partial(ServingEngine._decode_fn, ns)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, i32((e["max_slots"], 1)), caches,
        i32((e["max_slots"],))).compile()
    mem = compiled.memory_analysis()
    kv = c["bytes"]["kv_cache"]
    assert mem.alias_size_in_bytes == pytest.approx(kv, rel=0.01)
    assert mem.temp_size_in_bytes < 0.01 * kv

    # One layer's keys (or values); a layer's weights are not the cache's
    # concern and are left out by their shapes.
    layer_bytes = kv / cfg.n_layers / len(jax.tree.leaves(caches))
    cache_dims = {tuple(x.shape) for x in jax.tree.leaves(caches)}
    weight_dims = {_squeezed(x.shape[1:])
                   for x in jax.tree.leaves(params["blocks"]["stack"])}
    big = [(where, inst, op, dims)
           for where, inst, op, dtype, dims in unfused_arrays(
               compiled.as_text())
           if _ITEM_BYTES[dtype] * math.prod(dims) >= layer_bytes
           and _squeezed(dims) not in weight_dims
           and not (dims in cache_dims and re.search(
               r"dynamic-update-slice|scatter", f"{op} {inst}"))]
    assert not big


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
