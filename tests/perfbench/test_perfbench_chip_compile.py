"""The benchmark's configurations compiled for one described TPU v5e chip.

Each configuration's decode step and its largest bucketed prefill (every
slot admitted at the top bucket), at the published widths and the engine
sizing of its file, from shapes only.  The compiler refuses a program that
does not fit the chip; ``memory_analysis()`` must also agree with the bytes
the configuration file reckons (weights and cache).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this file.
"""

from __future__ import annotations

import functools
import json
import os
import types
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from perfbench import driver, weights
from repro.models.common import DtypePolicy
from repro.models.model import init_decode_caches
from repro.models.transformer import MoECtx
from repro.serving.engine import ServingEngine

ROOT = Path(__file__).resolve().parents[2]
V5E_HBM_BYTES = 15.75e9      # what the compiler reports as one chip's HBM
CONFIGS = ["qwen3-4b", "h2o-danube-1.8b"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("name", CONFIGS)
def test_step_fits_one_v5e_as_reckoned(name, step, one_chip,
                                       no_persistent_cache):
    c = json.loads((ROOT / f"perfbench/configs/{name}.json").read_text())
    cfg, e = driver.model_config(c), c["engine"]
    ns = types.SimpleNamespace(cfg=cfg, moe_ctx=MoECtx(impl="dropping"),
                               policy=DtypePolicy.serve())
    params = _on(jax.eval_shape(lambda: weights.program_params(0, c)),
                 one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    slots = e["max_slots"]
    if step == "decode":
        caches = _on(jax.eval_shape(lambda: init_decode_caches(
            cfg, slots, e["s_max"], jnp.bfloat16)), one_chip)
        args = (params, i32((slots, 1)), caches, i32((slots,)))
        fn = functools.partial(ServingEngine._decode_fn, ns)
        want_args = c["bytes"]["resident"]
    else:
        args = (params, i32((slots, max(e["buckets"]))), i32((slots,)))
        fn = functools.partial(ServingEngine._prefill_fn, ns)
        want_args = c["bytes"]["params"]
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(want_args, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES
