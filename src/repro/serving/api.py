"""One-call serving facade: build an engine around any architecture +
scheduler and serve a request list.

    from repro.serving.api import serve
    results = serve("qwen3-4b", scheduler="ewsjf", requests=reqs)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke_config
from ..core import EWSJFConfig, EWSJFScheduler, FCFSScheduler, Request, SJFScheduler
from ..models import init_params
from ..models.common import DtypePolicy
from .engine import EngineConfig, ServingEngine

SCHEDULERS = {
    "fcfs": lambda: FCFSScheduler(),
    "sjf": lambda: SJFScheduler(),
    "ewsjf": lambda: EWSJFScheduler(EWSJFConfig(min_history=8,
                                                reopt_interval=0.5)),
}

# Engine sizing at full width, for one 16 GB TPU v5e chip: qwen3-4b's bf16
# params take 8.82 GB and this cache 1.21 GB (147,456 B per token).  The
# eager slot writes can hold up to three cache copies at once, which still
# leaves headroom under the chip's 15.75 GB.
FULL_WIDTH_ENGINE = dict(max_slots=8, s_max=1024, kv_pool_tokens=8192,
                         buckets=(64, 128, 256, 512))


def serve(arch: str, requests: list[Request], *, scheduler: str = "ewsjf",
          smoke: bool = True, params=None,
          engine_config: Optional[EngineConfig] = None,
          admission=None, seed: int = 0) -> dict:
    """Serve ``requests`` to completion; returns {finished, stats, engine}.

    ``smoke`` serves the architecture's reduced CPU preset in float32;
    ``smoke=False`` serves its published widths in the serving dtype
    (bfloat16 params, compute and caches), sized by ``FULL_WIDTH_ENGINE``
    unless ``engine_config`` is given.  Params are random, from ``seed``.

    ``admission`` is an optional replica-facing SLO admission controller
    (see ``repro.cluster.AdmissionController``): over-budget sheddable
    requests are refused at ingress and reported in ``stats()['shed']``."""
    if smoke:
        cfg, dtype, policy = get_smoke_config(arch), jnp.float32, None
        default_engine = EngineConfig(max_slots=4, s_max=256,
                                      kv_pool_tokens=4096,
                                      buckets=(32, 64, 128, 256))
    else:
        cfg, dtype, policy = get_config(arch), jnp.bfloat16, DtypePolicy.serve()
        default_engine = EngineConfig(**FULL_WIDTH_ENGINE)
    if params is None:
        # jitted so the float32 draws fuse into the bf16 cast instead of
        # sitting whole in device memory next to the params built so far
        params = jax.jit(init_params, static_argnums=(1, 2))(
            jax.random.PRNGKey(seed), cfg, dtype)
    sched = SCHEDULERS[scheduler]()
    eng = ServingEngine(cfg, params, sched, engine_config or default_engine,
                        policy=policy, admission=admission)
    finished = eng.run(requests)
    return {"finished": finished, "stats": eng.stats(), "engine": eng}
