"""Layer stack assembly: pattern-aware blocks + scan over layer groups.

Every architecture is a sequence of n_layers blocks whose kinds repeat with
period ``len(cfg.pattern)`` (e.g. gemma3: 5×local + 1×global; recurrent-
gemma: rglru, rglru, local).  The stack is executed as

    head blocks (unrolled)   — cfg.first_dense_layers (deepseek dense MLP)
    scan over n_periods      — ONE traced period regardless of depth, so the
                               HLO stays O(1) in n_layers (required to
                               compile 80-layer models for 512 devices)
    tail blocks (unrolled)   — n_layers % period remainder

Caches mirror this layout: {"head": [..], "stack": {slot_i: stacked}, "tail": [..]}.
Decode carries the stacked caches through the scan and writes each layer's
new rows in place at the layer index (:func:`stack_decode`), so a donated
cache is never copied.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (TIME_AXIS, attn_chunk_decode, attn_decode,
                        attn_forward, gqa_decode_ring, init_attention,
                        layer_view, ring_cache_from_prefill, window_for)
from .common import rms_norm
from .mlp import init_mlp, mlp_forward
from .moe import aux_load_balance_loss, init_moe, moe_forward
from .rglru import init_rglru, init_rglru_cache, rglru_decode, rglru_forward
from .ssm import init_ssm, init_ssm_cache, ssm_decode, ssm_forward

ATTN_KINDS = ("attn", "local", "global")


def _segments(P: int) -> int:
    """Divisor of P nearest to sqrt(P) (two-level remat scan split)."""
    import math
    best, target = 1, math.sqrt(P)
    for d in range(1, P + 1):
        if P % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return best


@dataclass(frozen=True)
class MoECtx:
    impl: str = "dropping"            # dense | dropping | ep_a2a
    mesh: Any = None
    batch_axes: tuple = ("data",)
    expert_axis: str = "model"
    # Activation sharding pin (PartitionSpec for (B, S, d) hiddens).  GSPMD
    # left alone re-shards the layer stack to batch-replicated/d-sharded —
    # killing data parallelism; this constraint holds batch on the data axes.
    x_spec: Any = None


def constrain_x(x, moe_ctx: "MoECtx"):
    if moe_ctx.x_spec is not None:
        return jax.lax.with_sharding_constraint(x, moe_ctx.x_spec)
    return x


def _uses_ring(cfg: ModelConfig, kind: str) -> bool:
    return kind == "local" or (kind == "attn" and cfg.attn_kind == "swa")


def layer_kinds(cfg: ModelConfig) -> list[str]:
    p = cfg.pattern
    return [p[i % len(p)] for i in range(cfg.n_layers)]


def stack_layout(cfg: ModelConfig) -> tuple[int, int, int]:
    """(head, n_periods, tail) block counts."""
    period = len(cfg.pattern)
    head = cfg.first_dense_layers
    rem = cfg.n_layers - head
    return head, rem // period, rem % period


# --------------------------------------------------------------------------
# single block
# --------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, use_moe: bool, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    p: dict = {"ln1": jnp.zeros((d,), dtype=dtype)}
    if kind in ATTN_KINDS:
        p["mixer"] = init_attention(ks[0], cfg, dtype)
    elif kind == "ssm":
        p["mixer"] = init_ssm(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    if kind != "ssm":
        p["ln2"] = jnp.zeros((d,), dtype=dtype)
        if use_moe:
            p["mlp"] = init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, dtype,
                                gated=not cfg.is_encoder_only)
    return p


def block_forward(bp: dict, x, cfg: ModelConfig, kind: str, positions,
                  use_moe: bool, moe_ctx: MoECtx,
                  want_cache: bool):
    """Full-sequence block.  Returns (x, cache_or_None, aux_loss)."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    cache = None
    if kind in ATTN_KINDS:
        if want_cache:
            mix, kv = attn_forward(bp["mixer"], h, cfg, kind, positions,
                                   return_kv=True)
            if cfg.use_mla:
                cache = kv
            elif _uses_ring(cfg, kind):
                cache = ring_cache_from_prefill(kv, window_for(cfg, kind))
            else:
                cache = kv
        else:
            mix = attn_forward(bp["mixer"], h, cfg, kind, positions)
    elif kind == "ssm":
        if want_cache:
            mix, cache = ssm_forward(bp["mixer"], h, cfg, return_state=True)
        else:
            mix = ssm_forward(bp["mixer"], h, cfg)
    else:  # rglru
        if want_cache:
            mix, cache = rglru_forward(bp["mixer"], h, cfg, return_state=True)
        else:
            mix = rglru_forward(bp["mixer"], h, cfg)
    x = x + mix.astype(x.dtype)
    aux = jnp.zeros((), dtype=jnp.float32)
    if "mlp" in bp:
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if use_moe:
            y, (probs, idx) = moe_forward(
                bp["mlp"], h2, cfg, impl=moe_ctx.impl, mesh=moe_ctx.mesh,
                batch_axes=moe_ctx.batch_axes, expert_axis=moe_ctx.expert_axis)
            aux = aux_load_balance_loss(
                probs.reshape(-1, cfg.n_experts), idx.reshape(-1, cfg.moe_top_k),
                cfg.n_experts)
        else:
            y = mlp_forward(bp["mlp"], h2)
        x = x + y.astype(x.dtype)
    return x, cache, aux


def block_decode(bp: dict, x, cache, cache_pos, cfg: ModelConfig, kind: str,
                 use_moe: bool, moe_ctx: MoECtx, layer=None):
    """One-token decode through a block.  ``cache`` is the block's own, or
    with ``layer`` the layer scan's stacked caches, of which only index
    ``layer`` is read and written.  Returns (x, updated cache): attention
    writes the new rows in place, recurrent state is replaced whole."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        if not cfg.use_mla and _uses_ring(cfg, kind):
            mix, new_cache = gqa_decode_ring(bp["mixer"], h, cache, cache_pos,
                                             cfg, window=window_for(cfg, kind),
                                             layer=layer)
        else:
            mix, new_cache = attn_decode(bp["mixer"], h, cache, cache_pos,
                                         cfg, kind, layer=layer)
    else:
        step = ssm_decode if kind == "ssm" else rglru_decode
        state = {n: layer_view(t, layer) for n, t in cache.items()}
        mix, new_state = step(bp["mixer"], h, state, cfg)
        if layer is None:
            new_cache = new_state
        else:
            new_cache = {n: jax.lax.dynamic_update_index_in_dim(
                t, new_state[n].astype(t.dtype), layer, 0)
                for n, t in cache.items()}
    x = x + mix.astype(x.dtype)
    if "mlp" in bp:
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if use_moe:
            y, _ = moe_forward(
                bp["mlp"], h2, cfg, impl=moe_ctx.impl, mesh=moe_ctx.mesh,
                batch_axes=moe_ctx.batch_axes, expert_axis=moe_ctx.expert_axis)
        else:
            y = mlp_forward(bp["mlp"], h2)
        x = x + y.astype(x.dtype)
    return x, new_cache


def supports_chunked_decode(cfg: ModelConfig) -> bool:
    """True when every layer of the stack can run :func:`block_chunk` —
    chunked prefill / prefix-offset prefill against a full-layout cache.
    Excludes ring-buffer (SWA/local) attention (a later chunk token
    overwrites the ring slot an earlier in-chunk query still needs),
    recurrent state (ssm/rglru need strictly sequential scans), encoder-only
    stacks (no decode cache), and non-token frontends."""
    if cfg.is_encoder_only or cfg.input_mode != "tokens":
        return False
    kinds = set(layer_kinds(cfg))
    if not all(k in ATTN_KINDS for k in kinds):
        return False
    return not any(_uses_ring(cfg, k) for k in kinds)


def block_chunk(bp: dict, x, cache, pos0, cfg: ModelConfig, kind: str,
                use_moe: bool, moe_ctx: MoECtx):
    """C-token chunk decode through a block (x: (B,C,d)).  Returns
    (x, new_cache).  Only attention kinds — see supports_chunked_decode."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    if kind not in ATTN_KINDS or (not cfg.use_mla and _uses_ring(cfg, kind)):
        raise ValueError(f"chunked decode unsupported for layer kind {kind}")
    mix, new_cache = attn_chunk_decode(bp["mixer"], h, cache, pos0, cfg, kind)
    x = x + mix.astype(x.dtype)
    if "mlp" in bp:
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if use_moe:
            y, _ = moe_forward(
                bp["mlp"], h2, cfg, impl=moe_ctx.impl, mesh=moe_ctx.mesh,
                batch_axes=moe_ctx.batch_axes, expert_axis=moe_ctx.expert_axis)
        else:
            y = mlp_forward(bp["mlp"], h2)
        x = x + y.astype(x.dtype)
    return x, new_cache


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, s_max: int,
                     dtype) -> dict:
    """Zero decode-cache for one block (shapes only — also used to build
    ShapeDtypeStructs for the dry-run)."""
    if kind in ATTN_KINDS:
        if cfg.use_mla:
            return {"latent": jnp.zeros((batch, s_max, cfg.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((batch, s_max, cfg.rope_head_dim), dtype)}
        w = window_for(cfg, kind)
        length = min(w, s_max) if _uses_ring(cfg, kind) and w else s_max
        shape = (batch, cfg.n_kv_heads, length, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "ssm":
        return init_ssm_cache(cfg, batch, dtype)
    return init_rglru_cache(cfg, batch, dtype)


# --------------------------------------------------------------------------
# full stack
# --------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype) -> dict:
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    use_moe = cfg.n_experts > 0
    keys = jax.random.split(key, 3)
    params: dict = {"head": [], "tail": []}
    hk = jax.random.split(keys[0], max(head, 1))
    for i in range(head):
        params["head"].append(init_block(hk[i], cfg, kinds[i],
                                         use_moe=False, dtype=dtype))
    if n_periods > 0:
        def init_period(k):
            sk = jax.random.split(k, len(cfg.pattern))
            return {f"slot_{i}": init_block(sk[i], cfg, kind, use_moe, dtype)
                    for i, kind in enumerate(cfg.pattern)}
        pk = jax.random.split(keys[1], n_periods)
        params["stack"] = jax.vmap(init_period)(pk)
    tk = jax.random.split(keys[2], max(tail, 1))
    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        params["tail"].append(init_block(tk[i], cfg, kind, use_moe, dtype))
    return params


def init_stack_cache(cfg: ModelConfig, batch: int, s_max: int, dtype) -> dict:
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    cache: dict = {"head": [], "tail": []}
    for i in range(head):
        cache["head"].append(init_block_cache(cfg, kinds[i], batch, s_max, dtype))
    if n_periods > 0:
        per = {f"slot_{i}": init_block_cache(cfg, kind, batch, s_max, dtype)
               for i, kind in enumerate(cfg.pattern)}
        cache["stack"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_periods,) + t.shape), per)
    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        cache["tail"].append(init_block_cache(cfg, kind, batch, s_max, dtype))
    return cache


def stack_forward(params: dict, x, cfg: ModelConfig, positions,
                  moe_ctx: MoECtx, *, want_cache: bool = False,
                  remat: bool = False):
    """Returns (x, caches_or_None, aux_total)."""
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    use_moe = cfg.n_experts > 0
    aux_total = jnp.zeros((), dtype=jnp.float32)
    caches: dict = {"head": [], "tail": []}

    for i in range(head):
        x, c, aux = block_forward(params["head"][i], x, cfg, kinds[i],
                                  positions, False, moe_ctx, want_cache)
        aux_total += aux
        if want_cache:
            caches["head"].append(c)

    if n_periods > 0:
        def period_fn(x, period_params):
            x = constrain_x(x, moe_ctx)
            aux_p = jnp.zeros((), dtype=jnp.float32)
            cc = {}
            for i, kind in enumerate(cfg.pattern):
                x, c, aux = block_forward(period_params[f"slot_{i}"], x, cfg,
                                          kind, positions, use_moe, moe_ctx,
                                          want_cache)
                aux_p += aux
                if want_cache:
                    cc[f"slot_{i}"] = c
            return x, aux_p, cc

        if remat:
            period_fn = jax.checkpoint(
                period_fn, policy=jax.checkpoint_policies.nothing_saveable)

        def scan_body(carry, period_params):
            x, aux_acc = carry
            x, aux_p, cc = period_fn(x, period_params)
            return (x, aux_acc + aux_p), (cc if want_cache else None)

        n_seg = _segments(n_periods) if (remat and not want_cache) else 1
        if n_seg > 1:
            # Two-level remat scan: the outer scan saves one carry per
            # segment; the checkpointed segment body's inner carries are
            # rematerialized only while that segment is differentiated.
            # Activation stash: O(P) carries -> O(n_seg + P/n_seg).
            seg_len = n_periods // n_seg
            seg_params = jax.tree.map(
                lambda t: t.reshape(n_seg, seg_len, *t.shape[1:]),
                params["stack"])

            @jax.checkpoint
            def seg_body(carry, seg_p):
                (x2, aux2), _ = jax.lax.scan(scan_body, carry, seg_p)
                return (x2, aux2), None

            (x, aux_total), _ = jax.lax.scan(seg_body, (x, aux_total),
                                             seg_params)
        else:
            (x, aux_total), stack_caches = jax.lax.scan(
                scan_body, (x, aux_total), params["stack"])
            if want_cache:
                caches["stack"] = stack_caches

    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        x, c, aux = block_forward(params["tail"][i], x, cfg, kind,
                                  positions, use_moe, moe_ctx, want_cache)
        aux_total += aux
        if want_cache:
            caches["tail"].append(c)

    return x, (caches if want_cache else None), aux_total


def stack_decode(params: dict, x, caches: dict, cache_pos, cfg: ModelConfig,
                 moe_ctx: MoECtx):
    """One-token decode through the whole stack.  Returns (x, new_caches).

    The stacked caches ride in the scan's carry with the layer index, and
    each layer writes only its new rows at that index: with the caches
    donated, the step updates them where they lie."""
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    use_moe = cfg.n_experts > 0
    new_caches: dict = {"head": [], "tail": []}

    for i in range(head):
        x, c = block_decode(params["head"][i], x, caches["head"][i], cache_pos,
                            cfg, kinds[i], False, moe_ctx)
        new_caches["head"].append(c)

    if n_periods > 0:
        def scan_body(carry, pp):
            x, layer, stack = carry
            x = constrain_x(x, moe_ctx)
            stack = dict(stack)
            for i, kind in enumerate(cfg.pattern):
                x, stack[f"slot_{i}"] = block_decode(
                    pp[f"slot_{i}"], x, stack[f"slot_{i}"], cache_pos, cfg,
                    kind, use_moe, moe_ctx, layer=layer)
            return (x, layer + 1, stack), None

        (x, _, new_caches["stack"]), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.int32), caches["stack"]),
            params["stack"])

    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        x, c = block_decode(params["tail"][i], x, caches["tail"][i], cache_pos,
                            cfg, kind, use_moe, moe_ctx)
        new_caches["tail"].append(c)

    return x, new_caches


def decode_rows_inside(cfg: ModelConfig, caches: dict, pos):
    """Whether some row's position (``pos``, (B,)) lies inside every
    attention cache of the stack: [0, T) of a full cache, >= 0 of a ring
    (which any such position maps into).  ``attention.write_rows`` points
    the writes of rows outside at such a row's."""
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    blocks = [(kinds[i], c, 0) for i, c in enumerate(caches["head"])]
    if n_periods > 0:
        blocks += [(kind, caches["stack"][f"slot_{i}"], 1)
                   for i, kind in enumerate(cfg.pattern)]
    blocks += [(cfg.pattern[i % len(cfg.pattern)], c, 0)
               for i, c in enumerate(caches["tail"])]
    inside = pos >= 0
    for kind, c, stacked in blocks:
        if kind in ATTN_KINDS and (cfg.use_mla or not _uses_ring(cfg, kind)):
            for name, t in c.items():
                inside &= pos < t.shape[TIME_AXIS[name] + stacked]
    return jnp.any(inside)


def stack_chunk(params: dict, x, caches: dict, pos0, cfg: ModelConfig,
                moe_ctx: MoECtx):
    """Chunked decode through the whole stack (same {head, scan, tail}
    traversal as stack_decode; x (B,C,d)).  Returns (x, new_caches)."""
    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)
    use_moe = cfg.n_experts > 0
    new_caches: dict = {"head": [], "tail": []}

    for i in range(head):
        x, c = block_chunk(params["head"][i], x, caches["head"][i], pos0,
                           cfg, kinds[i], False, moe_ctx)
        new_caches["head"].append(c)

    if n_periods > 0:
        def scan_body(x, inp):
            x = constrain_x(x, moe_ctx)
            pp, pc = inp
            ncs = {}
            for i, kind in enumerate(cfg.pattern):
                x, nc = block_chunk(pp[f"slot_{i}"], x, pc[f"slot_{i}"],
                                    pos0, cfg, kind, use_moe, moe_ctx)
                ncs[f"slot_{i}"] = nc
            return x, ncs

        x, stack_caches = jax.lax.scan(
            scan_body, x, (params["stack"], caches["stack"]))
        new_caches["stack"] = stack_caches

    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        x, c = block_chunk(params["tail"][i], x, caches["tail"][i], pos0,
                           cfg, kind, use_moe, moe_ctx)
        new_caches["tail"].append(c)

    return x, new_caches
