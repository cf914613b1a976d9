"""Model facade: init / train_loss / prefill / decode_step for every
assigned architecture, driven entirely by ModelConfig.

Inputs:
  * input_mode == "tokens"     : batch {"tokens": (B,S) i32, "labels": (B,S) i32}
  * input_mode == "embeddings" : batch {"embeddings": (B,S,d) bf16, "labels": ...}
    (VLM / audio frontends are stubs per the assignment — input_specs()
    provides precomputed patch/frame embeddings.)

The cross-entropy is computed in sequence chunks against a vocab-sharded
unembedding so the full (B,S,V) logits tensor never materializes (required:
gemma3's 262k vocab × 4k seq × 16 rows/device would be ~34 GB).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import DtypePolicy, embed_init, dense_init, rms_norm
from .transformer import (MoECtx, constrain_x, decode_rows_inside,
                          init_stack, init_stack_cache, stack_chunk,
                          stack_decode, stack_forward)

AUX_LOSS_WEIGHT = 0.01


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    p: dict = {"blocks": init_stack(ks[0], cfg, dtype),
               "final_norm": jnp.zeros((cfg.d_model,), dtype=dtype)}
    needs_embed = cfg.input_mode == "tokens" or not cfg.is_encoder_only
    if needs_embed:
        p["embed"] = embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype)
    if not cfg.tie_embeddings or not needs_embed:
        p["head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
    return p


def _unembed(params, cfg: ModelConfig):
    if "head" in params:
        return params["head"]
    return params["embed"].T                       # tied


def _embed_inputs(params, batch: dict, cfg: ModelConfig, compute_dtype):
    if cfg.input_mode == "embeddings":
        return batch["embeddings"].astype(compute_dtype)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(jnp.float32)
    return x.astype(compute_dtype)


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def chunked_cross_entropy(hidden, w_head, labels, *, chunk: int = 512,
                          softcap: float = 0.0) -> jnp.ndarray:
    """Mean CE over all positions, computed in sequence chunks with the
    one-hot-einsum label pick (shards cleanly over a vocab-partitioned head).
    hidden (B,S,d), w_head (d,V), labels (B,S)."""
    B, S, d = hidden.shape
    V = w_head.shape[1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nch = S // chunk
    h = hidden.reshape(B, nch, chunk, d).transpose(1, 0, 2, 3)
    y = labels.reshape(B, nch, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        h_c, y_c = inp                                    # (B,chunk,d), (B,chunk)
        logits = (h_c.astype(w_head.dtype) @ w_head).astype(jnp.float32)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)           # (B,chunk)
        onehot = jax.nn.one_hot(y_c, V, dtype=logits.dtype)
        ll = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return acc + jnp.sum(lse - ll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, y))
    return total / (B * S)


def train_loss(params, batch: dict, cfg: ModelConfig,
               moe_ctx: MoECtx = MoECtx(), *,
               policy: DtypePolicy = DtypePolicy.train(),
               remat: bool = True) -> jnp.ndarray:
    x = constrain_x(_embed_inputs(params, batch, cfg, policy.compute), moe_ctx)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cast = jax.tree.map(lambda t: t.astype(policy.compute)
                        if t.dtype == jnp.float32 and t.ndim >= 2 else t,
                        params["blocks"])
    h, _, aux = stack_forward(cast, x, cfg, positions, moe_ctx, remat=remat)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w_head = _unembed(params, cfg).astype(policy.compute)
    loss = chunked_cross_entropy(h, w_head, batch["labels"],
                                 softcap=cfg.logit_softcap)
    return loss + AUX_LOSS_WEIGHT * aux


# --------------------------------------------------------------------------
# serving steps
# --------------------------------------------------------------------------

def prefill(params, batch: dict, cfg: ModelConfig,
            moe_ctx: MoECtx = MoECtx(), *,
            policy: DtypePolicy = DtypePolicy.serve()):
    """Full-prompt forward.  Returns (last-position logits, caches).
    Encoder-only models return per-position logits and no cache."""
    x = constrain_x(_embed_inputs(params, batch, cfg, policy.compute), moe_ctx)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    want_cache = not cfg.is_encoder_only
    h, caches, _ = stack_forward(params["blocks"], x, cfg, positions, moe_ctx,
                                 want_cache=want_cache)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w_head = _unembed(params, cfg)
    if cfg.is_encoder_only:
        logits = (h.astype(w_head.dtype) @ w_head).astype(jnp.float32)
        return logits, None
    logits = (h[:, -1:].astype(w_head.dtype) @ w_head).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, caches


def decode_step(params, tokens, caches, cache_pos, cfg: ModelConfig,
                moe_ctx: MoECtx = MoECtx(), *,
                policy: DtypePolicy = DtypePolicy.serve()):
    """One token for every sequence.  tokens (B,1) i32; cache_pos scalar i32
    or (B,) per-row positions (tokens already in each row's cache).
    Returns (logits (B,1,V), new caches): each row's new entries written in
    place, none for a row whose position lies outside the cache."""
    def step(caches):
        x = jnp.take(params["embed"], tokens, axis=0).astype(policy.compute)
        if cfg.tie_embeddings:
            x = x * jnp.sqrt(float(cfg.d_model)).astype(policy.compute)
        h, new_caches = stack_decode(params["blocks"], x, caches, cache_pos,
                                     cfg, moe_ctx)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        w_head = _unembed(params, cfg)
        logits = (h.astype(w_head.dtype) @ w_head).astype(jnp.float32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits, new_caches

    pos = jnp.asarray(cache_pos, jnp.int32)
    if pos.ndim == 0:
        return step(caches)
    # Rows outside the caches write nothing only beside a row inside them
    # (attention.write_rows); with none, the step leaves the caches alone.
    def skip(caches):
        return jnp.zeros(tokens.shape + (cfg.vocab_size,), jnp.float32), caches
    return jax.lax.cond(decode_rows_inside(cfg, caches, pos), step, skip,
                        caches)


def chunk_step(params, tokens, caches, pos0, cfg: ModelConfig,
               moe_ctx: MoECtx = MoECtx(), *,
               policy: DtypePolicy = DtypePolicy.serve()):
    """Prefill one C-token chunk against existing decode caches.

    tokens (B,C) i32; ``pos0`` scalar i32 — tokens already resident in every
    row's cache (the chunk occupies absolute positions pos0..pos0+C-1).
    Returns (logits at the chunk's last position, (B,1,V) f32, new caches).
    With pos0=0 and C=prompt_len this is a whole prefill; with C=1 it is
    decode_step — the engine uses it for both chunked prefill and
    prefix-offset (radix-reuse) prefill.  Requires
    ``transformer.supports_chunked_decode(cfg)``."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(policy.compute)
    if cfg.tie_embeddings:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(policy.compute)
    h, new_caches = stack_chunk(params["blocks"], x, caches, pos0,
                                cfg, moe_ctx)
    h = rms_norm(h[:, -1:], params["final_norm"], cfg.norm_eps)
    w_head = _unembed(params, cfg)
    logits = (h.astype(w_head.dtype) @ w_head).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, new_caches


def init_decode_caches(cfg: ModelConfig, batch: int, s_max: int,
                       dtype=jnp.bfloat16) -> dict:
    return init_stack_cache(cfg, batch, s_max, dtype)


def pad_prefill_caches(caches: dict, cfg: ModelConfig, target_len: int) -> dict:
    """Grow prefill caches (seq length S) to a decode capacity ``target_len``:
    full/MLA caches get zero-padding on the sequence axis; ring caches grow
    to the window size (slot semantics preserved — see gqa_decode_ring);
    SSM/RG-LRU states are O(1) and pass through."""
    from .attention import TIME_AXIS, window_for
    from .transformer import _uses_ring, layer_kinds, stack_layout

    head, n_periods, tail = stack_layout(cfg)
    kinds = layer_kinds(cfg)

    def pad_entry(c: dict, kind: str, stacked: bool) -> dict:
        if kind not in ("attn", "local", "global"):
            return c
        if not cfg.use_mla and _uses_ring(cfg, kind):
            w = window_for(cfg, kind)
            tgt = min(w, target_len) if w else target_len
        else:
            tgt = target_len
        out = {}
        for name, t in c.items():
            ax = TIME_AXIS[name] + int(stacked)
            pad = tgt - t.shape[ax]
            if pad > 0:
                widths = [(0, 0)] * t.ndim
                widths[ax] = (0, pad)
                t = jnp.pad(t, widths)
            out[name] = t
        return out

    new: dict = {"head": [], "tail": []}
    for i in range(head):
        new["head"].append(pad_entry(caches["head"][i], kinds[i], False))
    if n_periods > 0:
        new["stack"] = {
            f"slot_{i}": pad_entry(caches["stack"][f"slot_{i}"], kind, True)
            for i, kind in enumerate(cfg.pattern)}
    for i in range(tail):
        kind = cfg.pattern[i % len(cfg.pattern)]
        new["tail"].append(pad_entry(caches["tail"][i], kind, False))
    return new
