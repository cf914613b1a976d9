"""Attention variants for the assigned architectures.

* GQA/MHA/MQA with RoPE — qwen3 (qk_norm), phi3.5, gemma3, h2o-danube (SWA),
  internvl2, hubert (bidirectional), recurrentgemma (MQA local).
* MLA (multi-head latent attention) — deepseek-v2-lite, minicpm3.  The KV
  cache holds the compressed latent (r + rope_dim per token); decode uses the
  *absorbed* formulation (q projected through W_uk so scores hit the latent
  directly) — the memory-bandwidth win MLA exists for.

All softmax math in fp32 (DtypePolicy.accum); everything else in the compute
dtype.  Shapes: x (B, S, d); caches are contiguous — GQA keys and values
head-major (B, K, S_max, hd), the layout the decode contraction reads, MLA
latents (B, S_max, r) — and decode writes each row's new entry in place
(:func:`write_rows`).  The paged path lives in serving/kv_cache.py +
kernels/paged_attention.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# §Perf flag (EXPERIMENTS.md): K/V of prefill attention are born sharded on
# the flattened K·hd dim (column-sharded wk/wv); every blockwise q-block
# then re-gathers them — 36 layers x 64 blocks = 1.3 TB/chip of all-gathers
# at 32k.  Constraining K/V replicated-over-model (batch stays sharded)
# gathers them ONCE per layer; q stays head-sharded, scores/outputs stay
# distributed.  kv_heads <= TP for every assigned arch, so no memory cost
# beyond the vanilla TP-attention layout.
_OPT_KV_REPLICATE = os.environ.get("REPRO_BLOCKWISE_OPT", "0") == "1"

from ..configs.base import ModelConfig
from .common import DtypePolicy, apply_rope, attention_mask, dense_init, rms_norm


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.use_mla:
        r, rd, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.v_head_dim
        p = {
            "wq": dense_init(ks[0], d, H * (hd + rd), dtype),
            "w_dkv": dense_init(ks[1], d, r, dtype),
            "w_krope": dense_init(ks[2], d, rd, dtype),
            "w_uk": dense_init(ks[3], r, H * hd, dtype),
            "w_uv": dense_init(ks[4], r, H * vd, dtype),
            "wo": dense_init(ks[5], H * vd, d, dtype),
            "kv_norm": jnp.zeros((r,), dtype=dtype),
        }
    else:
        p = {
            "wq": dense_init(ks[0], d, H * hd, dtype),
            "wk": dense_init(ks[1], d, K * hd, dtype),
            "wv": dense_init(ks[2], d, K * hd, dtype),
            "wo": dense_init(ks[3], H * hd, d, dtype),
        }
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((hd,), dtype=dtype)
            p["k_norm"] = jnp.zeros((hd,), dtype=dtype)
    return p


def kv_cache_spec(cfg: ModelConfig, batch: int, s_max: int, dtype):
    """Shape (as jax.ShapeDtypeStruct-compatible tuples) of one layer's
    decode cache."""
    if cfg.use_mla:
        return {"latent": ((batch, s_max, cfg.kv_lora_rank), dtype),
                "k_rope": ((batch, s_max, cfg.rope_head_dim), dtype)}
    return {"k": ((batch, cfg.n_kv_heads, s_max, cfg.head_dim), dtype),
            "v": ((batch, cfg.n_kv_heads, s_max, cfg.head_dim), dtype)}


# Time (sequence) axis of each decode-cache leaf within one layer's cache,
# batch first: GQA k/v (B, K, T, hd), MLA latent/k_rope (B, T, r).
TIME_AXIS = {"k": 2, "v": 2, "latent": 1, "k_rope": 1}


def layer_view(buf, layer):
    """One layer's cache: ``buf`` itself, or its ``layer`` index along a
    stacked (L, ...) buffer."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def write_rows(buf, rows, pos, t_axis: int, layer=None):
    """Write each batch row's entry for one position into a decode cache.

    ``buf`` is one layer's cache (B, ...) or, with ``layer``, the stacked
    (L, B, ...) cache of a layer scan; ``rows`` (B, ...) is one position's
    entry (the layer shape without its time axis ``t_axis``); ``pos`` holds
    each row's time index (B,), or one index shared by every row (the dry
    run's), which the write clamps into the cache.  The writes are
    dynamic-update-slices of single rows, so a donated or loop-carried
    buffer is updated where it lies and nothing else of it is copied.

    A row whose index lies outside [0, T) writes nothing: its write is
    pointed at that of a row inside, with that row's values.  The buffer is
    never read here (a read makes the compiler relayout the whole cache),
    so some row must lie inside; ``decode_step`` skips a step where none
    does."""
    lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)
    T = buf.shape[len(lead) + t_axis]
    rows = jnp.expand_dims(rows.astype(buf.dtype), t_axis)
    pos = jnp.asarray(pos, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def put(buf, upd, b, p):
        start = [zero] * upd.ndim
        start[t_axis] = p
        upd = upd.reshape((1,) * len(lead) + upd.shape)
        return jax.lax.dynamic_update_slice(buf, upd,
                                            lead + (b,) + tuple(start[1:]))

    if pos.ndim == 0:
        return put(buf, rows, zero, pos)
    inside = (pos >= 0) & (pos < T)
    src = jnp.where(inside, jnp.arange(pos.shape[0]), jnp.argmax(inside))
    rows, pos = rows[src], pos[src]
    for b in range(rows.shape[0]):
        buf = put(buf, rows[b:b + 1], src[b], pos[b])
    return buf


# --------------------------------------------------------------------------
# GQA path
# --------------------------------------------------------------------------

def _qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (x @ params["wk"]).reshape(B, S, K, hd)
    v = (x @ params["wv"]).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend(q, k, v, mask):
    """q (B,S,H,hd), head-major k/v (B,K,T,hd), mask (S,T) or
    (B,1,1,S,T)."""
    B, S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bskgh,bkth->bkgst", qg, k) * scale
    scores = scores.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bkth->bskgh", probs, v)
    return out.reshape(B, S, H * hd)


def _head_major(t):
    """(B, S, K, hd) -> (B, K, S, hd)."""
    return t.transpose(0, 2, 1, 3)


BLOCKWISE_THRESHOLD = 2048     # use blockwise attention when S exceeds this


def gqa_forward(params, x, cfg: ModelConfig, *, window: int,
                positions, causal: bool = True, return_kv: bool = False):
    """Full-sequence attention (train / prefill).  ``return_kv`` also
    returns the keys and values head-major, as the decode cache holds
    them."""
    from .blockwise import blockwise_gqa_attend
    q, k, v = _qkv(params, x, cfg, positions)
    S = x.shape[1]
    if S > BLOCKWISE_THRESHOLD:
        if _OPT_KV_REPLICATE:
            from jax.sharding import PartitionSpec as P
            U = P.UNCONSTRAINED
            k = jax.lax.with_sharding_constraint(k, P(U, None, None, None))
            v = jax.lax.with_sharding_constraint(v, P(U, None, None, None))
        out = blockwise_gqa_attend(q, k, v, causal=causal, window=window)
        k, v = _head_major(k), _head_major(v)
    else:
        k, v = _head_major(k), _head_major(v)
        mask = attention_mask(S, S, causal=causal, window=window)
        out = gqa_attend(q, k, v, mask)
    y = out @ params["wo"]
    if return_kv:
        return y, {"k": k, "v": v}
    return y


def _pos_vec(cache_pos, B):
    """Per-row positions (B,) from a scalar (dry-run serve_step) or a (B,)
    vector (slot-based engine, sequences at different lengths)."""
    p = jnp.asarray(cache_pos, dtype=jnp.int32)
    return jnp.full((B,), p, jnp.int32) if p.ndim == 0 else p


def _decode_qkv(params, x, posv, cfg: ModelConfig):
    """One token's query (B,1,H,hd) and new key/value rows (B,K,hd)."""
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, 1, H, hd)
    k_new = (x @ params["wk"]).reshape(B, 1, K, hd)
    v_new = (x @ params["wv"]).reshape(B, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, params["k_norm"], cfg.norm_eps)
    pos = posv[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k_new = apply_rope(k_new, pos, cfg.rope_theta)
    return q, k_new[:, 0], v_new


def gqa_decode(params, x, cache: dict, cache_pos, cfg: ModelConfig,
               *, window: int, layer=None):
    """Single-token decode.  x (B,1,d); cache k/v (B,K,S_max,hd), or the
    layer scan's stacked (L,B,K,S_max,hd) with ``layer``; cache_pos: scalar
    int or (B,) vector — tokens already in each cache.  Returns the output
    and the cache with each row's new key and value written in place."""
    posv = _pos_vec(cache_pos, x.shape[0])
    q, k_new, v_new = _decode_qkv(params, x, posv, cfg)
    k = write_rows(cache["k"], k_new, cache_pos, 2, layer)
    v = write_rows(cache["v"], v_new, cache_pos, 2, layer)
    T = k.shape[-2]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    mask = k_pos <= posv[:, None]                       # (B,T) causal
    if window and window > 0:
        mask &= k_pos > (posv[:, None] - window)
    out = gqa_attend(q, layer_view(k, layer), layer_view(v, layer),
                     mask[:, None, None, None, :])
    y = out @ params["wo"]
    return y, {"k": k, "v": v}


def gqa_decode_ring(params, x, cache: dict, cache_pos, cfg: ModelConfig,
                    *, window: int, layer=None):
    """Single-token decode with a *ring-buffer* window cache — the memory
    win that makes SWA/local layers O(window) instead of O(seq) in the
    long_500k cell.  cache k/v: (B, K, W, hd) (stacked as in
    :func:`gqa_decode`), slot = abs_pos % W, keys are stored post-RoPE so no
    re-rotation is needed.  A negative position writes nothing."""
    W = cache["k"].shape[-2]
    posv = _pos_vec(cache_pos, x.shape[0])
    q, k_new, v_new = _decode_qkv(params, x, posv, cfg)
    p = jnp.asarray(cache_pos, jnp.int32)
    slot = jnp.where(p < 0, -1, jnp.mod(p, W))
    k = write_rows(cache["k"], k_new, slot, 2, layer)
    v = write_rows(cache["v"], v_new, slot, 2, layer)
    # slot s holds absolute position pos - ((pos - s) mod W); valid if >= 0.
    pos = posv[:, None]
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    abs_pos = pos - jnp.mod(pos - s_idx, W)                 # (B, W)
    mask = abs_pos >= 0
    out = gqa_attend(q, layer_view(k, layer), layer_view(v, layer),
                     mask[:, None, None, None, :])
    y = out @ params["wo"]
    return y, {"k": k, "v": v}


def ring_cache_from_prefill(kv: dict, window: int) -> dict:
    """Convert full prefill k/v (B, K, S, hd) into ring-buffer layout."""
    out = {}
    for name in ("k", "v"):
        t = kv[name]
        S = t.shape[2]
        W = min(window, S) if window else S
        last = t[:, :, S - W:, :]
        shift = (S - W) % W if W else 0
        out[name] = jnp.roll(last, shift=shift, axis=2)
    return out


# --------------------------------------------------------------------------
# MLA path
# --------------------------------------------------------------------------

def mla_forward(params, x, cfg: ModelConfig, *, positions,
                causal: bool = True, window: int = 0, return_kv: bool = False):
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    r, rd, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.v_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    c = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)  # (B,S,r)
    k_rope = (x @ params["w_krope"]).reshape(B, S, 1, rd)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    k_nope = (c @ params["w_uk"]).reshape(B, S, H, hd)
    v = (c @ params["w_uv"]).reshape(B, S, H, vd)
    scale = (hd + rd) ** -0.5
    if S > BLOCKWISE_THRESHOLD:
        # Fold MLA into MHA form (q/k = [nope ‖ rope]) and reuse the
        # blockwise online-softmax path.
        from .blockwise import blockwise_gqa_attend
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1)
        if _OPT_KV_REPLICATE:
            from jax.sharding import PartitionSpec as P
            U = P.UNCONSTRAINED
            k_full = jax.lax.with_sharding_constraint(
                k_full, P(U, None, None, None))
            v = jax.lax.with_sharding_constraint(v, P(U, None, None, None))
        out = blockwise_gqa_attend(q_full, k_full, v, causal=causal,
                                   window=window, scale=scale)
    else:
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshd,btzd->bhst", q_rope,
                               k_rope)) * scale
        mask = attention_mask(S, S, causal=causal, window=window)
        scores = jnp.where(mask, scores.astype(jnp.float32),
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, H * vd)
    y = out @ params["wo"]
    if return_kv:
        return y, {"latent": c, "k_rope": k_rope[:, :, 0, :]}
    return y


def mla_decode(params, x, cache: dict, cache_pos, cfg: ModelConfig,
               *, window: int = 0, layer=None):
    """Absorbed-MLA decode: scores hit the cached latent directly —
    q_eff = q_nope @ W_uk (per head) → (B,H,r); attention over latent (B,T,r);
    output = (probs @ latent) @ W_uv.  KV traffic = r + rd per token instead
    of 2·H·hd — the MLA serving win.  Stacked caches with ``layer`` as in
    :func:`gqa_decode`."""
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim
    r, rd, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.v_head_dim
    posv = _pos_vec(cache_pos, B)
    pos = posv[:, None]
    q = (x @ params["wq"]).reshape(B, 1, H, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)[:, 0]     # (B,H,rd)
    c_new = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)
    k_rope_new = apply_rope((x @ params["w_krope"]).reshape(B, 1, 1, rd),
                            pos, cfg.rope_theta)[:, 0, 0]      # (B,rd)
    latent_buf = write_rows(cache["latent"], c_new[:, 0], cache_pos, 1,
                            layer)
    k_rope_buf = write_rows(cache["k_rope"], k_rope_new, cache_pos, 1, layer)
    latent = layer_view(latent_buf, layer)
    k_rope = layer_view(k_rope_buf, layer)
    # absorb: q_eff[b,h,r] = q_nope[b,h,:] @ W_uk[:, h, :]  (W_uk: (r, H, hd))
    w_uk = params["w_uk"].reshape(r, H, hd)
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    scale = (hd + rd) ** -0.5
    scores = (jnp.einsum("bhr,btr->bht", q_eff, latent)
              + jnp.einsum("bhd,btd->bht", q_rope, k_rope)) * scale
    T = latent.shape[1]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    mask = k_pos <= posv[:, None]                              # (B,T)
    if window and window > 0:
        mask &= k_pos > (posv[:, None] - window)
    scores = jnp.where(mask[:, None, :], scores.astype(jnp.float32),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(latent.dtype)
    ctx = jnp.einsum("bht,btr->bhr", probs, latent)            # (B,H,r)
    w_uv = params["w_uv"].reshape(r, H, vd)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv).reshape(B, 1, H * vd)
    y = out @ params["wo"]
    return y, {"latent": latent_buf, "k_rope": k_rope_buf}


# --------------------------------------------------------------------------
# chunked decode (multi-token prefill against an existing cache)
# --------------------------------------------------------------------------

def gqa_chunk_decode(params, x, cache: dict, pos0, cfg: ModelConfig,
                     *, window: int = 0):
    """Process one contiguous C-token span against an existing full-layout
    cache: write K/V at absolute positions ``pos0 .. pos0+C-1``, attend
    causally over everything resident up to each query.  This is the one
    primitive both chunked prefill and radix prefix reuse need — a prefill
    that *starts at an offset* (pos0=0 degrades to plain prefill; C=1 to
    single-token decode).  x (B,C,d); cache k/v (B,K,S_max,hd); pos0 is a
    scalar shared by every row (the engine runs one slot per chunk call).
    Ring-buffer (windowed) caches are NOT supported: a later chunk token
    would overwrite the ring slot an earlier in-chunk query still needs —
    the engine gates on ``supports_chunked_decode``."""
    B, C, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p0 = jnp.asarray(pos0, jnp.int32).reshape(())
    positions = p0 + jnp.arange(C, dtype=jnp.int32)            # (C,)
    pos_b = jnp.broadcast_to(positions[None], (B, C))
    q = (x @ params["wq"]).reshape(B, C, H, hd)
    k_new = (x @ params["wk"]).reshape(B, C, K, hd)
    v_new = (x @ params["wv"]).reshape(B, C, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k_new = apply_rope(k_new, pos_b, cfg.rope_theta)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], _head_major(k_new).astype(cache["k"].dtype), p0, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], _head_major(v_new).astype(cache["v"].dtype), p0, axis=2)
    T = k.shape[2]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (C, T), 1)
    mask = k_pos <= positions[:, None]                         # (C,T) causal
    if window and window > 0:
        mask &= k_pos > (positions[:, None] - window)
    out = gqa_attend(q, k, v, mask)
    y = out @ params["wo"]
    return y, {"k": k, "v": v}


def mla_chunk_decode(params, x, cache: dict, pos0, cfg: ModelConfig,
                     *, window: int = 0):
    """Chunked absorbed-MLA decode (see :func:`gqa_chunk_decode` for the
    contract): write C latent rows at ``pos0..pos0+C-1``, score every
    in-chunk query against the cached latent directly."""
    B, C, _ = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    r, rd, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.v_head_dim
    p0 = jnp.asarray(pos0, jnp.int32).reshape(())
    positions = p0 + jnp.arange(C, dtype=jnp.int32)
    pos_b = jnp.broadcast_to(positions[None], (B, C))
    q = (x @ params["wq"]).reshape(B, C, H, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, pos_b, cfg.rope_theta)         # (B,C,H,rd)
    c_new = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)
    k_rope_new = apply_rope((x @ params["w_krope"]).reshape(B, C, 1, rd),
                            pos_b, cfg.rope_theta)[:, :, 0]    # (B,C,rd)
    latent = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], c_new.astype(cache["latent"].dtype), p0, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), p0, axis=1)
    w_uk = params["w_uk"].reshape(r, H, hd)
    q_eff = jnp.einsum("bchd,rhd->bchr", q_nope, w_uk)
    scale = (hd + rd) ** -0.5
    scores = (jnp.einsum("bchr,btr->bhct", q_eff, latent)
              + jnp.einsum("bchd,btd->bhct", q_rope, k_rope)) * scale
    T = latent.shape[1]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (C, T), 1)
    mask = k_pos <= positions[:, None]
    if window and window > 0:
        mask &= k_pos > (positions[:, None] - window)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(latent.dtype)
    ctx = jnp.einsum("bhct,btr->bchr", probs, latent)
    w_uv = params["w_uv"].reshape(r, H, vd)
    out = jnp.einsum("bchr,rhd->bchd", ctx, w_uv).reshape(B, C, H * vd)
    y = out @ params["wo"]
    return y, {"latent": latent, "k_rope": k_rope}


# --------------------------------------------------------------------------
# dispatch by config
# --------------------------------------------------------------------------

def window_for(cfg: ModelConfig, kind: str) -> int:
    if kind == "local":
        return cfg.window
    if kind == "global":
        return 0
    if cfg.attn_kind == "swa":
        return cfg.window
    return 0


def attn_forward(params, x, cfg: ModelConfig, kind: str, positions,
                 return_kv: bool = False):
    w = window_for(cfg, kind)
    if cfg.use_mla:
        return mla_forward(params, x, cfg, positions=positions,
                           causal=cfg.causal, window=w, return_kv=return_kv)
    return gqa_forward(params, x, cfg, window=w, positions=positions,
                       causal=cfg.causal, return_kv=return_kv)


def attn_decode(params, x, cache, cache_pos, cfg: ModelConfig, kind: str,
                layer=None):
    w = window_for(cfg, kind)
    if cfg.use_mla:
        return mla_decode(params, x, cache, cache_pos, cfg, window=w,
                          layer=layer)
    return gqa_decode(params, x, cache, cache_pos, cfg, window=w, layer=layer)


def attn_chunk_decode(params, x, cache, pos0, cfg: ModelConfig, kind: str):
    w = window_for(cfg, kind)
    if cfg.use_mla:
        return mla_chunk_decode(params, x, cache, pos0, cfg, window=w)
    return gqa_chunk_decode(params, x, cache, pos0, cfg, window=w)
