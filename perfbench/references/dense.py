"""Plain forward of the dense decoder family, independent of the program.

RMSNorm, rotary positions (rotate-half, as the Hugging Face Llama, Mistral
and Qwen3 code), grouped-query attention with optional per-head query/key
RMSNorm (Qwen3) and an optional sliding window (Mistral), and a SwiGLU MLP.
No cache and no batching tricks: one causal pass over each whole sequence.

``mode`` "f32" is the reference: float32 with every matrix product at
"highest" precision.  ``mode`` "fp8" is the control: every operand of every
matrix product rounded to float8 e4m3 with one absmax scale per tensor, the
rest as in "f32".  Weights come from ``perfbench.weights`` and the seed, one
layer at a time, so the whole pass fits beside little else on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, mode: str):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + offset)


def _rope(x, pos, theta):
    """x (B, S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _layer(x, key, index, cfg_items, mode):
    cfg = dict(cfg_items)
    w = jax.tree.map(lambda t: t.astype(jnp.float32),
                     weights.layer(key, cfg, index, jnp.bfloat16))
    B, S, _ = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], eps)
    q = _mm("bsd,de->bse", h, w["wq"], mode).reshape(B, S, H, hd)
    k = _mm("bsd,de->bse", h, w["wk"], mode).reshape(B, S, K, hd)
    v = _mm("bsd,de->bse", h, w["wv"], mode).reshape(B, S, K, hd)
    if cfg["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, mode) / np.sqrt(hd)
    allowed = pos[None, :] <= pos[:, None]
    if cfg["sliding_window"]:
        allowed &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    s = jnp.where(allowed, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, mode).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, w["wo"], mode)
    h = _rms(x, w["ln2"], eps)
    g = _mm("bsd,df->bsf", h, w["w_gate"], mode)
    u = _mm("bsd,df->bsf", h, w["w_up"], mode)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key, cfg_items):
    return weights.reference_embed(key, dict(cfg_items), jnp.bfloat16)[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _readout(h, key, targets, cfg_items, mode):
    """Per position of one sequence: the top logit, the logits' standard
    deviation, the logit of each row of ``targets`` and the top token."""
    cfg = dict(cfg_items)
    offset, w = weights.reference_readout(key, cfg, jnp.bfloat16)
    h = _rms(h, offset, cfg["rms_norm_eps"])
    logits = _mm("sd,dv->sv", h, w, mode)
    at = jnp.take_along_axis(logits[None], targets[..., None], axis=-1)[..., 0]
    return (logits.max(-1), logits.std(-1), at,
            jnp.argmax(logits, -1).astype(jnp.int32))


def hidden(seed: int, cfg: dict, tokens: np.ndarray, mode: str = "f32"):
    """Final hidden states (B, S, d) of ``tokens`` (B, S), before the norm."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, type(None)))))
    key = weights.root_key(seed)
    x = _embed(jnp.asarray(tokens), key, items)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, key, i, items, mode)
    return x


def readout(seed: int, cfg: dict, h, targets: np.ndarray, mode: str = "f32"):
    """Apply :func:`_readout` to each sequence of ``h``; ``targets`` is
    (B, T, S) token ids.  Returns numpy (top, std, at (B, T, S), argmax)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, type(None)))))
    key = weights.root_key(seed)
    outs = [jax.device_get(_readout(h[b], key, jnp.asarray(targets[b]), items,
                                    mode))
            for b in range(h.shape[0])]
    return tuple(np.stack([o[i] for o in outs]) for i in range(4))
