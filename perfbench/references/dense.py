"""The dense decoder family: its program config, weights, counts and plain
forward.

RMSNorm, rotary positions (rotate-half, as the Hugging Face Llama, Mistral
and Qwen3 code), grouped-query attention with optional per-head query/key
RMSNorm (Qwen3) and an optional sliding window (Mistral), and a SwiGLU MLP
in every layer.

A family module is what a configuration names under ``reference``;
``perfbench.check.reference`` finds it as ``perfbench/references/<name>.py``
and is the only lookup.  Every part of the harness that depends on the
architecture goes through it, so a new family is one new file beside this
one.  Each function takes the configuration dict ``c``:

- ``model_config(c)``: the engine's ``repro.configs.base.ModelConfig``;
- ``program_params(seed, c, dtype)``: the parameter tree in the engine's
  layout, in ``dtype``, made on the default device in one jitted call;
- ``param_count(c)``: the parameters that tree holds;
- ``weight_bytes(c)``, ``kv_bytes_per_token(c)``, ``decode_flops(c,
  positions)``, ``decode_bytes(c, positions)``, ``prefill_flops(c, lens)``
  and ``prefill_bytes(c, lens)``: the algorithm's work, which
  ``perfbench.roofline`` forwards to the metric readers;
- ``hidden(seed, c, tokens, mode)`` and ``readout(seed, c, h, targets,
  mode)``: the plain reference that decides ``correct``
  (``perfbench.check``).

Weights come from ``perfbench.weights``' rule and the seed: the program's
tree here in one call, the reference's one layer at a time, so the whole
pass fits beside little else on the chip.  The reference imports nothing of
the program; ``model_config`` alone imports the program's config class, to
build the system under test.

``mode`` "f32" is the reference: float32 with every matrix product at
"highest" precision.  ``mode`` "fp8" is the control: every operand of every
matrix product rounded to float8 e4m3 with one absmax scale per tensor, the
rest as in "f32".  No cache and no batching tricks: one causal pass over
each whole sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.roofline import DTYPE_BYTES, head_params

# Leaf ids of a layer (``perfbench.weights`` keeps 1-3).
LEAF_IDS = {"ln1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14, "q_norm": 15,
            "k_norm": 16, "ln2": 17, "w_gate": 18, "w_up": 19, "w_down": 20}


# ---- the program's config and weights --------------------------------------

def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    window = c["sliding_window"]
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=c["qk_norm"], attn_kind="swa" if window else "full",
        window=window or 0, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        max_seq_len=c["max_position_embeddings"])


def layer_shapes(c: dict) -> dict:
    """{leaf: (shape, std)} of one decoder layer; std None marks a norm."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    out = {"ln1": ((d,), None),
           "wq": ((d, H * hd), d ** -0.5),
           "wk": ((d, K * hd), d ** -0.5),
           "wv": ((d, K * hd), d ** -0.5),
           "wo": ((H * hd, d), (H * hd) ** -0.5),
           "ln2": ((d,), None),
           "w_gate": ((d, ff), d ** -0.5),
           "w_up": ((d, ff), d ** -0.5),
           "w_down": ((ff, d), ff ** -0.5)}
    if c["qk_norm"]:
        out["q_norm"] = ((hd,), None)
        out["k_norm"] = ((hd,), None)
    return out


def layer(key, c: dict, index, dtype) -> dict:
    """One layer's leaves (``index`` may be traced)."""
    k = jax.random.fold_in(key, index)
    return {name: weights.draw(k, LEAF_IDS[name], shape, std, dtype)
            for name, (shape, std) in layer_shapes(c).items()}


def program_params(seed: int, c: dict, dtype=jnp.bfloat16) -> dict:
    """The whole tree in the layout the engine reads (``repro.models``:
    one scanned period of one attention block)."""

    def make(key):
        stack = jax.vmap(lambda i: layer(key, c, i, dtype))(
            jnp.arange(c["num_hidden_layers"]))
        block = {"ln1": stack["ln1"], "ln2": stack["ln2"],
                 "mixer": {n: stack[n] for n in ("wq", "wk", "wv", "wo",
                                                  "q_norm", "k_norm")
                           if n in stack},
                 "mlp": {n: stack[n] for n in ("w_gate", "w_up", "w_down")}}
        return {"blocks": {"head": [], "stack": {"slot_0": block}, "tail": []},
                **weights.outer(key, c, dtype)}

    return jax.jit(make)(weights.root_key(seed))


def param_count(c: dict) -> int:
    """Parameters the program holds (the tied head counted once)."""
    n = sum(int(np.prod(s)) for s, _ in layer_shapes(c).values())
    return n * c["num_hidden_layers"] + weights.outer_count(c)


# ---- operations and bytes ---------------------------------------------------

def layer_matmul_params(c: dict) -> int:
    """Weights one token meets in the matrix products of one layer."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff


def weight_bytes(c: dict) -> int:
    """Bytes a step reads to apply every layer and the unembedding once."""
    L = c["num_hidden_layers"]
    return DTYPE_BYTES * (L * layer_matmul_params(c) + head_params(c))


def kv_bytes_per_token(c: dict) -> int:
    """Keys and values of one position, all layers."""
    return (DTYPE_BYTES * c["num_hidden_layers"] * 2
            * c["num_key_value_heads"] * c["head_dim"])


def _attn_flops_per_key(c: dict) -> int:
    """Scores and weighted values of one query against one key, all
    layers and heads."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * \
        c["head_dim"]


def decode_flops(c: dict, positions) -> int:
    """One decode step of the active slots; ``positions`` are the cache
    positions their new tokens take (so each attends to pos + 1 keys)."""
    per_tok = 2 * (c["num_hidden_layers"] * layer_matmul_params(c)
                   + head_params(c))
    return sum(per_tok + _attn_flops_per_key(c) * (p + 1)
               for p in positions)


def decode_bytes(c: dict, positions) -> int:
    """Weights once, plus the keys and values each active slot holds."""
    return weight_bytes(c) + kv_bytes_per_token(c) * sum(
        p + 1 for p in positions)


def prefill_flops(c: dict, lens) -> int:
    """One prefill batch of real prompt lengths ``lens`` (padding is not
    work): every token through the layers, causal attention, and the
    unembedding at each prompt's last position."""
    L = c["num_hidden_layers"]
    return sum(2 * L * layer_matmul_params(c) * n
               + _attn_flops_per_key(c) * n * (n + 1) // 2
               + 2 * head_params(c) for n in lens)


def prefill_bytes(c: dict, lens) -> int:
    """Weights once, plus the keys and values the prompts write."""
    return weight_bytes(c) + kv_bytes_per_token(c) * sum(lens)


# ---- the plain reference ----------------------------------------------------

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
                     layer(key, cfg, index, jnp.bfloat16))
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
