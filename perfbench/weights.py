"""Random weights of the dense decoder family, drawn from the seed.

Each leaf of each layer has its own key, ``fold_in(fold_in(root, leaf),
layer)``, so the program's whole parameter tree is made on the device in one
jitted call, and the reference draws any one layer alone and gets the same
numbers.  Matrices are N(0, 1/fan_in), embeddings N(0, 0.02^2), and norm
scales are stored as offsets from 1, N(0, 0.1^2), the way the program keeps
them.  Every leaf is rounded to the served dtype; the reference reads those
rounded values in float32.

A tied model is stored for the program's tied path, which multiplies the
input embeddings by sqrt(hidden_size) (``repro.models.model``): the table is
stored divided by that scale and the final norm's gain multiplied by it, so
the input rows and the logits are those of the plain tied model (the
Hugging Face Llama and Qwen3 rule, no scale).  The reference reads the
stored values back through :func:`reference_embed` and
:func:`reference_readout`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
# Leaf ids: the key of a leaf never depends on which other leaves exist.
_IDS = {"embed": 1, "head": 2, "final_norm": 3, "ln1": 10, "wq": 11,
        "wk": 12, "wv": 13, "wo": 14, "q_norm": 15, "k_norm": 16, "ln2": 17,
        "w_gate": 18, "w_up": 19, "w_down": 20}


def root_key(seed: int):
    """A PRNG key from a seed of any size."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(cfg: dict) -> dict:
    """{leaf: (shape, std)} of one decoder layer; std None marks a norm."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    out = {"ln1": ((d,), None),
           "wq": ((d, H * hd), d ** -0.5),
           "wk": ((d, K * hd), d ** -0.5),
           "wv": ((d, K * hd), d ** -0.5),
           "wo": ((H * hd, d), (H * hd) ** -0.5),
           "ln2": ((d,), None),
           "w_gate": ((d, ff), d ** -0.5),
           "w_up": ((d, ff), d ** -0.5),
           "w_down": ((ff, d), ff ** -0.5)}
    if cfg["qk_norm"]:
        out["q_norm"] = ((hd,), None)
        out["k_norm"] = ((hd,), None)
    return out


def _draw(key, name: str, shape, std, dtype):
    k = jax.random.fold_in(key, _IDS[name])
    x = jax.random.normal(k, shape, jnp.float32) * (NORM_STD if std is None
                                                    else std)
    return x.astype(dtype)


def layer(key, cfg: dict, index, dtype) -> dict:
    """One layer's leaves (``index`` may be traced)."""
    return {name: _draw(jax.random.fold_in(key, index), name, shape, std, dtype)
            for name, (shape, std) in layer_shapes(cfg).items()}


def tied_scale(cfg: dict) -> float:
    """The factor the program's tied path puts on input embeddings."""
    return float(np.sqrt(cfg["hidden_size"])) if cfg["tie_word_embeddings"] \
        else 1.0


def embed(key, cfg: dict, dtype):
    """The program's table (vocab, hidden)."""
    x = _draw(key, "embed", (cfg["vocab_size"], cfg["hidden_size"]),
              EMBED_STD, jnp.float32)
    return (x / tied_scale(cfg)).astype(dtype)


def head(key, cfg: dict, dtype):
    """The program's separate unembedding (hidden, vocab) of an untied model."""
    d = cfg["hidden_size"]
    return _draw(key, "head", (d, cfg["vocab_size"]), d ** -0.5, dtype)


def final_norm(key, cfg: dict, dtype):
    """The program's final norm offset: the gain is 1 + offset."""
    x = _draw(key, "final_norm", (cfg["hidden_size"],), None, jnp.float32)
    return ((1.0 + x) * tied_scale(cfg) - 1.0).astype(dtype)


def reference_embed(key, cfg: dict, dtype):
    """The plain model's table, float32, from the stored ``dtype`` values."""
    return embed(key, cfg, dtype).astype(jnp.float32) * tied_scale(cfg)


def reference_readout(key, cfg: dict, dtype):
    """The plain model's final norm offset and unembedding (hidden, vocab),
    float32, from the stored ``dtype`` values."""
    s = tied_scale(cfg)
    offset = (1.0 + final_norm(key, cfg, dtype).astype(jnp.float32)) / s - 1.0
    if cfg["tie_word_embeddings"]:
        return offset, reference_embed(key, cfg, dtype).T
    return offset, head(key, cfg, dtype).astype(jnp.float32)


def program_params(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole tree in the layout the engine reads (``repro.models``:
    one scanned period of one attention block), in ``dtype``, made on the
    default device in one jitted call."""

    def make(key):
        stack = jax.vmap(lambda i: layer(key, cfg, i, dtype))(
            jnp.arange(cfg["num_hidden_layers"]))
        block = {"ln1": stack["ln1"], "ln2": stack["ln2"],
                 "mixer": {n: stack[n] for n in ("wq", "wk", "wv", "wo",
                                                  "q_norm", "k_norm")
                           if n in stack},
                 "mlp": {n: stack[n] for n in ("w_gate", "w_up", "w_down")}}
        p = {"blocks": {"head": [], "stack": {"slot_0": block}, "tail": []},
             "final_norm": final_norm(key, cfg, dtype),
             "embed": embed(key, cfg, dtype)}
        if not cfg["tie_word_embeddings"]:
            p["head"] = head(key, cfg, dtype)
        return p

    return jax.jit(make)(root_key(seed))


def param_count(cfg: dict) -> int:
    """Parameters the program holds (the tied head counted once)."""
    n = sum(int(np.prod(s)) for s, _ in layer_shapes(cfg).values())
    n *= cfg["num_hidden_layers"]
    n += cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return n + cfg["hidden_size"]
