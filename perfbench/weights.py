"""Random weights drawn from the seed: the rule every family's leaves follow,
and the leaves every family has (the embedding table, the unembedding and
the final norm).  A family's own layers are drawn by its module
(``perfbench/references/<family>.py``) with :func:`draw`.

Each leaf of each layer has its own key, ``fold_in(fold_in(root, layer),
leaf id)``, so the program's whole parameter tree is made on the device in
one jitted call, and the reference draws any one layer alone and gets the
same numbers.  Matrices are N(0, 1/fan_in), embeddings N(0, 0.02^2), and
norm scales are stored as offsets from 1, N(0, 0.1^2), the way the program
keeps them.  Every leaf is rounded to the served dtype; the reference reads
those rounded values in float32.

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

from perfbench import check

EMBED_STD = 0.02
NORM_STD = 0.1
# Leaf ids of the leaves every family has; a family numbers its own from 10.
# The key of a leaf never depends on which other leaves exist.
EMBED, HEAD, FINAL_NORM = 1, 2, 3


def root_key(seed: int):
    """A PRNG key from a seed of any size."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(key, leaf_id: int, shape, std, dtype):
    """One leaf: N(0, std^2), or a norm's offset where ``std`` is None."""
    k = jax.random.fold_in(key, leaf_id)
    x = jax.random.normal(k, shape, jnp.float32) * (NORM_STD if std is None
                                                    else std)
    return x.astype(dtype)


def tied_scale(cfg: dict) -> float:
    """The factor the program's tied path puts on input embeddings."""
    return float(np.sqrt(cfg["hidden_size"])) if cfg["tie_word_embeddings"] \
        else 1.0


def embed(key, cfg: dict, dtype):
    """The program's table (vocab, hidden)."""
    x = draw(key, EMBED, (cfg["vocab_size"], cfg["hidden_size"]), EMBED_STD,
             jnp.float32)
    return (x / tied_scale(cfg)).astype(dtype)


def head(key, cfg: dict, dtype):
    """The program's separate unembedding (hidden, vocab) of an untied model."""
    d = cfg["hidden_size"]
    return draw(key, HEAD, (d, cfg["vocab_size"]), d ** -0.5, dtype)


def final_norm(key, cfg: dict, dtype):
    """The program's final norm offset: the gain is 1 + offset."""
    x = draw(key, FINAL_NORM, (cfg["hidden_size"],), None, jnp.float32)
    return ((1.0 + x) * tied_scale(cfg) - 1.0).astype(dtype)


def outer(key, cfg: dict, dtype) -> dict:
    """The leaves of the program's tree outside its blocks."""
    p = {"final_norm": final_norm(key, cfg, dtype),
         "embed": embed(key, cfg, dtype)}
    if not cfg["tie_word_embeddings"]:
        p["head"] = head(key, cfg, dtype)
    return p


def outer_count(cfg: dict) -> int:
    """Parameters of :func:`outer` (the tied head counted once)."""
    return cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2) + cfg["hidden_size"]


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
    """The whole tree in the engine's layout, as the configuration's family
    module draws it (``perfbench.check.reference``)."""
    return check.reference(cfg).program_params(seed, cfg, dtype)


def param_count(cfg: dict) -> int:
    """Parameters the program holds, as the family counts them."""
    return check.reference(cfg).param_count(cfg)
