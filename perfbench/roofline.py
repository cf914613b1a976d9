"""Operations and bytes the algorithm needs, from the model's shapes.

Counted from the configuration and the engine's real positions, never from
the compiled program, so a later kernel or fused step is held to the same
work.  bf16 everywhere (2 bytes a value); norms and the embedding rows a
step gathers are left out of the bytes (under 0.01% of a step).
"""

from __future__ import annotations

import json
from pathlib import Path

DTYPE_BYTES = 2
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"perfbench/peaks.json has {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token meets in the matrix products of one layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> int:
    """Bytes a step reads to apply every layer and the unembedding once."""
    L = cfg["num_hidden_layers"]
    return DTYPE_BYTES * (L * layer_matmul_params(cfg) + head_params(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position, all layers."""
    return (DTYPE_BYTES * cfg["num_hidden_layers"] * 2
            * cfg["num_key_value_heads"] * cfg["head_dim"])


def _attn_flops_per_key(cfg: dict) -> int:
    """Scores and weighted values of one query against one key, all
    layers and heads."""
    return 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * \
        cfg["head_dim"]


def decode_flops(cfg: dict, positions) -> int:
    """One decode step of the active slots; ``positions`` are the cache
    positions their new tokens take (so each attends to pos + 1 keys)."""
    per_tok = 2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + head_params(cfg))
    return sum(per_tok + _attn_flops_per_key(cfg) * (p + 1)
               for p in positions)


def decode_bytes(cfg: dict, positions) -> int:
    """Weights once, plus the keys and values each active slot holds."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(
        p + 1 for p in positions)


def prefill_flops(cfg: dict, lens) -> int:
    """One prefill batch of real prompt lengths ``lens`` (padding is not
    work): every token through the layers, causal attention, and the
    unembedding at each prompt's last position."""
    L = cfg["num_hidden_layers"]
    return sum(2 * L * layer_matmul_params(cfg) * n
               + _attn_flops_per_key(cfg) * n * (n + 1) // 2
               + 2 * head_params(cfg) for n in lens)


def prefill_bytes(cfg: dict, lens) -> int:
    """Weights once, plus the keys and values the prompts write."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(lens)


def bound_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
