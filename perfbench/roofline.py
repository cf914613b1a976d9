"""Operations and bytes the algorithm needs, from the model's shapes.

Counted from the configuration and the engine's real positions, never from
the compiled program, so a later kernel or fused step is held to the same
work.  The counts of a step are the family's (``perfbench/references/
<family>.py``): the functions below forward to the module the
configuration names, so the metric readers stay the same for every family.
bf16 everywhere (2 bytes a value); norms and the embedding rows a step
gathers are left out of the bytes (under 0.01% of a step).
"""

from __future__ import annotations

import json
from pathlib import Path

from perfbench import check

DTYPE_BYTES = 2
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"perfbench/peaks.json has {sorted(table)}")
    return table[device_kind]


def head_params(cfg: dict) -> int:
    """Weights of the unembedding."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> int:
    """Bytes a step reads to apply every layer and the unembedding once."""
    return check.reference(cfg).weight_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Cache bytes of one position, all layers."""
    return check.reference(cfg).kv_bytes_per_token(cfg)


def decode_flops(cfg: dict, positions) -> int:
    """One decode step of the active slots; ``positions`` are the cache
    positions their new tokens take (so each attends to pos + 1 keys)."""
    return check.reference(cfg).decode_flops(cfg, positions)


def decode_bytes(cfg: dict, positions) -> int:
    """Weights once, plus the cache each active slot holds."""
    return check.reference(cfg).decode_bytes(cfg, positions)


def prefill_flops(cfg: dict, lens) -> int:
    """One prefill batch of real prompt lengths ``lens`` (padding is not
    work)."""
    return check.reference(cfg).prefill_flops(cfg, lens)


def prefill_bytes(cfg: dict, lens) -> int:
    """Weights once, plus the cache the prompts write."""
    return check.reference(cfg).prefill_bytes(cfg, lens)


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token meets in the matrix products of one layer, of a
    family whose layers are all alike."""
    return check.reference(cfg).layer_matmul_params(cfg)


def bound_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
