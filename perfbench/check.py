"""Whether what the timed path served is correct.

Once the window has closed and the engine is freed, a sample of the
requests it finished, drawn from the seed and holding the one with the most
served tokens, goes through the configuration's plain reference: each
prompt with its served tokens, in one causal pass.  At each position where a
token was served (the prompt's end, for the prefill's token, and every
decoded token after it) the served token's reference logit must lie close to
the reference's best: the widest gap, in standard deviations of that
position's reference logits, is the number compared.  The served tokens are
greedy, so a sound engine only departs from the reference's top where bf16
rounding decides a near tie.
"""

from __future__ import annotations

import importlib

import numpy as np

from perfbench import traffic

SAMPLE = 8                   # requests compared per run


def reference(cfg: dict):
    """The family module the configuration names under ``reference``,
    ``perfbench/references/<name>.py``: the one lookup through which the
    harness reaches whatever depends on the architecture (the program's
    config, the weights, the roofline counts and the plain reference; the
    interface is set out in ``perfbench/references/dense.py``).  A name
    with no such module is an error that names it."""
    family = cfg["reference"]
    name = f"perfbench.references.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ModuleNotFoundError(
            f"reference {family!r}: no family module "
            f"perfbench/references/{family}.py", name=name) from None


def sample(served: dict, seed: int, k: int = SAMPLE) -> list[int]:
    """Request ids: the one with the most served tokens, then others drawn
    from the seed (stream 2)."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r]), -r))
    rest = [r for r in rids if r != longest]
    rng = traffic.seed_seq(seed, 2)
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) \
        if rest else []
    return [longest] + sorted(int(r) for r in pick)


def pack(prompts: dict, served: dict, rids: list[int], length: int):
    """Token rows (prompt + served[:-1], zero padded to ``length``), the
    served token due at each position, and where a served token is due."""
    B = len(rids)
    tokens = np.zeros((B, length), np.int32)
    targets = np.zeros((B, length), np.int32)
    mask = np.zeros((B, length), bool)
    for b, rid in enumerate(rids):
        p, s = np.asarray(prompts[rid]), np.asarray(served[rid])
        seq = np.concatenate([p, s[:-1]])
        tokens[b, :len(seq)] = seq
        pos = np.arange(len(p) - 1, len(p) - 1 + len(s))
        targets[b, pos] = s
        mask[b, pos] = True
    return tokens, targets, mask


def gaps(top, std, at, mask) -> np.ndarray:
    """Reference gap of each compared position, in standard deviations."""
    return ((top - at) / std)[mask]


def compare(cfg: dict, seed: int, prompts: dict, served: dict,
            length: int, control: bool = False) -> dict:
    """The numbers ``correct`` is decided on.  ``control`` also reads the
    gap of the tokens the reference's float8 twin ranks first."""
    ref = reference(cfg)
    rids = sample(served, seed)
    tokens, targets, mask = pack(prompts, served, rids, length)
    h = ref.hidden(seed, cfg, tokens, "f32")
    top, std, at, am = ref.readout(seed, cfg, h, targets[:, None, :])
    g = gaps(top, std, at[:, 0], mask)
    out = {"requests": len(rids), "positions": int(mask.sum()),
           "logit_gap": float(g.max()) if g.size else float("inf"),
           "top_agree": float((am[mask] == targets[mask]).mean())
           if g.size else 0.0}
    if control:
        h8 = ref.hidden(seed, cfg, tokens, "fp8")
        am8 = ref.readout(seed, cfg, h8, targets[:, None, :], "fp8")[3]
        del h8
        top, std, at, _ = ref.readout(seed, cfg, h, am8[:, None, :])
        g8 = gaps(top, std, at[:, 0], mask)
        out["control_logit_gap"] = float(g8.max())
        out["control_top_agree"] = float((am8[mask] == am[mask]).mean())
    return out
