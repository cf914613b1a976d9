"""Open-loop traffic from a mix file and a seed.

A mix file (``perfbench/traffic/<name>.json``) names its arrival process and
its request classes: each class has a share and lognormal prompt and output
lengths (median, sigma, clipped to [min, max]).  The cell file gives the rate.

Every seed gets the same work: the same requests (class, prompt length,
output length) and the same multiset of inter-arrival gaps.  Each class
pairs prompt and output lengths the same way for every seed.  Lengths and
gaps are the distributions' quantiles at (i + 0.5) / n, so a run of
``seconds`` at ``rate`` holds round(rate x seconds) requests whatever the
seed.  The order of the requests and of the gaps is drawn from the mix's
``schedule_seed``, so every seed meets the same arrivals and only the token
ids follow the seed.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    """One request as the generator draws it; ``offset`` is seconds after
    the window opens."""

    rid: int
    cls: str
    offset: float
    prompt: np.ndarray
    max_new_tokens: int


def seed_seq(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for ``stream`` of ``seed`` (any size of int)."""
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths: the clipped lognormal's quantiles at (i + 0.5) / n."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, seconds: float) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process, as quantiles of the
    exponential, scaled to add up to ``seconds``."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (seconds / g.sum())


def class_counts(mix: dict, n: int) -> list[int]:
    shares = [c["share"] for c in mix["classes"]]
    counts = [int(round(s * n)) for s in shares]
    counts[0] += n - sum(counts)
    return counts


def generate(mix: dict, rate: float, seconds: float, seed: int, vocab: int,
             stream: int = 0, first_id: int = 0) -> list[Planned]:
    """The requests due in a window of ``seconds`` at ``rate`` req/s, in
    arrival order.  ``stream`` separates independent draws of one seed (the
    scheduler's history before the window is stream 1)."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    n = max(int(round(rate * seconds)), 1)
    rng = seed_seq(seed, stream)
    order_rng = seed_seq(mix["schedule_seed"], stream)
    labels, prompts, outputs = [], [], []
    for c, (spec, k) in enumerate(zip(mix["classes"], class_counts(mix, n))):
        if k == 0:
            continue
        labels += [spec["name"]] * k
        pairing = np.random.default_rng(c).permutation(k)
        prompts.append(lognormal_quantiles(spec["prompt"], k))
        outputs.append(lognormal_quantiles(spec["output"], k)[pairing])
    prompts = np.concatenate(prompts)
    outputs = np.concatenate(outputs)
    order = order_rng.permutation(n)
    gaps = order_rng.permutation(exponential_gaps(n, seconds))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    for j, i in enumerate(order):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int64)
        out.append(Planned(rid=first_id + j, cls=labels[i],
                           offset=float(offsets[j]),
                           prompt=toks.astype(np.int32),
                           max_new_tokens=int(outputs[i])))
    return out
