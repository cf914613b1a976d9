"""Logical-axis sharding rules for every architecture × execution mode.

Mesh axes
    single-pod : (data=16, model=16)
    multi-pod  : (pod=2, data=16, model=16)

Policies (MaxText-style logical rules, resolved per-tensor by name+shape):

  train  — batch over (pod?, data); FSDP: d_model rows of weights over
           "data"; TP: heads/ff/vocab over "model"; optimizer state mirrors
           parameter sharding (ZeRO-3).
  serve  — TP over "model"; weights replicated over data/pod (latency) —
           except archs flagged ``serve_fsdp`` (internvl2-76b: 152 GB bf16
           doesn't fit 16-way TP on v5e), which also shard weights over
           "data".  Decode caches: batch over data (when divisible),
           head_dim / MLA-latent over "model" (always divisible by 16 for
           the assigned archs); ring/SSM states likewise.

Every rule degrades to replication when a dimension isn't divisible by the
mesh axis (e.g. minicpm3's 73448 vocab, mamba2's 50280) — recorded by the
dry-run so the roofline table shows the cost.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig

# Archs whose *serving* weights must also be FSDP-sharded over "data".
SERVE_FSDP_ARCHS = {"internvl2-76b"}


@dataclass(frozen=True)
class ShardingPolicy:
    mesh: Mesh
    mode: str                      # "train" | "serve"
    cfg: ModelConfig
    batch_axes: tuple = ("data",)  # ("pod","data") on the multi-pod mesh
    tp_axis: str = "model"
    fsdp_axis: Optional[str] = "data"

    def __post_init__(self):
        if self.mode == "serve" and self.cfg.name not in SERVE_FSDP_ARCHS:
            object.__setattr__(self, "fsdp_axis", None)

    # ---- helpers ----------------------------------------------------------

    def _ax(self, axis: Optional[str], dim: int) -> Optional[str]:
        """Use ``axis`` only if the dim divides evenly over it."""
        if axis is None:
            return None
        size = self.mesh.shape[axis]
        return axis if dim % size == 0 and dim >= size else None

    def _batch(self, dim: int):
        sizes = int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))
        if dim % sizes == 0 and dim >= sizes:
            return tuple(self.batch_axes) if len(self.batch_axes) > 1 \
                else self.batch_axes[0]
        # try just "data"
        if "data" in self.batch_axes and dim % self.mesh.shape["data"] == 0 \
                and dim >= self.mesh.shape["data"]:
            return "data"
        return None

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---- parameter rules ---------------------------------------------------

    def param_spec(self, path: str, shape: tuple) -> P:
        """Sharding for one parameter, identified by its tree path (e.g.
        'blocks/stack/slot_0/mixer/wq').  Stacked (scan) params carry a
        leading period dim — detected via '/stack/' in the path."""
        cfg = self.cfg
        stacked = _is_stacked(path)
        lead: tuple = (None,) if stacked else ()
        core = shape[1:] if stacked else shape
        name = path.rsplit("/", 1)[-1]
        tp, fs = self.tp_axis, self.fsdp_axis

        def pspec(*axes) -> P:
            return P(*(lead + axes))

        if name == "embed" or (name == "head" and len(core) == 2):
            if name == "embed":
                V, d = core
                return P(self._ax(tp, V), self._ax(fs, d))
            d, V = core
            return P(self._ax(fs, d), self._ax(tp, V))
        if len(core) == 1:          # norms, biases, A_log, lam, ...
            return pspec(None)
        # MoE expert tensors (E, d_in, d_out)
        if name in ("w_gate", "w_up", "w_down") and len(core) == 3:
            E = core[0]
            e_ax = self._ax(tp, E)
            if name == "w_down":
                return pspec(e_ax, None, self._ax(fs, core[2]))
            return pspec(e_ax, self._ax(fs, core[1]), None)
        if name == "router":
            return pspec(None, None)
        if name == "conv_w":
            return pspec(None, None)
        # attention / MLA / mlp / ssm / rglru 2-D weights
        if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_x_in", "w_gate_in",
                    "in_proj"):
            return pspec(self._ax(fs, core[0]), self._ax(tp, core[1]))
        if name in ("wo", "w_down", "w_out", "out_proj"):
            return pspec(self._ax(tp, core[0]), self._ax(fs, core[1]))
        if name == "w_dkv":
            return pspec(self._ax(fs, core[0]), self._ax(tp, core[1]))
        if name == "w_krope":
            return pspec(self._ax(fs, core[0]), None)
        if name in ("w_uk", "w_uv"):
            return pspec(self._ax(tp, core[0]), None)
        if name in ("w_a", "w_i"):
            return pspec(self._ax(tp, core[0]), None)
        return pspec(*([None] * len(core)))

    def params_shardings(self, params_tree):
        """Pytree of NamedSharding matching ``params_tree`` (of arrays or
        ShapeDtypeStructs)."""
        def visit(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            return self.named(self.param_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(visit, params_tree)

    # ---- activation / batch rules ----------------------------------------

    def batch_shardings(self, batch_tree):
        def visit(path, leaf):
            b = self._batch(leaf.shape[0]) if leaf.ndim >= 1 else None
            return self.named(P(*([b] + [None] * (leaf.ndim - 1))))
        return jax.tree_util.tree_map_with_path(visit, batch_tree)

    # ---- decode-cache rules -------------------------------------------------

    def cache_spec(self, path: str, shape: tuple) -> P:
        """Decode caches — flash-decode sharding: batch over data, KV
        *sequence* over model (partial softmax per shard + small max/sum
        all-reduce; the naive head-dim contraction made GSPMD replicate the
        whole cache — see EXPERIMENTS.md §Perf).  Falls back to the feature
        dim when the sequence doesn't divide.
        k/v (B,K,S,hd): S over tp.  MLA latent (B,S,r)/k_rope: S over tp.
        ssm (B,H,P,N): H over tp.  conv/h states: last dim over tp."""
        stacked = _is_stacked(path)
        lead: tuple = (None,) if stacked else ()
        core = shape[1:] if stacked else shape
        name = path.rsplit("/", 1)[-1]
        tp = self.tp_axis
        b = self._batch(core[0])
        if name in ("k", "v"):
            s_ax = self._ax(tp, core[2])
            hd_ax = self._ax(tp, core[3]) if s_ax is None else None
            return P(*(lead + (b, None, s_ax, hd_ax)))
        if name in ("latent", "k_rope"):
            s_ax = self._ax(tp, core[1])
            f_ax = self._ax(tp, core[2]) if s_ax is None else None
            return P(*(lead + (b, s_ax, f_ax)))
        if name == "ssm":
            return P(*(lead + (b, self._ax(tp, core[1]), None, None)))
        if name in ("conv", "h"):
            return P(*(lead + (b,) + (None,) * (len(core) - 2)
                       + (self._ax(tp, core[-1]),)))
        return P(*(lead + (b,) + (None,) * (len(core) - 1)))

    def logits_sharding(self, shape: tuple):
        """(B, S, V) logits: batch over data, vocab over model (kept sharded
        so serve_step never gathers the vocab axis; sampling reduces it)."""
        b = self._batch(shape[0])
        return self.named(P(b, None, self._ax(self.tp_axis, shape[-1])))

    def cache_shardings(self, cache_tree):
        def visit(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            return self.named(self.cache_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(visit, cache_tree)

    def scalar_sharding(self):
        return self.named(P())


def _is_stacked(path: str) -> bool:
    return path.startswith("stack/") or "/stack/" in path


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def batch_axes_for(mesh: Mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
