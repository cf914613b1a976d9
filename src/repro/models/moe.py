"""Mixture-of-Experts layers (phi3.5-moe, deepseek-v2-lite).

Three execution paths, selected by ``impl``:

* ``dense``   — every expert on every token, combined by router weights.
                O(E·N·d·ff) compute: ONLY for tiny smoke-test configs.
* ``dropping``— Switch-style capacity dispatch with scatter/gather (no giant
                dispatch einsums — positions via cumsum of a one-hot, then
                scatter-add into (E, C, d)).  GSPMD shards the expert axis
                over the ``model`` mesh axis.  Used in single-program form.
* ``ep_a2a``  — explicit expert parallelism: shard_map over the mesh with
                lax.all_to_all dispatch/return, experts sharded over the
                ``model`` axis.  This is the production path for the
                multi-pod mesh — collective volume = 2 × tokens·d per hop
                (down from all-gather's full duplication).

All paths share router semantics: softmax over expert logits, top-k, gates
renormalized over the selected k (deepseek convention).  Over-capacity
tokens are dropped (their combine weight is 0) — standard for static-shape
TPU MoE; capacity_factor controls the head-room.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import dense_init


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "w_gate": _expert_init(ks[1], E, d, ff, dtype),
        "w_up": _expert_init(ks[2], E, d, ff, dtype),
        "w_down": _expert_init(ks[3], E, ff, d, dtype),
    }
    if cfg.n_shared_experts:
        sff = cfg.moe_d_ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kk[0], d, sff, dtype),
                       "w_up": dense_init(kk[1], d, sff, dtype),
                       "w_down": dense_init(kk[2], sff, d, dtype)}
    return p


def _expert_init(key, E, d_in, d_out, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (E, d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def _route(x2d: jnp.ndarray, router: jnp.ndarray, top_k: int):
    """x2d (N, d) → gates (N, k) fp32 renormalized, idx (N, k) int32."""
    logits = (x2d.astype(jnp.float32) @ router)            # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def aux_load_balance_loss(probs: jnp.ndarray, idx: jnp.ndarray, E: int) -> jnp.ndarray:
    """Switch-style auxiliary load-balancing loss (used in train_step)."""
    N = probs.shape[0]
    me = probs.mean(0)                                      # mean router prob
    one_hot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    ce = one_hot.mean(0)                                    # fraction routed (top-1)
    return E * jnp.sum(me * ce)


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.moe_top_k / max(cfg.n_experts, 1)
            * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)        # round up to 8 for TPU lanes


# --------------------------------------------------------------------------
# dense path (smoke tests)
# --------------------------------------------------------------------------

def moe_dense(params, x, cfg: ModelConfig):
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    gates, idx, probs = _route(x2, params["router"], cfg.moe_top_k)
    # all experts on all tokens
    h = jnp.einsum("nd,edf->enf", x2, params["w_gate"])
    u = jnp.einsum("nd,edf->enf", x2, params["w_up"])
    y_all = jnp.einsum("enf,efd->end", jax.nn.silu(h) * u, params["w_down"])
    # combine top-k
    E = cfg.n_experts
    w = jnp.zeros((x2.shape[0], E), dtype=jnp.float32)
    w = jnp.take_along_axis(
        w, idx, axis=1)  # noop shape trick replaced below
    combine = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                      * gates[..., None], axis=1)           # (N, E)
    y = jnp.einsum("end,ne->nd", y_all.astype(jnp.float32), combine)
    y = y.astype(x.dtype)
    if "shared" in params:
        y = y + _shared_forward(params["shared"], x2)
    return y.reshape(B, S, d), (probs, idx)


def _shared_forward(sp, x2):
    h = jax.nn.silu(x2 @ sp["w_gate"]) * (x2 @ sp["w_up"])
    return h @ sp["w_down"]


# --------------------------------------------------------------------------
# dropping path (single-program; GSPMD shards expert axis)
# --------------------------------------------------------------------------

def moe_dropping(params, x, cfg: ModelConfig):
    B, S, d = x.shape
    N = B * S
    E, k = cfg.n_experts, cfg.moe_top_k
    C = _capacity(N, cfg)
    x2 = x.reshape(N, d)
    gates, idx, probs = _route(x2, params["router"], k)

    # position of each (token, choice) within its expert, via cumsum of
    # one-hot — O(N·E) int traffic, no N·E·C tensors.
    flat_e = idx.reshape(-1)                                # (N·k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # (N·k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)        # exclusive cumsum
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    gates_flat = gates.reshape(-1) * keep.astype(jnp.float32)

    # scatter tokens into (E, C, d) expert buffers
    xk = jnp.repeat(x2, k, axis=0)                          # (N·k, d)
    buf = jnp.zeros((E, C, d), dtype=x.dtype)
    safe_pos = jnp.where(keep, pos, C - 1)
    contrib = jnp.where(keep[:, None], xk, 0).astype(x.dtype)
    buf = buf.at[flat_e, safe_pos].add(contrib, mode="drop")

    # expert MLPs (batched over E; E is sharded over 'model' by GSPMD)
    h = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, params["w_down"])

    # gather back + weighted combine
    y_tok = y_buf[flat_e, safe_pos]                         # (N·k, d)
    y_tok = y_tok.astype(jnp.float32) * gates_flat[:, None]
    y = y_tok.reshape(N, k, d).sum(axis=1).astype(x.dtype)
    if "shared" in params:
        y = y + _shared_forward(params["shared"], x2)
    return y.reshape(B, S, d), (probs, idx)


# --------------------------------------------------------------------------
# expert-parallel all-to-all path (shard_map; production meshes)
# --------------------------------------------------------------------------

def moe_ep_a2a(params, x, cfg: ModelConfig, mesh, *, batch_axes=("data",),
               expert_axis: str = "model"):
    """Expert parallelism with explicit all-to-all dispatch.

    Token batch is sharded over ``batch_axes``; experts over ``expert_axis``
    (size S_e).  Per device: route local tokens, bucket them per *expert*,
    all_to_all ships each expert-shard its buckets, local expert compute,
    all_to_all back, combine.  Collective volume per layer ≈ 2·N_loc·k/E·C
    ·d — the minimum for EP."""
    from jax.sharding import PartitionSpec as P

    E, k, d = cfg.n_experts, cfg.moe_top_k, cfg.d_model
    S_e = 1
    for ax in ([expert_axis] if isinstance(expert_axis, str) else expert_axis):
        S_e *= mesh.shape[ax]
    assert E % S_e == 0, f"experts {E} must divide over axis size {S_e}"
    E_loc = E // S_e

    x_spec = P(batch_axes, None, None)
    ew_spec = P(expert_axis, None, None)

    def local_fn(x_loc, router, w_gate, w_up, w_down):
        B_loc, S, _ = x_loc.shape
        N = B_loc * S
        C = _capacity(N, cfg)                 # capacity per expert (local view)
        x2 = x_loc.reshape(N, d)
        gates, idx, probs = _route(x2, router, k)
        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
        keep = pos < C
        gates_flat = gates.reshape(-1) * keep.astype(jnp.float32)
        safe_pos = jnp.where(keep, pos, C - 1)
        xk = jnp.repeat(x2, k, axis=0)
        send = jnp.zeros((E, C, d), dtype=x_loc.dtype)
        contrib = jnp.where(keep[:, None], xk, 0).astype(x_loc.dtype)
        send = send.at[flat_e, safe_pos].add(contrib, mode="drop")
        # ship: (E, C, d) = (S_e, E_loc, C, d) --a2a--> (S_e_src, E_loc, C, d)
        send = send.reshape(S_e, E_loc, C, d)
        recv = jax.lax.all_to_all(send, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (S_e, E_loc, C, d) — dim0 = source shard
        xin = recv.transpose(1, 0, 2, 3).reshape(E_loc, S_e * C, d)
        h = jnp.einsum("ecd,edf->ecf", xin, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xin, w_up)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, w_down)
        y = y.reshape(E_loc, S_e, C, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(E, C, d)
        y_tok = back[flat_e, safe_pos].astype(jnp.float32) * gates_flat[:, None]
        y_out = y_tok.reshape(N, k, d).sum(1).astype(x_loc.dtype)
        return y_out.reshape(B_loc, S, d), probs, idx

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(), ew_spec, ew_spec, ew_spec),
        out_specs=(x_spec, P(batch_axes, None), P(batch_axes, None)),
        check_vma=False)
    y, probs, idx = fn(x, params["router"], params["w_gate"],
                       params["w_up"], params["w_down"])
    if "shared" in params:
        B, S, _ = x.shape
        y = y + _shared_forward(params["shared"], x.reshape(-1, d)).reshape(B, S, d)
    return y, (probs, idx)


def moe_forward(params, x, cfg: ModelConfig, impl: str = "dropping",
                mesh=None, batch_axes=("data",), expert_axis="model"):
    if impl == "dense":
        return moe_dense(params, x, cfg)
    if impl == "dropping":
        return moe_dropping(params, x, cfg)
    if impl == "ep_a2a":
        return moe_ep_a2a(params, x, cfg, mesh, batch_axes=batch_axes,
                          expert_axis=expert_axis)
    raise ValueError(f"unknown moe impl {impl}")
