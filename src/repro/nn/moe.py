"""Mixture-of-Experts with expert parallelism.

Experts are sharded over the 'model' mesh axis (EP); within each expert the
weights are additionally FSDP-sharded over 'data' and all-gathered per layer
(AD turns the gather into reduce-scatter gradients — ZeRO-3 semantics).

Two dispatch strategies, chosen by token count:
  * sorted all-to-all (train/prefill): tokens are seq-sharded over 'model';
    each shard top-k routes its tokens, packs per-destination capacity
    buffers, and exchanges them with a single `all_to_all` (GShard-style,
    capacity factor with drops + load-balance auxiliary loss);
  * replicated-token (decode): tokens are replicated over 'model'; each
    shard runs only its local experts, masked by the routing decision, and
    partial outputs are `psum`-combined.  For one-token decode this costs
    E_local ≈ top-k expert evaluations — no all_to_all latency on the
    critical path.

Without an installed mesh (CPU unit tests) a dense reference path runs the
exact same math serially — it doubles as the oracle for the shard_map path.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nn.config import ModelConfig
from repro.nn.layers import _act, _init
from repro.parallel.sharding import batch_axes, current_mesh
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, fe, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    p = {
        "router": _init(ks[0], (d, e), 0),
        "w_gate": _init(ks[1], (e, d, fe), 1),
        "w_up": _init(ks[2], (e, d, fe), 1),
        "w_down": _init(ks[3], (e, fe, d), 1),
    }
    if cfg.n_shared_experts:
        from repro.nn.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared_experts * fe, gated=True)
    return p


def moe_specs(cfg: ModelConfig) -> Params:
    s = {
        "router": (None, None),
        "w_gate": ("tp", "fsdp", None),
        "w_up": ("tp", "fsdp", None),
        "w_down": ("tp", None, "fsdp"),
    }
    if cfg.n_shared_experts:
        from repro.nn.layers import mlp_specs
        s["shared"] = mlp_specs(gated=True)
    return s


def _route(x_f32: jax.Array, router: jax.Array, topk: int):
    """x (T, D) -> probs (T, E), top-k (T, k) values+indices (normalized)."""
    logits = x_f32 @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, topk)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _aux_loss(probs: jax.Array, gate_idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch/GShard load-balance loss: E · Σ_e f_e · p̄_e."""
    assign = jax.nn.one_hot(gate_idx[..., 0], n_experts, dtype=jnp.float32)
    f = jnp.mean(assign, axis=0)
    pbar = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * pbar)


def _expert_ffn(w_gate, w_up, w_down, x, act: str) -> jax.Array:
    """Per-expert gated FFN.  x: (E, C, D); weights (E, D, F)/(E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    h = _act(g, act) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------- reference
def moe_reference(params: Params, x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """Dense single-device MoE (oracle; exact, no capacity drops)."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    probs, gate_vals, gate_idx = _route(xf.astype(jnp.float32),
                                        params["router"].astype(jnp.float32),
                                        cfg.moe_topk)
    out = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(cfg.n_experts):
        w = (gate_vals * (gate_idx == e)).sum(-1)  # (T,)
        h = _act(xf @ params["w_gate"][e].astype(x.dtype), cfg.act) * (
            xf @ params["w_up"][e].astype(x.dtype))
        y = (h @ params["w_down"][e].astype(x.dtype)).astype(jnp.float32)
        out = out + w[:, None] * y
    aux = _aux_loss(probs, gate_idx, cfg.n_experts)
    y = out.astype(x.dtype).reshape(B, S, D)
    if "shared" in params:
        from repro.nn.layers import mlp
        y = y + mlp(params["shared"], x, cfg.act)
    return y, aux


# ----------------------------------------------------------- sharded paths
def _pack_dispatch(xf, gate_vals, gate_idx, n_experts, capacity):
    """Sort-based capacity dispatch.  Returns (buffer (E, C, D), combine
    indices/weights for the return scatter)."""
    T, D = xf.shape
    k = gate_idx.shape[-1]
    flat_expert = gate_idx.reshape(-1)              # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), k)
    flat_gate = gate_vals.reshape(-1)
    # Position of each assignment within its expert (rank by arrival).
    onehot = jax.nn.one_hot(flat_expert, n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos_in_expert = jnp.sum(pos, axis=-1)           # (T*k,)
    keep = pos_in_expert < capacity
    slot = jnp.where(keep, flat_expert * capacity + pos_in_expert, T * 0 - 1)
    buf = jnp.zeros((n_experts * capacity, D), xf.dtype)
    buf = buf.at[jnp.where(keep, slot, n_experts * capacity)].set(
        xf[flat_token], mode="drop")
    return (buf.reshape(n_experts, capacity, D),
            flat_token, slot, jnp.where(keep, flat_gate, 0.0))


def _moe_body_a2a(xb, router, w_gate, w_up, w_down, cfg: ModelConfig,
                  model_size: int):
    """Per-shard body (tokens seq-sharded over 'model')."""
    Bl, Sl, D = xb.shape
    xf = xb.reshape(-1, D)
    T = xf.shape[0]
    probs, gate_vals, gate_idx = _route(xf.astype(jnp.float32),
                                        router.astype(jnp.float32), cfg.moe_topk)
    aux = _aux_loss(probs, gate_idx, cfg.n_experts)
    cap = max(int(T * cfg.moe_topk * cfg.capacity_factor / cfg.n_experts), 4)
    buf, tok_idx, slot, gate = _pack_dispatch(xf, gate_vals, gate_idx,
                                              cfg.n_experts, cap)
    e_loc = cfg.n_experts // model_size
    # (E, C, D) -> (M, E_loc, C, D) -> exchange -> (M, E_loc, C, D) src-major
    buf = buf.reshape(model_size, e_loc, cap, D)
    recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(model_size, e_loc, cap, D)
    toks = recv.transpose(1, 0, 2, 3).reshape(e_loc, model_size * cap, D)
    # FSDP: weights arrive sharded over 'data' on the D (or F) dim.
    w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
    w_up = jax.lax.all_gather(w_up, "data", axis=1, tiled=True)
    w_down = jax.lax.all_gather(w_down, "data", axis=2, tiled=True)
    y = _expert_ffn(w_gate.astype(xb.dtype), w_up.astype(xb.dtype),
                    w_down.astype(xb.dtype), toks, cfg.act)
    y = y.reshape(e_loc, model_size, cap, D).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(cfg.n_experts * cap, D)
    gathered = back[jnp.clip(slot, 0, cfg.n_experts * cap - 1)]
    contrib = gathered.astype(jnp.float32) * gate[:, None]
    out = jnp.zeros((T, D), jnp.float32).at[tok_idx].add(contrib)
    return out.astype(xb.dtype).reshape(Bl, Sl, D), aux


def _moe_body_replicated(xb, router, w_gate, w_up, w_down, cfg: ModelConfig,
                         model_size: int, model_idx):
    """Per-shard body (tokens replicated over 'model'; decode path)."""
    Bl, Sl, D = xb.shape
    xf = xb.reshape(-1, D)
    probs, gate_vals, gate_idx = _route(xf.astype(jnp.float32),
                                        router.astype(jnp.float32), cfg.moe_topk)
    aux = _aux_loss(probs, gate_idx, cfg.n_experts)
    e_loc = cfg.n_experts // model_size
    w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
    w_up = jax.lax.all_gather(w_up, "data", axis=1, tiled=True)
    w_down = jax.lax.all_gather(w_down, "data", axis=2, tiled=True)
    # Evaluate every local expert on every token, weight by routing gates.
    xe = jnp.broadcast_to(xf[None], (e_loc,) + xf.shape)
    y = _expert_ffn(w_gate.astype(xb.dtype), w_up.astype(xb.dtype),
                    w_down.astype(xb.dtype), xe, cfg.act)  # (E_loc, T, D)
    local_ids = model_idx * e_loc + jnp.arange(e_loc)
    gates = jnp.sum(
        gate_vals[None] * (gate_idx[None] == local_ids[:, None, None]), -1)
    out = jnp.einsum("et,etd->td", gates.astype(jnp.float32),
                     y.astype(jnp.float32))
    out = jax.lax.psum(out, "model")
    aux = jax.lax.pmean(aux, "model")
    return out.astype(xb.dtype).reshape(Bl, Sl, D), aux


def moe(params: Params, x: jax.Array, cfg: ModelConfig,
        decode: bool = False) -> Tuple[jax.Array, jax.Array]:
    ctx = current_mesh()
    if ctx is None:
        return moe_reference(params, x, cfg)
    mesh = ctx.mesh
    model_size = mesh.shape["model"]
    ba = batch_axes()

    if decode or x.shape[1] == 1:
        def body(xb, router, wg, wu, wd):
            idx = jax.lax.axis_index("model")
            return _moe_body_replicated(xb, router, wg, wu, wd, cfg,
                                        model_size, idx)
        in_specs = (P(ba, None, None), P(None, None),
                    P("model", "data", None), P("model", "data", None),
                    P("model", None, "data"))
        out_specs = (P(ba, None, None), P())
    else:
        def body(xb, router, wg, wu, wd):
            return _moe_body_a2a(xb, router, wg, wu, wd, cfg, model_size)
        in_specs = (P(ba, "model", None), P(None, None),
                    P("model", "data", None), P("model", "data", None),
                    P("model", None, "data"))
        out_specs = (P(ba, "model", None), P())

    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    y, aux = mapped(
        x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    aux = jnp.mean(aux)
    if "shared" in params:
        from repro.nn.layers import mlp
        y = y + mlp(params["shared"], x, cfg.act)
    return y, aux
