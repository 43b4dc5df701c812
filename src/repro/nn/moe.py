"""Mixture-of-Experts: a router over all `n_experts`, the experts this
device holds, and expert parallelism over a mesh.

Without an installed mesh (one device: serving, CPU tests) `moe_local`
runs the layer as one device's share of an expert-parallel deployment:
the router keeps its full width and top-k, the device holds experts
[0, experts_held) and computes their part of the result for the tokens
routed to them, plus the shared experts; what absent experts would add is
left out (`config.ModelConfig.n_experts_held`; all of them when 0).  No
token is dropped.  Decode (one token a row) runs every held expert over
the batch, reading each expert's weights once per step; longer inputs
sort their (token, slot) pairs by expert and run the held ones through a
grouped matmul (`jax.lax.ragged_dot`), so no expert sees a token that was
not routed to it.  `moe_reference` is the plain loop over held experts
that serves as the oracle.

Over a mesh, experts are sharded over the 'model' axis (EP); within each
expert the weights are additionally FSDP-sharded over 'data' and
all-gathered per layer (AD turns the gather into reduce-scatter gradients
— ZeRO-3 semantics).  Two dispatch strategies, chosen by token count:
  * sorted all-to-all (train/prefill): tokens are seq-sharded over 'model';
    each shard top-k routes its tokens, packs per-destination capacity
    buffers, and exchanges them with a single `all_to_all` (GShard-style,
    capacity factor with drops + load-balance auxiliary loss);
  * replicated-token (decode): tokens are replicated over 'model'; each
    shard runs only its local experts, masked by the routing decision, and
    partial outputs are `psum`-combined.  For one-token decode this costs
    E_local ≈ top-k expert evaluations — no all_to_all latency on the
    critical path.
The mesh paths hold every expert (`n_experts_held` 0).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nn.config import ModelConfig
from repro.nn.layers import _act, _init
from repro.parallel.sharding import batch_axes, current_mesh
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    """Router over all `n_experts`; weights of the `experts_held`."""
    ks = jax.random.split(key, 5)
    d, fe, e = cfg.d_model, cfg.d_ff_expert, cfg.experts_held
    p = {
        "router": _init(ks[0], (d, cfg.n_experts), 0),
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


def _route(x_f32: jax.Array, router: jax.Array, topk: int,
           norm_topk: bool = True, scaling: float = 1.0):
    """x (T, D) -> probs (T, E), top-k (T, k) values+indices: softmax over
    every expert, greedy top-k, the k gates renormalised to sum 1
    (`norm_topk`) or else scaled by `scaling` (Hugging Face DeepSeek-V2's
    `norm_topk_prob` / `routed_scaling_factor`).  The router's product
    runs in float32 (`highest`: a TPU's default would round both operands
    to bf16), so near-tied experts are chosen as the float32 model does."""
    logits = jnp.matmul(x_f32, router, precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, topk)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    elif scaling != 1.0:
        gate_vals = gate_vals * scaling
    return probs, gate_vals, gate_idx


def _route_cfg(xf: jax.Array, router: jax.Array, cfg: ModelConfig):
    return _route(xf.astype(jnp.float32), router.astype(jnp.float32),
                  cfg.moe_topk, cfg.norm_topk_prob, cfg.routed_scaling_factor)


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


# ----------------------------------------------------------- one device
def _held_gates(gate_vals: jax.Array, gate_idx: jax.Array, n_held: int
                ) -> jax.Array:
    """(T, n_held) gate of each held expert for each token (0 where the
    token did not route to it)."""
    hit = gate_idx[..., None] == jnp.arange(n_held)          # (T, k, E_h)
    return jnp.sum(jnp.where(hit, gate_vals[..., None], 0.0), axis=1)


def _shared(params: Params, x: jax.Array, y: jax.Array, cfg: ModelConfig):
    if "shared" not in params:
        return y
    from repro.nn.layers import mlp
    with jax.named_scope("moe_shared"):
        return y + mlp(params["shared"], x, cfg.act)


def moe_reference(params: Params, x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """One device's share, plainly (the oracle): route over every expert,
    then each held expert in turn over every token with its gate (0 where
    not routed), plus the shared experts.  Exact, no capacity drops."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    probs, gate_vals, gate_idx = _route_cfg(xf, params["router"], cfg)
    out = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(cfg.experts_held):
        w = (gate_vals * (gate_idx == e)).sum(-1)  # (T,)
        h = _act(xf @ params["w_gate"][e].astype(x.dtype), cfg.act) * (
            xf @ params["w_up"][e].astype(x.dtype))
        y = (h @ params["w_down"][e].astype(x.dtype)).astype(jnp.float32)
        out = out + w[:, None] * y
    aux = _aux_loss(probs, gate_idx, cfg.n_experts)
    y = out.astype(x.dtype).reshape(B, S, D)
    return _shared(params, x, y, cfg), aux


def _experts_dense(params: Params, xf: jax.Array, gates: jax.Array,
                   act: str) -> jax.Array:
    """Every held expert over every token, weighted by its gates (T, E_h):
    each expert's weights are read once (the decode batch)."""
    dt = xf.dtype
    g = jnp.einsum("td,edf->etf", xf, params["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->etf", xf, params["w_up"].astype(dt))
    y = jnp.einsum("etf,efd->etd", _act(g, act) * u,
                   params["w_down"].astype(dt))
    return jnp.einsum("te,etd->td", gates, y.astype(jnp.float32))


def _experts_grouped(params: Params, xf: jax.Array, gate_vals: jax.Array,
                     gate_idx: jax.Array, n_held: int, act: str
                     ) -> jax.Array:
    """Each (token, slot) pair routed to a held expert through that expert
    alone: pairs sorted by expert (the rest last, in no group), one
    grouped matmul per projection, results scattered back by gate."""
    T, k = gate_idx.shape
    dt = xf.dtype
    expert = gate_idx.reshape(-1)
    key = jnp.where(expert < n_held, expert, n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    token = (jnp.arange(T * k, dtype=jnp.int32) // k)[order]
    here = key[order] < n_held
    rows = xf[token]
    g = jax.lax.ragged_dot(rows, params["w_gate"].astype(dt), sizes)
    u = jax.lax.ragged_dot(rows, params["w_up"].astype(dt), sizes)
    y = jax.lax.ragged_dot(_act(g, act) * u, params["w_down"].astype(dt),
                           sizes).astype(jnp.float32)
    # rows past the groups are the pairs routed elsewhere: no expert here
    w = jnp.where(here, gate_vals.reshape(-1)[order], 0.0)
    y = jnp.where(here[:, None], y, 0.0) * w[:, None]
    return jnp.zeros((T, xf.shape[1]), jnp.float32).at[token].add(y)


def moe_local(params: Params, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One device's share of the layer (module docstring).  Returns
    (y, aux loss, routed): routed (B, S) int32 counts each token's top-k
    slots that went to a held expert."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    n_held = cfg.experts_held
    with jax.named_scope("moe_route"):
        probs, gate_vals, gate_idx = _route_cfg(xf, params["router"], cfg)
        aux = _aux_loss(probs, gate_idx, cfg.n_experts)
        routed = jnp.sum(gate_idx < n_held, axis=-1, dtype=jnp.int32)
    with jax.named_scope("moe_experts"):
        if S == 1:
            out = _experts_dense(params, xf,
                                 _held_gates(gate_vals, gate_idx, n_held),
                                 cfg.act)
        else:
            out = _experts_grouped(params, xf, gate_vals, gate_idx, n_held,
                                   cfg.act)
        y = out.astype(x.dtype).reshape(B, S, D)
    return _shared(params, x, y, cfg), aux, routed.reshape(B, S)


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
    probs, gate_vals, gate_idx = _route_cfg(xf, router, cfg)
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
    probs, gate_vals, gate_idx = _route_cfg(xf, router, cfg)
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
        decode: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(y, aux loss, routed): routed (B, S) int32 counts each token's top-k
    slots that went to an expert held here (every slot over a mesh)."""
    ctx = current_mesh()
    if ctx is None:
        return moe_local(params, x, cfg)
    if cfg.experts_held != cfg.n_experts:
        raise ValueError(f"{cfg.name}: expert parallelism over a mesh holds "
                         f"every expert (n_experts_held must be 0)")
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
    routed = jnp.full(x.shape[:2], cfg.moe_topk, jnp.int32)
    return y, aux, routed
