"""Block assembly and the layer stack.

Homogeneous stacks (the deep dense/MoE models) run under `jax.lax.scan` over
stacked per-layer parameters with full rematerialization — HLO size stays
O(1) in depth and only block inputs are saved for backward.  Heterogeneous
stacks (hymba's per-layer windows, xlstm's mLSTM/sLSTM mix, deepseek's
leading dense layer) are unrolled; their layer counts are modest.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.attention import attention, attention_specs, init_attention
from repro.nn.config import ModelConfig
from repro.nn.layers import (
    init_mlp,
    init_rmsnorm,
    mlp,
    mlp_specs,
    rmsnorm,
    rmsnorm_specs,
)
from repro.nn.moe import init_moe, moe, moe_specs
from repro.nn.ssm import (
    init_mamba,
    init_mlstm,
    init_slstm,
    mamba,
    mamba_specs,
    mlstm,
    mlstm_specs,
    slstm,
    slstm_specs,
)
from repro.parallel.sharding import shard

Params = Dict[str, Any]


def layer_kind(cfg: ModelConfig, i: int) -> str:
    if cfg.family == "ssm":
        if cfg.slstm_every and (i % cfg.slstm_every == cfg.slstm_every - 1):
            return "slstm"
        return "mlstm"
    if cfg.hybrid_parallel:
        return "hybrid"
    return "attn"


def is_homogeneous(cfg: ModelConfig) -> bool:
    kinds = {layer_kind(cfg, i) for i in range(cfg.n_layers)}
    if len(kinds) > 1:
        return False
    if cfg.sliding_window and cfg.global_layers:
        return False  # static mask structure differs per layer
    moe_flags = set(cfg.layer_is_moe)
    return len(moe_flags) <= 1


# ---------------------------------------------------------------- blocks
def init_block(key, cfg: ModelConfig, i: int) -> Params:
    kind = layer_kind(cfg, i)
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": init_rmsnorm(cfg.d_model)}
    if kind == "mlstm":
        p["cell"] = init_mlstm(ks[0], cfg)
        return p
    if kind == "slstm":
        p["cell"] = init_slstm(ks[0], cfg)
        return p
    p["attn"] = init_attention(ks[0], cfg)
    if kind == "hybrid":
        p["mamba"] = init_mamba(ks[1], cfg)
    p["norm2"] = init_rmsnorm(cfg.d_model)
    if cfg.layer_is_moe[i]:
        p["moe"] = init_moe(ks[2], cfg)
    else:
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.gated)
    return p


def block_specs(cfg: ModelConfig, i: int) -> Params:
    kind = layer_kind(cfg, i)
    s: Params = {"norm1": rmsnorm_specs()}
    if kind == "mlstm":
        s["cell"] = mlstm_specs(cfg)
        return s
    if kind == "slstm":
        s["cell"] = slstm_specs(cfg)
        return s
    s["attn"] = attention_specs(cfg)
    if kind == "hybrid":
        s["mamba"] = mamba_specs(cfg)
    s["norm2"] = rmsnorm_specs()
    if cfg.layer_is_moe[i]:
        s["moe"] = moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(cfg.gated)
    return s


def apply_block(
    bp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    i: int,
    *,
    positions=None,
    prefix_len: int = 0,
    cache: Optional[Params] = None,
    cache_pos=None,
    make_cache: bool = False,
    cache_len: int = 0,
    page_table=None,
    cache_layer=0,
    valid_len=None,
    kernel_backend: str = "xla",
    kernel_interpret: bool = False,
) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """Returns (x, new_cache, aux): aux as `zero_aux` gives it, this
    layer's router loss and, in a model with expert layers, the (B, S)
    count of each token's top-k slots routed to experts held here.
    ``valid_len`` marks how many of a chunked-prefill chunk's tokens are
    real (recurrent layers freeze their state past it; attention masks
    make it irrelevant there).  With a ``page_table`` the cache holds page
    pools stacked over layers and this block reads and writes layer
    ``cache_layer`` of them in place."""
    kind = layer_kind(cfg, i)
    aux = zero_aux(cfg, x)
    with jax.named_scope("attn_proj"):
        h = rmsnorm(bp["norm1"], x, cfg.norm_eps)

    if kind in ("mlstm", "slstm"):
        fn = mlstm if kind == "mlstm" else slstm
        y, new_cache = fn(bp["cell"], h, cfg, cache=cache,
                          make_cache=make_cache, valid_len=valid_len)
        x = x + y
        x = shard(x, "batch", "sp", None)
        return x, new_cache, aux

    window = cfg.window_for_layer(i)
    attn_cache = cache.get("attn") if cache else None
    y_attn, new_attn_cache = attention(
        bp["attn"], h, cfg, layer_window=window, positions=positions,
        prefix_len=prefix_len, cache=attn_cache, cache_pos=cache_pos,
        make_cache=make_cache, cache_len=cache_len, page_table=page_table,
        cache_layer=cache_layer, kernel_backend=kernel_backend,
        kernel_interpret=kernel_interpret)

    new_cache: Optional[Params] = None
    if kind == "hybrid":
        mamba_cache = cache.get("mamba") if cache else None
        y_ssm, new_mamba_cache = mamba(bp["mamba"], h, cfg, cache=mamba_cache,
                                       make_cache=make_cache,
                                       valid_len=valid_len)
        # hymba: mean of the two normalized branch outputs
        y = 0.5 * (y_attn + y_ssm)
        if new_attn_cache is not None or new_mamba_cache is not None:
            new_cache = {"attn": new_attn_cache, "mamba": new_mamba_cache}
    else:
        y = y_attn
        if new_attn_cache is not None:
            new_cache = {"attn": new_attn_cache}

    with jax.named_scope("attn_proj"):
        x = x + y
        x = shard(x, "batch", "sp", None)
    with jax.named_scope("mlp"):
        h2 = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if "moe" in bp:
            # multi-token chunked prefill takes the batch routing path
            # (same numerics as the monolithic prefill); only true
            # one-token steps use the replicated-token decode strategy
            y2, loss, routed = moe(
                bp["moe"], h2, cfg,
                decode=(cache is not None and x.shape[1] == 1))
            aux = {"loss": loss, "moe_routed": routed}
        else:
            y2 = mlp(bp["mlp"], h2, cfg.act)
        x = x + y2
        x = shard(x, "batch", "sp", None)
    return x, new_cache, aux


# ------------------------------------------------------------- the stack
def zero_aux(cfg: ModelConfig, x: jax.Array) -> Dict[str, jax.Array]:
    """A layer's auxiliary outputs before any are added: the router's
    load-balance loss and, when the model has expert layers, the (B, S)
    int32 count of top-k slots routed to experts held here."""
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if any(cfg.layer_is_moe):
        aux["moe_routed"] = jnp.zeros(x.shape[:2], jnp.int32)
    return aux


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _block_signature(cfg: ModelConfig, i: int):
    """Layers with equal signatures share block structure (and can scan)."""
    return (layer_kind(cfg, i), cfg.window_for_layer(i), cfg.layer_is_moe[i])


def stack_plan(cfg: ModelConfig, min_group: int = 4) -> List[Tuple[int, int, bool]]:
    """Partition layers into (start, length, scanned) segments: maximal runs
    of identical signatures become lax.scan groups (HLO stays O(#segments)),
    singletons/short runs unroll.  hymba → [g, scan·14, g, scan·15, g];
    deepseek → [dense, scan·26]; xlstm → [scan·7, s, scan·7, s, scan·7, s]."""
    if not cfg.scan_layers:
        min_group = max(min_group, 10**9)  # force full unroll if disabled
    segs: List[Tuple[int, int, bool]] = []
    i = 0
    while i < cfg.n_layers:
        j = i
        sig = _block_signature(cfg, i)
        while j < cfg.n_layers and _block_signature(cfg, j) == sig:
            j += 1
        run = j - i
        if run >= min_group:
            segs.append((i, run, True))
        else:
            segs.extend((k, 1, False) for k in range(i, j))
        i = j
    return segs


def init_stack(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers)
    segments = []
    for start, length, scanned in stack_plan(cfg):
        if scanned:
            segments.append(jax.vmap(
                lambda k, s=start: init_block(k, cfg, s))(
                    keys[start:start + length]))
        else:
            segments.append(init_block(keys[start], cfg, start))
    return {"segments": segments}


def stack_specs(cfg: ModelConfig) -> Params:
    segments = []
    for start, length, scanned in stack_plan(cfg):
        base = block_specs(cfg, start)
        if scanned:
            base = jax.tree.map(lambda spec: (None,) + tuple(spec), base,
                                is_leaf=lambda x: isinstance(x, tuple))
        segments.append(base)
    return {"segments": segments}


def apply_stack(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions=None,
    prefix_len: int = 0,
    caches: Optional[Any] = None,
    cache_pos=None,
    make_cache: bool = False,
    cache_len: int = 0,
    page_table=None,
    valid_len=None,
    kernel_backend: str = "xla",
    kernel_interpret: bool = False,
) -> Tuple[jax.Array, Optional[Any], Dict[str, jax.Array]]:
    aux_total = zero_aux(cfg, x)
    plan = stack_plan(cfg)
    new_caches: List[Any] = []
    any_cache = False

    for seg_idx, (start, length, scanned) in enumerate(plan):
        seg_params = params["segments"][seg_idx]
        seg_cache = caches[seg_idx] if caches is not None else None
        # page pools are read and written in place at a layer index: a
        # scanned segment's (L, P, page, ...) pools ride the loop's carry
        # whole, a single layer's pass as a stack of one (a bitcast)
        paged = page_table is not None and seg_cache is not None
        block = functools.partial(
            apply_block, cfg=cfg, i=start, positions=positions,
            prefix_len=prefix_len, cache_pos=cache_pos,
            make_cache=make_cache, cache_len=cache_len,
            page_table=page_table, valid_len=valid_len,
            kernel_backend=kernel_backend, kernel_interpret=kernel_interpret)

        if not scanned:
            if cfg.remat and seg_cache is None and not make_cache:
                x, nc, a = jax.checkpoint(
                    lambda b, v: block(b, v, cache=None),
                    prevent_cse=False)(seg_params, x)
            elif paged:
                x, nc, a = block(seg_params, x, cache=jax.tree.map(
                    lambda p: p.reshape((1,) + p.shape), seg_cache))
                nc = jax.tree.map(lambda p: p.reshape(p.shape[1:]), nc)
            else:
                x, nc, a = block(seg_params, x, cache=seg_cache)
            new_caches.append(nc)
            aux_total = _add(aux_total, a)
            any_cache = any_cache or nc is not None
            continue

        if seg_cache is None:
            def body(carry, bp):
                xx, aux = carry
                if cfg.remat:
                    fn = jax.checkpoint(lambda b, v: block(b, v, cache=None),
                                        prevent_cse=False)
                    xx_new, nc, a = fn(bp, xx)
                else:
                    xx_new, nc, a = block(bp, xx, cache=None)
                if nc is None:
                    nc = jnp.zeros((), jnp.float32)  # scan needs a leaf
                return (xx_new, _add(aux, a)), nc

            (x, aux_total), ncs = jax.lax.scan(body, (x, aux_total),
                                               seg_params)
        elif paged:
            # decode only: nothing differentiates it, so no remat
            def body(carry, layer_in):
                xx, aux, pools = carry
                bp, layer = layer_in
                xx_new, pools, a = block(bp, xx, cache=pools,
                                         cache_layer=layer)
                return (xx_new, _add(aux, a), pools), None

            (x, aux_total, ncs), _ = jax.lax.scan(
                body, (x, aux_total, seg_cache),
                (seg_params, jnp.arange(length, dtype=jnp.int32)))
        else:
            def body(carry, layer_in):
                xx, aux = carry
                bp, layer_cache = layer_in
                if cfg.remat:
                    fn = jax.checkpoint(lambda b, v, c: block(b, v, cache=c),
                                        prevent_cse=False)
                    xx_new, nc, a = fn(bp, xx, layer_cache)
                else:
                    xx_new, nc, a = block(bp, xx, cache=layer_cache)
                if nc is None:
                    nc = jnp.zeros((), jnp.float32)
                return (xx_new, _add(aux, a)), nc

            (x, aux_total), ncs = jax.lax.scan(body, (x, aux_total),
                                               (seg_params, seg_cache))
        if seg_cache is None and not make_cache:
            ncs = None
        new_caches.append(ncs)
        any_cache = any_cache or ncs is not None

    if not any_cache:
        new_caches = None
    return x, new_caches, aux_total
