"""Jittable step functions and their sharding trees.

Everything the dry-run lowers comes from here, so the launcher (train.py /
serve.py) and the dry-run exercise the *same* code.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.pipeline import DataConfig, make_batch_specs
from repro.nn.config import ModelConfig
from repro.nn.model import chunk_prefill, decode_step, init_cache, init_params, lm_loss, prefill, param_specs
from repro.nn.transformer import layer_kind
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.schedules import cosine, wsd
from repro.parallel.sharding import make_spec


# ----------------------------------------------------------------- steps
def make_train_step(cfg: ModelConfig, peak_lr: float = 3e-4,
                    warmup: int = 2000, total: int = 100_000):
    if cfg.name.startswith("minicpm"):
        sched = functools.partial(wsd, peak_lr=peak_lr, warmup=warmup,
                                  stable=int(total * 0.8),
                                  decay=int(total * 0.1))
    else:
        sched = functools.partial(cosine, peak_lr=peak_lr, warmup=warmup,
                                  total=total)

    def train_step(params, opt_state: AdamWState, batch, step):
        def loss_fn(p):
            return lm_loss(p, batch, cfg)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        lr = sched(step)
        new_params, new_opt, om = adamw_update(params, grads, opt_state, lr)
        out_metrics = {"loss": loss, "lr": lr, **metrics, **om}
        return new_params, new_opt, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, cache_len: int):
    def prefill_step(params, batch):
        return prefill(params, batch, cfg, cache_len)
    return prefill_step


# Root of the decode programs' named scopes; their parts are embed,
# attn_proj, kv_pool, paged_attention, mlp (with moe_route, moe_experts,
# moe_shared in expert layers), lm_head (nn.model, nn.transformer,
# nn.attention, nn.moe) and sample (below).  Scopes are metadata
# on the HLO ops: the compiled program is unchanged.
DECODE_SCOPE = "decode_step"


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, token, caches, pos):
        with jax.named_scope(DECODE_SCOPE):
            logits, new_caches, _ = decode_step(params, token, caches, pos,
                                                cfg)
        return logits, new_caches
    return serve_step


# Jit-compiled step cache shared by every engine/launcher instance touching
# the same config: ModelConfig is frozen/hashable, so two ServingEngine
# instances (e.g. the reuse-on/reuse-off benchmark arms) compile once.
@functools.lru_cache(maxsize=None)
def cached_prefill_step(cfg: ModelConfig, cache_len: int):
    return jax.jit(make_prefill_step(cfg, cache_len=cache_len))


@functools.lru_cache(maxsize=None)
def cached_serve_step(cfg: ModelConfig):
    """Batched decode step; `pos` may be a scalar or a per-row (B,) vector —
    the vector form is what slot-based continuous batching decodes with."""
    return jax.jit(make_serve_step(cfg), donate_argnums=(2,))


def make_chunk_prefill_step(cfg: ModelConfig, chunk_len: int, cache_len: int):
    def chunk_prefill_step(params, tokens, caches, start, n_valid):
        return chunk_prefill(params, tokens, caches, start, n_valid, cfg)
    return chunk_prefill_step


@functools.lru_cache(maxsize=None)
def cached_chunk_prefill_step(cfg: ModelConfig, chunk_len: int,
                              cache_len: int):
    """One chunked-prefill step: `tokens` (1, chunk_len) land at absolute
    positions [start, start+chunk_len) of a `cache_len` staging cache, with
    only the first `n_valid` real (the engine pads the tail chunk up to a
    bucket-ladder rung).  Keyed on the padded chunk length, so the number of
    LRU misses IS the number of distinct jit traces — with bucketing on it
    is bounded by the ladder size instead of growing with every new prompt
    length (see prefill_cache_info)."""
    return jax.jit(make_chunk_prefill_step(cfg, chunk_len, cache_len),
                   donate_argnums=(2,))


def prefill_cache_info() -> Dict[str, int]:
    """Hit/miss/trace counters over the prefill step caches (process-wide,
    shared by every engine instance of the same config — the compile-count
    tests and the bucketing benchmark read deltas of these)."""
    mono = cached_prefill_step.cache_info()
    chunk = cached_chunk_prefill_step.cache_info()
    return {
        "prefill_hits": mono.hits, "prefill_misses": mono.misses,
        "prefill_traces": mono.currsize,
        "chunk_hits": chunk.hits, "chunk_misses": chunk.misses,
        "chunk_traces": chunk.currsize,
        "hits": mono.hits + chunk.hits,
        "misses": mono.misses + chunk.misses,
        "traces": mono.currsize + chunk.currsize,
    }


# ----------------------------------------- chunked-prefill staging install
def _finalize_attn_entry(cfg: ModelConfig, entry, *, axis: int, window: int,
                         target_len: int, true_len, ring_windows: bool):
    """Convert one attention cache entry from the raw full-length staging
    layout to the serving-arena layout: quantize int8 tenants (staging
    attends in bf16, exactly like monolithic prefill, and quantizes once
    here), ring-gather sliding-window layers down to their window-sized
    ring (slot arenas only — page pools store full positions), and slice
    everything else to the arena length."""
    def shape_to(leaf):
        if ring_windows and 0 < window < target_len:
            # ring slot i holds the largest valid position ≡ i (mod window);
            # slots with no valid position yet gather clipped garbage that
            # the decode mask (abs_pos >= 0) never admits
            last = true_len - 1
            idx = last - ((last - jnp.arange(window)) % window)
            return jnp.take(leaf, idx, axis=axis, mode="clip")
        if leaf.shape[axis] > target_len:
            return jax.lax.slice_in_dim(leaf, 0, target_len, axis=axis)
        return leaf

    if cfg.attn_type == "mla":
        return {k: shape_to(v) for k, v in entry.items()}
    if cfg.kv_cache_dtype == "int8":
        from repro.nn.attention import _kv_quant
        kq, ks = _kv_quant(entry["k"])
        vq, vs = _kv_quant(entry["v"])
        return {"k": shape_to(kq), "v": shape_to(vq),
                "k_scale": shape_to(ks), "v_scale": shape_to(vs)}
    return {"k": shape_to(entry["k"]), "v": shape_to(entry["v"])}


def _make_stage_finalize(cfg: ModelConfig, target_len: int,
                         ring_windows: bool):
    from repro.nn.transformer import stack_plan
    plan = stack_plan(cfg)

    def finalize(staging, true_len):
        out = []
        for seg, (start, _, scanned) in zip(staging, plan):
            if isinstance(seg, dict) and "attn" in seg:
                fixed = dict(seg)
                fixed["attn"] = _finalize_attn_entry(
                    cfg, seg["attn"], axis=2 if scanned else 1,
                    window=cfg.window_for_layer(start),
                    target_len=target_len, true_len=true_len,
                    ring_windows=ring_windows)
                out.append(fixed)
            else:
                out.append(seg)    # pure recurrent state: length-free
        return out

    return finalize


@functools.lru_cache(maxsize=None)
def cached_stage_install(cfg: ModelConfig, staging_len: int, arena_len: int):
    """Staging → slot-arena row: ring windowed layers, slice the rest to
    `arena_len`, quantize int8 tenants.  Not donated: ring/slice outputs
    change leaf shapes, so donated staging buffers would never be reused
    (XLA warns instead)."""
    return jax.jit(_make_stage_finalize(cfg, arena_len, ring_windows=True))


@functools.lru_cache(maxsize=None)
def cached_stage_quantize(cfg: ModelConfig, staging_len: int):
    """Staging → paged-pool install source: page pools keep full positions
    (no ring), so this only quantizes int8 tenants.  NOT donated — the page
    writer slices several blocks out of the same finalized cache."""
    return jax.jit(_make_stage_finalize(cfg, staging_len,
                                        ring_windows=False))


def make_paged_serve_step(cfg: ModelConfig, kernel_backend: str = "xla",
                          interpret: bool = False):
    def paged_serve_step(params, token, caches, pos, page_table):
        with jax.named_scope(DECODE_SCOPE):
            logits, new_caches, _ = decode_step(
                params, token, caches, pos, cfg, page_table=page_table,
                kernel_backend=kernel_backend, kernel_interpret=interpret)
        return logits, new_caches
    return paged_serve_step


@functools.lru_cache(maxsize=None)
def cached_paged_serve_step(cfg: ModelConfig, kernel_backend: str = "xla",
                            interpret: bool = False):
    """Decode step over a paged KV arena: caches are page pools, `pos` is
    the per-row (B,) write positions, `page_table` (B, T) maps each row's
    logical blocks to physical pages (serving.paging builds both).
    `kernel_backend`/`interpret` are trace-time constants selecting the
    Pallas paged-attention kernel and its interpret mode (engine knob)."""
    return jax.jit(make_paged_serve_step(cfg, kernel_backend, interpret),
                   donate_argnums=(2,))


def _counter_names(cfg: ModelConfig):
    return ("moe_routed_here",) if any(cfg.layer_is_moe) else ()


def decode_counters(cfg: ModelConfig, out: np.ndarray):
    """Split a fused decode step's output into its (B,) token ids and a
    dict of the (B,) per-row counts the step appends after them: for a
    model with expert layers "moe_routed_here", each row's top-k slots
    routed to experts held here, summed over layers."""
    names = _counter_names(cfg)
    rows = len(out) // (1 + len(names))
    return out[:rows], {key: out[(i + 1) * rows:(i + 2) * rows]
                        for i, key in enumerate(names)}


@functools.lru_cache(maxsize=None)
def cached_fused_paged_serve_step(cfg: ModelConfig,
                                  kernel_backend: str = "xla",
                                  interpret: bool = False):
    """Paged decode step with sampling fused in: logits never leave the
    device — the jitted fn samples every row (greedy/temperature/top-k,
    `serving.sampling.sample_tokens`) and returns only the (B,) int32
    token ids, followed by per-row counts (one array: one device→host
    transfer; `decode_counters` splits it).  temps (B,) f32, top_ks (B,) int32, keys
    (B, 2) raw uint32 per-request PRNG roots, steps (B,) int32 fold_in
    indices."""
    from repro.serving.sampling import sample_tokens

    counters = _counter_names(cfg)

    def fused_paged_serve_step(params, token, caches, pos, page_table,
                               temps, top_ks, keys, steps):
        with jax.named_scope(DECODE_SCOPE):
            logits, new_caches, aux = decode_step(
                params, token, caches, pos, cfg, page_table=page_table,
                kernel_backend=kernel_backend, kernel_interpret=interpret)
            with jax.named_scope("sample"):
                toks = sample_tokens(logits, cfg.vocab, temperatures=temps,
                                     top_ks=top_ks, keys=keys, steps=steps)
            if counters:
                toks = jnp.concatenate([toks, aux["moe_routed"][:, 0]])
        return toks, new_caches

    return jax.jit(fused_paged_serve_step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def cached_sample_tokens(vocab: int):
    """Batched sampler for the split (non-fused) path: one jitted device
    call per decode batch instead of one host sync per row."""
    from repro.serving.sampling import sample_tokens

    def sample(logits, temps, top_ks, keys, steps):
        return sample_tokens(logits, vocab, temperatures=temps,
                             top_ks=top_ks, keys=keys, steps=steps)

    return jax.jit(sample)


# ------------------------------------------------------------- shardings
def _is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, (str, tuple)) for e in x)


def param_shardings(cfg: ModelConfig, mesh: Mesh):
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, make_spec(*s)), specs,
        is_leaf=_is_spec_leaf)


def opt_shardings(param_sh, mesh: Mesh):
    return AdamWState(step=NamedSharding(mesh, P()),
                      mu=param_sh, nu=param_sh)


def batch_shardings(cfg: ModelConfig, mesh: Mesh):
    ba = make_spec("batch")[0]
    out: Dict[str, NamedSharding] = {}
    d = DataConfig(seq_len=8, global_batch=8)  # structure only
    for k in make_batch_specs(cfg, d):
        if k in ("tokens", "targets", "loss_mask"):
            out[k] = NamedSharding(mesh, P(ba, None))
        else:  # embeds / patch_embeds: shard seq over model too
            out[k] = NamedSharding(mesh, P(ba, "model", None))
    return out


def _cache_entry_spec(cfg: ModelConfig, window: int, mesh: Mesh):
    ba = make_spec("batch")[0]
    if cfg.attn_type == "mla":
        return {"c_kv": NamedSharding(mesh, P(ba, "model", None)),
                "k_rope": NamedSharding(mesh, P(ba, "model", None))}
    out = {"k": NamedSharding(mesh, P(ba, "model", None, None)),
           "v": NamedSharding(mesh, P(ba, "model", None, None))}
    if cfg.kv_cache_dtype == "int8":
        out["k_scale"] = NamedSharding(mesh, P(ba, "model", None))
        out["v_scale"] = NamedSharding(mesh, P(ba, "model", None))
    return out


def batch_axis_for(mesh: Mesh, global_batch: int):
    """The batch mesh axes, or None (replicate) when the batch is too small
    to shard (e.g. long_500k's single sequence)."""
    ba = make_spec("batch")[0]
    if ba is None:
        return None
    axes = (ba,) if isinstance(ba, str) else tuple(ba)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return ba if global_batch % n == 0 else None


def cache_shardings(cfg: ModelConfig, mesh: Mesh, global_batch: int = 0):
    """Mirrors nn.model.init_cache structure (segment plan)."""
    from repro.nn.transformer import stack_plan
    ba = make_spec("batch")[0]
    if global_batch:
        ba = batch_axis_for(mesh, global_batch)
    rep = lambda *s: NamedSharding(mesh, P(*s))

    def entry(window):
        if cfg.attn_type == "mla":
            return {"c_kv": rep(ba, "model", None),
                    "k_rope": rep(ba, "model", None)}
        out = {"k": rep(ba, "model", None, None),
               "v": rep(ba, "model", None, None)}
        if cfg.kv_cache_dtype == "int8":
            out["k_scale"] = rep(ba, "model", None)
            out["v_scale"] = rep(ba, "model", None)
        return out

    def layer_spec(i: int):
        kind = layer_kind(cfg, i)
        if kind == "mlstm":
            return {"S": rep(ba, None, None, None), "n": rep(ba, None, None)}
        if kind == "slstm":
            return {"c": rep(ba, None, None), "n": rep(ba, None, None),
                    "h": rep(ba, None, None)}
        if kind == "hybrid":
            return {
                "attn": entry(cfg.window_for_layer(i)),
                "mamba": {"conv": rep(ba, None, "model"),
                          "h": rep(ba, "model", None)},
            }
        return {"attn": entry(cfg.window_for_layer(i))}

    out = []
    for start, length, scanned in stack_plan(cfg):
        one = layer_spec(start)
        if scanned:
            one = jax.tree.map(
                lambda sh: NamedSharding(mesh, P(None, *sh.spec)), one,
                is_leaf=lambda x: hasattr(x, "spec"))
        out.append(one)
    return out


# ------------------------------------------------------- abstract inputs
def _bf16_floats(tree):
    def cast(l):
        if jnp.issubdtype(l.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        return jax.ShapeDtypeStruct(l.shape, l.dtype)
    return jax.tree.map(cast, tree)


def abstract_params(cfg: ModelConfig):
    shapes = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return _bf16_floats(shapes)


def abstract_opt_state(aparams):
    return jax.eval_shape(adamw_init, aparams)


def abstract_caches(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, cache_len))


def input_specs(cfg: ModelConfig, shape_kind: str, seq_len: int,
                global_batch: int) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    if shape_kind == "train":
        data = DataConfig(seq_len=seq_len, global_batch=global_batch)
        return {
            "params": abstract_params(cfg),
            "opt_state": None,  # filled by caller
            "batch": make_batch_specs(cfg, data),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
    if shape_kind == "prefill":
        data = DataConfig(seq_len=seq_len, global_batch=global_batch)
        return {
            "params": abstract_params(cfg),
            "batch": make_batch_specs(cfg, data),
        }
    # decode: one token, cache of seq_len
    return {
        "params": abstract_params(cfg),
        "token": jax.ShapeDtypeStruct((global_batch,), jnp.int32),
        "caches": abstract_caches(cfg, global_batch, seq_len),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
