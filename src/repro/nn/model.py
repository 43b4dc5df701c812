"""Top-level language model: embeddings → stack → norm → logits, plus the
training loss and the prefill/decode entry points used by serving.

Input contract (`batch` dict):
  tokens        (B, S) int32          — LM families
  embeds        (B, S, d_model)       — stubbed modality frontend (hubert)
  patch_embeds  (B, P, d_model)       — stubbed vision frontend (paligemma)
  loss_mask     (B, S) f32 optional   — 1.0 where loss is counted
  targets       (B, S) int32 optional — explicit labels (encoder models)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.config import ModelConfig
from repro.nn.layers import embed, embedding_specs, init_embedding, init_rmsnorm, rmsnorm, rmsnorm_specs, unembed
from repro.nn.ssm import init_mamba_cache, init_mlstm_cache, init_slstm_cache
from repro.nn.transformer import (
    apply_stack,
    init_stack,
    layer_kind,
    stack_specs,
)
from repro.parallel.sharding import shard

Params = Dict[str, Any]


def init_params(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "embedding": init_embedding(k1, cfg),
        "stack": init_stack(k2, cfg),
        "final_norm": init_rmsnorm(cfg.d_model),
    }


# Matmul weights: the forward pass reads each of these only through
# `.astype(activation dtype)`, and activations are bf16, so holding them in
# bf16 changes no computed value.  Norm scales, biases, SSM state
# parameters, MoE routers and MLA's float32 absorbed projections (w_uk,
# w_uv) keep their dtype.
BF16_WEIGHTS = frozenset({
    "table", "head", "w_q", "w_k", "w_v", "w_o", "w_up", "w_down", "w_gate",
    "w_dkv", "w_kr", "w_in", "w_out", "w_xproj", "w_dt", "w_if", "w_gates",
})


def serving_params(params: Params) -> Params:
    """`params` with every BF16_WEIGHTS leaf cast to bf16 (half the device
    memory of float32, identical forward outputs)."""
    def cast(path, leaf):
        if getattr(path[-1], "key", None) in BF16_WEIGHTS:
            return leaf.astype(jnp.bfloat16)
        return leaf
    return jax.tree_util.tree_map_with_path(cast, params)


def init_serving_params(key, cfg: ModelConfig) -> Params:
    """`serving_params(init_params(key, cfg))` on the default device, cast
    inside one jitted init so no float32 copy of the whole model is ever
    held there."""
    return jax.jit(lambda k: serving_params(init_params(k, cfg)))(key)


def param_specs(cfg: ModelConfig) -> Params:
    return {
        "embedding": embedding_specs(cfg),
        "stack": stack_specs(cfg),
        "final_norm": rmsnorm_specs(),
    }


def _inputs_to_x(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Returns (x, prefix_len)."""
    if cfg.input_mode == "embeddings":
        return batch["embeds"].astype(jnp.bfloat16), 0
    if cfg.input_mode == "prefix_vlm" and "patch_embeds" in batch:
        tok = embed(params["embedding"], batch["tokens"], cfg)
        pat = batch["patch_embeds"].astype(tok.dtype)
        x = jnp.concatenate([pat, tok], axis=1)
        return shard(x, "batch", "sp", None), pat.shape[1]
    return embed(params["embedding"], batch["tokens"], cfg), 0


def forward(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    caches=None,
    cache_pos=None,
    make_cache: bool = False,
    cache_len: int = 0,
    last_only: bool = False,
    page_table=None,
    kernel_backend: str = "xla",
    kernel_interpret: bool = False,
) -> Tuple[jax.Array, Optional[Any], Dict[str, jax.Array]]:
    """Returns (logits, new_caches, aux): aux as `nn.transformer.zero_aux`
    names it, summed over layers.  ``last_only`` restricts the unembed to
    the final position (prefill/decode)."""
    with jax.named_scope("embed"):
        x, prefix_len = _inputs_to_x(params, batch, cfg)
    x, new_caches, aux = apply_stack(
        params["stack"], x, cfg, prefix_len=prefix_len, caches=caches,
        cache_pos=cache_pos, make_cache=make_cache, cache_len=cache_len,
        page_table=page_table,
        kernel_backend=kernel_backend, kernel_interpret=kernel_interpret)
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if last_only:
            x = x[:, -1:]
        logits = unembed(params["embedding"], x, cfg)
    return logits, new_caches, aux


def _chunked_ce(params, x, targets, loss_mask, cfg: ModelConfig,
                chunk: int = 512) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over the (huge, vocab-parallel) logits, computed in
    sequence chunks so the full (B, S, V) tensor never materializes."""
    B, S, _ = x.shape
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        loss_mask = jnp.pad(loss_mask, ((0, 0), (0, pad)))
    n = (S + pad) // chunk
    xs = (x.reshape(B, n, chunk, -1).swapaxes(0, 1),
          targets.reshape(B, n, chunk).swapaxes(0, 1),
          loss_mask.reshape(B, n, chunk).swapaxes(0, 1))

    vocab_ok = jnp.arange(cfg.vocab_padded) < cfg.vocab

    vocab_iota = jnp.arange(cfg.vocab_padded, dtype=jnp.int32)

    def body(carry, blk):
        tot, cnt = carry
        xb, tb, mb = blk
        logits = unembed(params["embedding"], xb, cfg).astype(jnp.float32)
        logits = jnp.where(vocab_ok, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # NOT take_along_axis: a dynamic gather over the vocab-sharded axis
        # makes GSPMD all-gather the full logits (GBs); a masked reduction
        # stays sharded and psums a (B, chunk) scalar field instead.
        picked = jnp.sum(
            jnp.where(vocab_iota == tb[..., None], logits, 0.0), axis=-1)
        nll = (lse - picked) * mb
        return (tot + jnp.sum(nll), cnt + jnp.sum(mb)), None

    carry = (jnp.zeros(()), jnp.zeros(()))
    if cfg.unroll_chunks:
        for i in range(n):
            carry, _ = body(carry, jax.tree.map(lambda a, i=i: a[i], xs))
        tot, cnt = carry
    else:
        (tot, cnt), _ = jax.lax.scan(body, carry, xs)
    return tot / jnp.maximum(cnt, 1.0), cnt


def lm_loss(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, prefix_len = _inputs_to_x(params, batch, cfg)
    x, _, aux = apply_stack(params["stack"], x, cfg, prefix_len=prefix_len)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)

    if cfg.is_encoder:
        targets = batch["targets"]
        mask = batch.get("loss_mask",
                         jnp.ones(targets.shape, jnp.float32)).astype(jnp.float32)
        ce, cnt = _chunked_ce(params, x, targets, mask, cfg)
    else:
        tokens = batch["tokens"]
        if cfg.input_mode == "prefix_vlm":
            # loss only over text positions (x includes the image prefix)
            x = x[:, prefix_len:]
        targets = tokens[:, 1:]
        xx = x[:, :-1]
        mask = batch.get("loss_mask", jnp.ones(tokens.shape, jnp.float32))
        mask = mask[:, 1:].astype(jnp.float32)
        ce, cnt = _chunked_ce(params, xx, targets, mask, cfg)

    loss = ce + cfg.router_aux_weight * aux["loss"]
    return loss, {"ce": ce, "aux": aux["loss"], "tokens": cnt}


# ------------------------------------------------------------------ cache
def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, staging: bool = False):
    """Allocate decode caches, mirroring the stack's segment plan:
    scanned segments get stacked (length, ...) caches, singles get dicts.
    ``staging=True`` gives the chunked-prefill staging layout instead:
    sliding-window layers keep full ``cache_len`` buffers (every position
    stored, the window applied in the score mask, the ring produced only at
    arena-install time) and int8 tenants keep raw bf16 K/V (quantization is
    deferred to the install, exactly like the monolithic prefill quantizes
    once after attending in full precision)."""
    from repro.nn.transformer import stack_plan

    def attn_cache(window: int):
        if cfg.attn_type == "mla":
            return {
                "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
            }
        L = min(window, cache_len) if window and not staging else cache_len
        int8 = cfg.kv_cache_dtype == "int8" and not staging
        kv_dt = jnp.int8 if int8 else dtype
        out = {
            "k": shard(jnp.zeros((batch, L, cfg.n_kv_heads, cfg.head_dim), kv_dt),
                       "batch", "sp", None, None),
            "v": shard(jnp.zeros((batch, L, cfg.n_kv_heads, cfg.head_dim), kv_dt),
                       "batch", "sp", None, None),
        }
        if int8:
            out["k_scale"] = shard(
                jnp.zeros((batch, L, cfg.n_kv_heads), jnp.float32),
                "batch", "sp", None)
            out["v_scale"] = shard(
                jnp.zeros((batch, L, cfg.n_kv_heads), jnp.float32),
                "batch", "sp", None)
        return out

    def layer_cache(i: int):
        kind = layer_kind(cfg, i)
        if kind == "mlstm":
            return init_mlstm_cache(cfg, batch)
        if kind == "slstm":
            return init_slstm_cache(cfg, batch)
        if kind == "hybrid":
            return {"attn": attn_cache(cfg.window_for_layer(i)),
                    "mamba": init_mamba_cache(cfg, batch, dtype)}
        return {"attn": attn_cache(cfg.window_for_layer(i))}

    caches = []
    for start, length, scanned in stack_plan(cfg):
        one = layer_cache(start)
        if scanned:
            one = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (length,) + a.shape), one)
        caches.append(one)
    return caches


def prefill(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            cache_len: int):
    """Run the prompt through the model, returning (next_token_logits, caches)."""
    logits, caches, _ = forward(params, batch, cfg, make_cache=True,
                                cache_len=cache_len, last_only=True)
    return logits[:, 0], caches


def chunk_prefill(params: Params, tokens: jax.Array, caches, start, n_valid,
                  cfg: ModelConfig):
    """One chunked-prefill step: run ``tokens`` (B, C) at absolute positions
    [start, start+C) against the staging ``caches`` built by earlier chunks
    (``init_cache(..., staging=True)`` zeros for the first chunk).
    Only the first ``n_valid`` tokens are real; the padded tail writes K/V
    the position masks never admit and leaves recurrent state frozen.
    Returns (logits at position start + n_valid - 1, updated caches) — the
    last chunk's logits are the prompt's next-token distribution, exactly
    as ``prefill`` returns it."""
    B, C = tokens.shape
    positions = (start + jnp.arange(C))[None, :]
    x = embed(params["embedding"], tokens, cfg)
    x, new_caches, _ = apply_stack(
        params["stack"], x, cfg, positions=positions, caches=caches,
        cache_pos=start, valid_len=n_valid)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = unembed(params["embedding"], x, cfg)
    return logits[:, 0], new_caches


def decode_step(params: Params, token: jax.Array, caches, pos,
                cfg: ModelConfig, page_table=None,
                kernel_backend: str = "xla", kernel_interpret: bool = False):
    """One autoregressive step.  token (B,) int32; pos scalar or (B,) int32.
    With ``page_table`` (B, T), caches are page pools and pos must be the
    per-row (B,) write positions (see serving.paging).  kernel_backend
    routes paged attention through the Pallas kernels (trace-time
    constant; see nn.attention).  Returns (logits, caches, aux), aux the
    layers' summed aux (`forward`)."""
    batch = {"tokens": token[:, None]}
    logits, new_caches, aux = forward(params, batch, cfg, caches=caches,
                                      cache_pos=pos, last_only=True,
                                      page_table=page_table,
                                      kernel_backend=kernel_backend,
                                      kernel_interpret=kernel_interpret)
    return logits[:, 0], new_caches, aux
