"""Attention: GQA/MQA/MLA, sliding-window, prefix-LM and encoder masks.

Three execution paths:
  * plain      — full-score softmax, for short sequences and decode;
  * chunked    — double-scan online-softmax ("flash" in XLA; the Pallas TPU
                 kernel in `repro.kernels.flash_attention` implements the
                 same contract), bounded memory at 32k+ sequence lengths;
  * banded     — sliding-window attention via static-size dynamic slices:
                 O(S·w) compute instead of O(S²) masking.

All paths accumulate in fp32 and share a single mask rule:
  valid(i, j) = j <= i + prefix OR not causal, AND i - j < window (if windowed)
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import mla_decode as _pallas_mla
from repro.kernels.paged_attention.ops import paged_attention as _pallas_paged
from repro.nn.config import ModelConfig
from repro.nn.layers import (_init, apply_rope, init_rmsnorm, rmsnorm,
                             rope_angles, yarn_mscale)
from repro.parallel.sharding import shard

Params = Dict[str, Any]

NEG_INF = -2.0e38


# =================================================================== masks
def _mask(
    qpos: jax.Array, kpos: jax.Array, *, causal: bool, window: int, prefix_len: int
) -> jax.Array:
    """qpos (..., Q), kpos (..., K) -> bool (..., Q, K)."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    if causal:
        ok = k <= q
        if prefix_len:
            ok = ok | (k < prefix_len)
    else:
        ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if window > 0:
        ok = ok & (q - k < window)
    return ok


def _softmax_attend(q, k, v, mask, softcap: float) -> jax.Array:
    """q (B,Q,Hkv,G,D), k/v (B,K,Hkv,D), mask (B|1,Q,K) -> (B,Q,Hkv,G,D)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))


# ============================================================ chunked path
def chunked_attention(
    q: jax.Array,            # (B, Sq, Hkv, G, D)
    k: jax.Array,            # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    prefix_len: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    B, Sq, Hkv, G, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)

    if window > 0:
        return _banded_attention(q, k, v, window=window, softcap=softcap,
                                 q_chunk=q_chunk, unroll=unroll)

    if softcap == 0.0:
        # Flash path: custom-VJP online softmax — backward recomputes blocks
        # instead of saving O(S²/chunk) probabilities (repro.nn.flash).
        from repro.nn.flash import flash_chunked, flash_chunked_unrolled
        qf = q.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (B,Hkv,G,Sq,D)
        kf = k.transpose(0, 2, 1, 3).astype(jnp.float32)     # (B,Hkv,Skv,D)
        vf = v.transpose(0, 2, 1, 3).astype(jnp.float32)
        fn = flash_chunked_unrolled if unroll else flash_chunked
        o = fn(qf, kf, vf, causal, prefix_len, q_chunk, kv_chunk)
        return o.transpose(0, 3, 1, 2, 4)                    # (B,Sq,Hkv,G,D)

    nq = math.ceil(Sq / q_chunk)
    nk = math.ceil(Skv / kv_chunk)
    q_pad = nq * q_chunk - Sq
    k_pad = nk * kv_chunk - Skv
    qp = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    kp = kp.reshape(B, nk, kv_chunk, Hkv, D)
    vp = vp.reshape(B, nk, kv_chunk, Hkv, D)

    def outer(qi, q_blk):
        qpos = qi * q_chunk + jnp.arange(q_chunk)

        def inner(carry, blk):
            m_run, l_run, acc = carry
            kj, k_blk, v_blk = blk
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            ok = _mask(qpos, kpos, causal=causal, window=0, prefix_len=prefix_len)
            ok = ok & (kpos < Skv)[None, :]
            s = jnp.einsum("bqhgd,bkhd->bhgqk",
                           q_blk.astype(jnp.float32), k_blk.astype(jnp.float32))
            s = s * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, D), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for j in range(nk):
                carry, _ = inner(carry, (jnp.int32(j), kp[:, j], vp[:, j]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                inner, (m0, l0, a0),
                (jnp.arange(nk), kp.swapaxes(0, 1), vp.swapaxes(0, 1)),
            )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (B, qc, Hkv, G, D)

    qp = qp.reshape(B, nq, q_chunk, Hkv, G, D)
    if unroll:
        outs = jnp.stack([outer(jnp.int32(i), qp[:, i]) for i in range(nq)], 0)
    else:
        outs = jax.lax.map(lambda args: outer(*args),
                           (jnp.arange(nq), qp.swapaxes(0, 1)))
    out = outs.swapaxes(0, 1).reshape(B, nq * q_chunk, Hkv, G, D)
    return out[:, :Sq]


def _banded_attention(q, k, v, *, window: int, softcap: float, q_chunk: int,
                      unroll: bool = False):
    """Sliding-window attention: each q chunk attends a static-size
    [window + q_chunk] KV band fetched with dynamic_slice — O(S·w)."""
    B, Sq, Hkv, G, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    nq = math.ceil(Sq / q_chunk)
    q_pad = nq * q_chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
    band = window + q_chunk
    # Left-pad K/V by `window` so the band slice start is never negative.
    kp = jnp.pad(k, ((0, 0), (window, q_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, q_pad), (0, 0), (0, 0)))

    def outer(qi, q_blk):
        start = qi * q_chunk  # band covers original positions [start-w, start+qc)
        k_blk = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        qpos = start + jnp.arange(q_chunk)
        kpos = start - window + jnp.arange(band)
        ok = _mask(qpos, kpos, causal=True, window=window, prefix_len=0)
        ok = ok & (kpos >= 0)[None, :] & (kpos < Skv)[None, :]
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q_blk.astype(jnp.float32), k_blk.astype(jnp.float32)) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_blk.astype(jnp.float32))
        return out

    qp = qp.reshape(B, nq, q_chunk, Hkv, G, D)
    if unroll:
        outs = jnp.stack([outer(jnp.int32(i), qp[:, i]) for i in range(nq)], 0)
    else:
        outs = jax.lax.map(lambda args: outer(*args),
                           (jnp.arange(nq), qp.swapaxes(0, 1)))
    out = outs.swapaxes(0, 1).reshape(B, nq * q_chunk, Hkv, G, D)
    return out[:, :Sq]



# ======================================================== int8 KV cache
def _kv_quant(x: jax.Array):
    """Symmetric per-(token, head) int8 quantization of K/V slices.
    x: (B, S, H, D) -> (codes int8, scale f32 (B, S, H))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _kv_dequant(codes: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ================================================================== module
def init_attention(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if cfg.attn_type == "mla":
        p = {
            "w_q": _init(ks[0], (d, cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)), 0),
            "w_dkv": _init(ks[1], (d, cfg.kv_lora_rank), 0),
            "w_kr": _init(ks[2], (d, cfg.qk_rope_dim), 0),
            "w_uk": _init(ks[3], (cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_dim), 0),
            "w_uv": _init(ks[4], (cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim), 0),
            "w_o": _init(ks[5], (cfg.n_heads * cfg.v_head_dim, d), 0),
            "kv_norm": init_rmsnorm(cfg.kv_lora_rank),
        }
    else:
        p = {
            "w_q": _init(ks[0], (d, cfg.q_dim), 0),
            "w_k": _init(ks[1], (d, cfg.kv_dim), 0),
            "w_v": _init(ks[2], (d, cfg.kv_dim), 0),
            "w_o": _init(ks[3], (cfg.q_dim, d), 0),
        }
        if cfg.qk_norm:
            p["q_norm"] = init_rmsnorm(cfg.head_dim)
            p["k_norm"] = init_rmsnorm(cfg.head_dim)
    return p


def attention_specs(cfg: ModelConfig) -> Params:
    if cfg.attn_type == "mla":
        s = {
            "w_q": ("fsdp", "tp"), "w_dkv": ("fsdp", None), "w_kr": ("fsdp", None),
            "w_uk": ("fsdp", "tp"), "w_uv": ("fsdp", "tp"), "w_o": ("tp", "fsdp"),
            "kv_norm": {"scale": (None,)},
        }
    else:
        s = {"w_q": ("fsdp", "tp"), "w_k": ("fsdp", "tp"),
             "w_v": ("fsdp", "tp"), "w_o": ("tp", "fsdp")}
        if cfg.qk_norm:
            s["q_norm"] = {"scale": (None,)}
            s["k_norm"] = {"scale": (None,)}
    return s


def _gqa_qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("btd,dq->btq", x, params["w_q"].astype(x.dtype))
    k = jnp.einsum("btd,dq->btq", x, params["w_k"].astype(x.dtype))
    v = jnp.einsum("btd,dq->btq", x, params["w_v"].astype(x.dtype))
    q = q.reshape(B, S, Hkv, H // Hkv, D)
    k = k.reshape(B, S, Hkv, D)
    v = v.reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    cos, sin = rope_angles(positions, D, cfg.rope_theta, cfg.rope_scaling)
    qf = q.reshape(B, S, Hkv * (H // Hkv), D)
    qf = apply_rope(qf, cos, sin).reshape(B, S, Hkv, H // Hkv, D)
    k = apply_rope(k, cos, sin)
    return qf, k, v


def attention(
    params: Params,
    x: jax.Array,                       # (B, S, d_model)
    cfg: ModelConfig,
    *,
    layer_window: int = 0,
    positions: Optional[jax.Array] = None,
    prefix_len: int = 0,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    make_cache: bool = False,
    cache_len: int = 0,
    page_table: Optional[jax.Array] = None,
    cache_layer=0,
    kernel_backend: str = "xla",
    kernel_interpret: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Unified attention entry point.

    * train:   cache=None, make_cache=False
    * prefill: cache=None, make_cache=True (cache_len ≥ S)
    * decode:  cache given, S == 1, cache_pos = current position
    * chunked prefill: cache given, S > 1, cache_pos = scalar chunk start —
      this chunk's K/V land at absolute positions [cache_pos, cache_pos+S)
      of a full-length staging cache (windowed layers store every position
      and mask the window; no ring until arena install)
    * paged decode: cache leaves are page pools stacked over layers
      (L, P, page, ...) — GQA K/V pools (L, P, page, Hkv*D) — read and
      written in place at layer `cache_layer` (a single layer's pools
      pass as a stack of one, at layer 0), and page_table (B, T) maps
      each row's logical blocks to physical pages (cache_pos must be a
      per-row (B,) vector); the returned cache is the whole stack

    kernel_backend="pallas" routes eligible paged decode (single-token;
    GQA without sliding window or logit softcap, or MLA) through a Pallas
    kernel (`paged_attention`, or the latent `mla_decode`), which walks
    only the pages at or below each row's position instead of gathering
    the full table width; everything else falls back to the XLA path.
    kernel_interpret is the kernel's interpret mode (the engine passes
    `repro.kernels.interpret_mode()`) — both are trace-time constants.
    """
    B, S, _ = x.shape
    with jax.named_scope("attn_proj"):
        if positions is None:
            positions = jnp.arange(S)[None, :] if cache_pos is None else (
                cache_pos[:, None] if cache_pos.ndim else
                jnp.full((B, 1), cache_pos)
            )
            if positions.shape[0] == 1 and B > 1:
                positions = jnp.broadcast_to(positions, (B, S))

    if cfg.attn_type == "mla":
        return _mla_attention(params, x, cfg, positions=positions,
                              prefix_len=prefix_len, cache=cache,
                              cache_pos=cache_pos, make_cache=make_cache,
                              cache_len=cache_len, page_table=page_table,
                              cache_layer=cache_layer,
                              kernel_backend=kernel_backend,
                              kernel_interpret=kernel_interpret)

    with jax.named_scope("attn_proj"):
        q, k, v = _gqa_qkv(params, x, cfg, positions)
    new_cache = None
    o = None

    if cache is not None:
        if page_table is not None:
            # Paged decode: scatter the new token's K/V into its physical
            # page, then attend over the row's pages.  SWA layers store
            # full positions and mask the window (no ring buffer).
            use_pallas = (kernel_backend == "pallas" and S == 1
                          and layer_window <= 0 and cfg.logit_softcap <= 0)
            if use_pallas:
                # Pallas kernel walks only pages at/below each row's
                # position — no full-width gather.  Windowed/softcap
                # layers (none in the paged configs today) fall back to
                # the XLA path below.  The kernel reads layer
                # `cache_layer` of the stacked pools in place.  int8 pools
                # are dequantized elementwise first (this layer's, as a
                # stack of one): identical values to the XLA path's
                # gather-then-dequant.
                H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                with jax.named_scope("kv_pool"):
                    new_cache = _paged_scatter_gqa(cache, k, v, cfg,
                                                   cache_pos, page_table,
                                                   cache_layer)
                    if cfg.kv_cache_dtype == "int8":
                        kp, vp = (_pool_dequant(
                            new_cache[n][cache_layer],
                            new_cache[n + "_scale"][cache_layer],
                            k.dtype)[None] for n in ("k", "v"))
                        layer = 0
                    else:
                        kp, vp = new_cache["k"], new_cache["v"]
                        layer = cache_layer
                with jax.named_scope("paged_attention"):
                    o = _pallas_paged(q[:, 0].reshape(B, H, D), kp, vp,
                                      page_table, cache_pos, layer,
                                      interpret=kernel_interpret)
                    o = o.reshape(B, 1, Hkv, H // Hkv, D)
            else:
                with jax.named_scope("kv_pool"):
                    kc, vc, new_cache = _paged_append_gqa(
                        cache, k, v, cfg, cache_pos, page_table, cache_layer)
                Sc = kc.shape[1]
                kpos = jnp.arange(Sc)[None, :]
                cp = cache_pos[:, None]
                valid = kpos <= cp
                if layer_window > 0:
                    valid = valid & (kpos > cp - layer_window)
                valid = valid[:, None, :]                 # (B, 1, Sc)
        else:
            # Decode: append to the ring/full cache then attend over it.
            # SWA layers keep a ring buffer of `window` slots
            # (slot = pos % window); make_cache emits an exactly-window-sized
            # ring once the cache budget reaches the window, so the boundary
            # must accept `==` — a strictly-smaller cache is a full cache the
            # window never binds on.  Chunked prefill (S > 1) instead appends
            # at absolute positions into a full-length staging cache:
            # windowed layers store every position and mask the window in
            # the scores; the ring conversion happens when the staging cache
            # is installed into the serving arena (launch.steps).
            chunked = S > 1
            ring = (layer_window
                    if not chunked and 0 < layer_window <= cache["k"].shape[1]
                    else 0)
            slot = cache_pos % ring if ring else cache_pos
            # int8 tenants chunk-prefill into a *raw* bf16 staging cache
            # (quantization happens once at arena install, matching the
            # monolithic prefill's attend-raw-then-quantize order)
            if cfg.kv_cache_dtype == "int8" and not chunked:
                kq, ks = _kv_quant(k)
                vq, vs = _kv_quant(v)
                kc8 = _dus_batch(cache["k"], kq, slot)
                vc8 = _dus_batch(cache["v"], vq, slot)
                kss = _dus_batch(cache["k_scale"], ks, slot)
                vss = _dus_batch(cache["v_scale"], vs, slot)
                kc8 = shard(kc8, "batch", "sp", None, None)
                vc8 = shard(vc8, "batch", "sp", None, None)
                new_cache = {"k": kc8, "v": vc8, "k_scale": kss,
                             "v_scale": vss}
                kc = _kv_dequant(kc8, kss, k.dtype)
                vc = _kv_dequant(vc8, vss, v.dtype)
            else:
                kc = _dus_batch(cache["k"], k, slot)
                vc = _dus_batch(cache["v"], v, slot)
                kc = shard(kc, "batch", "sp", None, None)
                vc = shard(vc, "batch", "sp", None, None)
                new_cache = {"k": kc, "v": vc}
            Sc = kc.shape[1]
            kpos = jnp.arange(Sc)[None, :]
            if chunked:
                # Per-query causal (+window) mask over the staging cache:
                # query i sits at absolute position cache_pos + i.
                qpos = (cache_pos + jnp.arange(S))[:, None]
                valid = kpos <= qpos
                if layer_window > 0:
                    valid = valid & (qpos - kpos < layer_window)
                valid = valid[None]                       # (1, S, Sc)
            else:
                # cp: (1, 1) scalar broadcast or (B, 1) per-sequence
                # positions — the continuous-batching engine decodes a slot
                # batch where every row sits at a different position.
                cp = cache_pos[:, None] if jnp.ndim(cache_pos) else cache_pos
                if ring:
                    # Absolute position held by slot i: the largest p ≤
                    # cache_pos with p ≡ i (mod ring).
                    abs_pos = cp - ((cp - kpos) % ring)
                    valid = (abs_pos >= 0) & (abs_pos > cp - ring)
                else:
                    valid = kpos <= cp
                valid = valid[:, None, :]                 # (B|1, 1, Sc)
        if o is None:
            # the XLA path's attend (over the gathered pages when paged)
            with (jax.named_scope("paged_attention") if page_table is not None
                  else contextlib.nullcontext()):
                scale = 1.0 / math.sqrt(cfg.head_dim)
                s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                               kc.astype(jnp.float32)) * scale
                if cfg.logit_softcap > 0:
                    s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
                s = jnp.where(valid[:, None, None], s, NEG_INF)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                               vc.astype(jnp.float32))
    else:
        if S <= 1024:
            mask = _mask(positions, positions, causal=cfg.causal,
                         window=layer_window, prefix_len=prefix_len)
            o = _softmax_attend(q, k, v, mask, cfg.logit_softcap)
        else:
            o = chunked_attention(q, k, v, causal=cfg.causal,
                                  window=layer_window if cfg.causal else 0,
                                  prefix_len=prefix_len,
                                  softcap=cfg.logit_softcap,
                                  q_chunk=cfg.attn_q_chunk,
                                  kv_chunk=cfg.attn_kv_chunk,
                                  unroll=cfg.unroll_chunks)
        if make_cache:
            L = cache_len or S
            ring = layer_window if 0 < layer_window <= L else 0
            Lc = ring if ring else L
            int8 = cfg.kv_cache_dtype == "int8"
            if int8:
                k_st, ks_full = _kv_quant(k)
                v_st, vs_full = _kv_quant(v)
                kc = jnp.zeros((B, Lc, cfg.n_kv_heads, cfg.head_dim), jnp.int8)
                ksc = jnp.zeros((B, Lc, cfg.n_kv_heads), jnp.float32)
                vsc = jnp.zeros_like(ksc)
            else:
                k_st, v_st = k, v
                kc = jnp.zeros((B, Lc, cfg.n_kv_heads, cfg.head_dim), k.dtype)
            vc = jnp.zeros_like(kc)
            if ring:
                # Keep the last `ring` tokens at slot = pos % ring.
                n_keep = min(S, ring)
                keep_pos = jnp.arange(S - n_keep, S)
                slots = keep_pos % ring
                kc = kc.at[:, slots].set(k_st[:, -n_keep:])
                vc = vc.at[:, slots].set(v_st[:, -n_keep:])
                if int8:
                    ksc = ksc.at[:, slots].set(ks_full[:, -n_keep:])
                    vsc = vsc.at[:, slots].set(vs_full[:, -n_keep:])
            else:
                kc = kc.at[:, :S].set(k_st)
                vc = vc.at[:, :S].set(v_st)
                if int8:
                    ksc = ksc.at[:, :S].set(ks_full)
                    vsc = vsc.at[:, :S].set(vs_full)
            kc = shard(kc, "batch", "sp", None, None)
            vc = shard(vc, "batch", "sp", None, None)
            new_cache = {"k": kc, "v": vc}
            if int8:
                new_cache.update({"k_scale": ksc, "v_scale": vsc})

    with jax.named_scope("attn_proj"):
        o = o.astype(x.dtype).reshape(B, S, cfg.q_dim)
        y = jnp.einsum("btq,qd->btd", o, params["w_o"].astype(x.dtype))
    return y, new_cache


def _paged_ops(pool_leaf, cache_pos, page_table, layer):
    """Scatter/gather closures over one page pool shape family.

    Pool leaves carry (L, P, page, ...), page pools stacked over layers;
    `layer` is the one this call reads and writes, cache_pos (B,) each
    row's write position and page_table (B, T) its block→physical-page
    map.  `scatter` writes this step's (B, 1, ...) entries in place at
    (layer, physical page, offset); `gather` rebuilds the layer's
    row-ordered (B, T·page, ...) view in one gather.  Neither slices the
    layer's pool out of the stack.  Updated pools keep the slot path's
    sharding annotation (page axis in the batch role, no-op without a
    mesh) so sharded serving doesn't silently lose the KV constraint.

    Each active row's target page is exclusively owned (the arena COWs
    shared pages before the step), so the scatter rows never collide except
    on the reserved scratch page that inactive rows aim at — whose contents
    are never gathered.
    """
    page = pool_leaf.shape[2]
    B, T = page_table.shape
    block, offset = cache_pos // page, cache_pos % page
    phys = page_table[jnp.arange(B), block]

    def scatter(pool, new):
        pool = pool.at[layer, phys, offset].set(new[:, 0].astype(pool.dtype))
        return shard(pool, None, "batch", "sp", *((None,) * (pool.ndim - 3)))

    def gather(pool):
        return pool[layer, page_table].reshape((B, T * page) + pool.shape[3:])

    return scatter, gather


def _flat_heads(x: jax.Array) -> jax.Array:
    """(..., Hkv, D) -> (..., Hkv*D): the GQA page-pool layout, which keeps
    every page one lane-dense tile (see `serving.paging.init_page_pool`)."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _pool_dequant(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """int8 pool codes (..., Hkv*D) with per-(token, head) scales
    (..., Hkv) -> dequantized (..., Hkv*D), elementwise `_kv_dequant`."""
    Hkv = scale.shape[-1]
    split = codes.reshape(codes.shape[:-1] + (Hkv, codes.shape[-1] // Hkv))
    return _flat_heads(_kv_dequant(split, scale, dtype))


def _paged_scatter_gqa(cache, k, v, cfg: ModelConfig, cache_pos, page_table,
                       layer):
    """Write this step's (B, 1, Hkv, D) K/V projections into layer `layer`
    of stacked GQA page pools (L, P, page, Hkv*D) (+ int8 scales
    (L, P, page, Hkv)); returns the updated cache.  The Pallas path reads
    the pools through the page table itself; `_paged_append_gqa` gathers
    them for the XLA path."""
    scatter, _ = _paged_ops(cache["k"], cache_pos, page_table, layer)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
        return {"k": scatter(cache["k"], _flat_heads(kq)),
                "v": scatter(cache["v"], _flat_heads(vq)),
                "k_scale": scatter(cache["k_scale"], ks),
                "v_scale": scatter(cache["v_scale"], vs)}
    return {"k": scatter(cache["k"], _flat_heads(k)),
            "v": scatter(cache["v"], _flat_heads(v))}


def _paged_append_gqa(cache, k, v, cfg: ModelConfig, cache_pos, page_table,
                      layer):
    """Paged decode append + gather for GQA caches: scatter as
    `_paged_scatter_gqa`, then return (kc, vc, new_cache) with kc/vc
    gathered from layer `layer` to (B, T·page, Hkv, D) in logical position
    order."""
    new_cache = _paged_scatter_gqa(cache, k, v, cfg, cache_pos, page_table,
                                   layer)
    _, gather = _paged_ops(cache["k"], cache_pos, page_table, layer)
    shape = k.shape[:1] + (-1,) + k.shape[2:]
    if cfg.kv_cache_dtype == "int8":
        kc = _pool_dequant(gather(new_cache["k"]),
                           gather(new_cache["k_scale"]), k.dtype)
        vc = _pool_dequant(gather(new_cache["v"]),
                           gather(new_cache["v_scale"]), v.dtype)
    else:
        kc, vc = gather(new_cache["k"]), gather(new_cache["v"])
    return kc.reshape(shape), vc.reshape(shape), new_cache


def _dus_batch(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """dynamic_update_slice along axis 1 at (possibly traced) position."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype),
                                                   pos, axis=1)
    # per-batch positions
    def upd(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), p, axis=0)
    return jax.vmap(upd)(cache, new, pos)


# ==================================================================== MLA
def mla_temperature(cfg: ModelConfig) -> float:
    """YaRN's mscale(factor, mscale_all_dim)² when the rotary embedding is
    YaRN-scaled with an `mscale_all_dim` (Hugging Face DeepSeek-V2), else
    1: the factor on MLA's softmax scale."""
    y = cfg.rope_scaling
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(d_nope + d_rope)^-1/2 · `mla_temperature`."""
    return mla_temperature(cfg) / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)


def rope_pool_width(cfg: ModelConfig, platform: Optional[str] = None) -> int:
    """Lane width of a paged rope-key pool on `platform` (default: JAX's
    default backend).  A TPU DMA moves whole 128-lane tiles, so on a TPU
    the latent decode kernel needs the rope dim rounded up to whole tiles
    (a 64-wide last dim takes 128 lanes in device memory anyway, so the
    pool's footprint does not change); elsewhere it is the rope dim.
    Lanes past the rope dim hold zeros."""
    if (platform or jax.default_backend()) == "tpu":
        return -(-cfg.qk_rope_dim // 128) * 128
    return cfg.qk_rope_dim


def pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of x to `width`."""
    extra = width - x.shape[-1]
    if extra <= 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def _mla_attention(params, x, cfg: ModelConfig, *, positions, prefix_len,
                   cache, cache_pos, make_cache, cache_len,
                   page_table=None, cache_layer=0, kernel_backend="xla",
                   kernel_interpret=False):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = mla_softmax_scale(cfg)

    with jax.named_scope("attn_proj"):
        q = jnp.einsum("btd,dq->btq", x, params["w_q"].astype(x.dtype))
        q = q.reshape(B, S, H, nope + rope_d)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        cos, sin = rope_angles(positions, rope_d, cfg.rope_theta,
                               cfg.rope_scaling)
        q_rope = apply_rope(q_rope, cos, sin)

        c_kv = jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(x.dtype))
        c_kv = rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
        k_rope = jnp.einsum("btd,dr->btr", x, params["w_kr"].astype(x.dtype))
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]

    new_cache = None
    chunked = cache is not None and S > 1
    if cache is not None and not chunked:
        # ---- absorbed-matmul decode (DeepSeek-V2 §Low-Rank KV) ----
        # Never materialize per-head K/V from the latent cache: fold W_uk
        # into the query and W_uv into the output —
        #   score = (q_nope W_ukᵀ)·c_kv + q_rope·k_rope
        #   out   = W_uv (Σ_s p_s c_kv_s)
        # per cached token that is H·(r + d_rope) multiply-adds for the
        # score and H·r for the sum, against H·r·(d_nope + d_v) to expand
        # its K and V first (≈100× fewer at DeepSeek-V2-Lite's widths).
        w_uk = params["w_uk"].astype(jnp.float32).reshape(
            cfg.kv_lora_rank, H, nope)
        w_uv = params["w_uv"].astype(jnp.float32).reshape(
            cfg.kv_lora_rank, H, vdim)
        with jax.named_scope("attn_proj"):
            q_lat = jnp.einsum("bshn,rhn->bshr",
                               q_nope.astype(jnp.float32), w_uk)
        ctx = None
        if page_table is not None:
            # Paged latent cache: scatter this token's (c_kv, k_rope) into
            # its physical page of layer `cache_layer` in place (the rope
            # key zero-padded to the pool's lane width)
            scatter, gather = _paged_ops(cache["c_kv"], cache_pos,
                                         page_table, cache_layer)
            width = cache["k_rope"].shape[-1]
            with jax.named_scope("kv_pool"):
                new_cache = {"c_kv": scatter(cache["c_kv"], c_kv),
                             "k_rope": scatter(cache["k_rope"],
                                               pad_lanes(k_rope, width))}
            if kernel_backend == "pallas":
                # the latent kernel walks only each row's live pages of
                # this layer; W_uv and W_o stay outside it
                with jax.named_scope("paged_attention"):
                    ctx = _pallas_mla(
                        q_lat[:, 0], pad_lanes(q_rope[:, 0], width),
                        new_cache["c_kv"], new_cache["k_rope"], page_table,
                        cache_pos, cache_layer, scale=scale,
                        interpret=kernel_interpret)[:, None]
            else:
                # the XLA path (CPU tests): gather the row's pages back
                # into logical order, then the same absorbed math
                ckv_c = gather(new_cache["c_kv"])
                kr_c = gather(new_cache["k_rope"])[..., :rope_d]
        else:
            ckv_c = _dus_batch(cache["c_kv"], c_kv, cache_pos)
            kr_c = _dus_batch(cache["k_rope"], k_rope, cache_pos)
            ckv_c = shard(ckv_c, "batch", "sp", None)
            kr_c = shard(kr_c, "batch", "sp", None)
            new_cache = {"c_kv": ckv_c, "k_rope": kr_c}
        if ctx is None:
            Sc = ckv_c.shape[1]
            cp = cache_pos[:, None] if jnp.ndim(cache_pos) else cache_pos
            valid = (jnp.arange(Sc)[None, :] <= cp)[:, None, :]
            with (jax.named_scope("paged_attention")
                  if page_table is not None else contextlib.nullcontext()):
                s = jnp.einsum("bshr,bkr->bhsk", q_lat,
                               ckv_c.astype(jnp.float32))
                s = s + jnp.einsum("bshd,bkd->bhsk",
                                   q_rope.astype(jnp.float32),
                                   kr_c.astype(jnp.float32))
                s = s * scale
                s = jnp.where(valid[:, None], s, NEG_INF)
                p = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bhsk,bkr->bshr", p,
                                 ckv_c.astype(jnp.float32))
        with jax.named_scope("attn_proj"):
            o = jnp.einsum("bshr,rhv->bshv", ctx, w_uv)
            o = o.astype(x.dtype).reshape(B, S, H * vdim)
            y = jnp.einsum("btq,qd->btd", o, params["w_o"].astype(x.dtype))
        return y, new_cache

    if chunked:
        # Chunked prefill: append this chunk's latents into the staging
        # buffer, then materialize per-head K/V from the whole buffer the
        # way the monolithic prefill does — identical numerics per position,
        # so chunked and monolithic prefills agree bitwise.  The
        # absorbed-matmul trick stays decode-only (one token amortizes the
        # re-expansion; a prefill recomputes it anyway).
        ckv_c = _dus_batch(cache["c_kv"], c_kv, cache_pos)
        kr_c = _dus_batch(cache["k_rope"], k_rope, cache_pos)
        ckv_c = shard(ckv_c, "batch", "sp", None)
        kr_c = shard(kr_c, "batch", "sp", None)
        new_cache = {"c_kv": ckv_c, "k_rope": kr_c}
        c_all, kr_all = ckv_c, kr_c
        Sc = c_all.shape[1]
    else:
        c_all, kr_all = c_kv, k_rope
        Sc = S

    k_nope = jnp.einsum("btr,rq->btq", c_all, params["w_uk"].astype(x.dtype))
    k_nope = k_nope.reshape(B, Sc, H, nope)
    vv = jnp.einsum("btr,rq->btq", c_all, params["w_uv"].astype(x.dtype))
    vv = vv.reshape(B, Sc, H, vdim)

    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :], (B, Sc, H, rope_d))],
        axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)[:, :, :, None, :]
    qq = qq.reshape(B, S, H, 1, nope + rope_d)
    if mla_temperature(cfg) != 1.0:
        # YaRN's softmax temperature: every attend below scales scores by
        # (d_nope + d_rope)^-1/2 on float32 queries, so the rest of the
        # scale goes on the queries, in float32
        qq = qq.astype(jnp.float32) * mla_temperature(cfg)

    if chunked:
        # queries sit at `positions`; keys cover the whole staging buffer
        mask = _mask(positions, jnp.arange(Sc)[None, :], causal=cfg.causal,
                     window=0, prefix_len=prefix_len)
        o = _softmax_attend(qq, k, vv, mask, 0.0)
    elif S <= 1024:
        mask = _mask(positions, positions, causal=cfg.causal, window=0,
                     prefix_len=prefix_len)
        o = _softmax_attend(qq, k, vv, mask, 0.0)
    else:
        o = chunked_attention(qq, k, vv, causal=cfg.causal,
                              prefix_len=prefix_len,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk,
                              unroll=cfg.unroll_chunks)
    if make_cache:
        L = cache_len or S
        ckv_c = jnp.zeros((B, L, cfg.kv_lora_rank), c_kv.dtype).at[:, :S].set(c_kv)
        kr_c = jnp.zeros((B, L, rope_d), k_rope.dtype).at[:, :S].set(k_rope)
        new_cache = {"c_kv": shard(ckv_c, "batch", "sp", None),
                     "k_rope": shard(kr_c, "batch", "sp", None)}

    o = o.astype(x.dtype).reshape(B, S, H * vdim)
    y = jnp.einsum("btq,qd->btd", o, params["w_o"].astype(x.dtype))
    return y, new_cache
