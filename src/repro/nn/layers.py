"""Basic layers: RMSNorm, embeddings, rotary, gated MLP — pure JAX.

Every ``init_*`` has a matching ``*_specs`` returning the same pytree
structure filled with logical PartitionSpec tuples (consumed by
`repro.parallel.sharding.make_spec`).  Weights are FSDP-sharded over 'fsdp'
(= data axis) and tensor-parallel over 'tp' (= model axis).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.config import ModelConfig
from repro.parallel.sharding import shard

Params = Dict[str, Any]
Specs = Dict[str, Any]


def _init(key, shape, scale_axis: int, dtype=jnp.float32):
    fan_in = shape[scale_axis]
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(fan_in)


# ---------------------------------------------------------------- RMSNorm
def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_specs() -> Specs:
    return {"scale": (None,)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * params["scale"]).astype(dt)


# ---------------------------------------------------------------- Embedding
def init_embedding(key, cfg: ModelConfig) -> Params:
    p = {"table": jax.random.normal(key, (cfg.vocab_padded, cfg.d_model)) * 0.02}
    if not cfg.tie_embeddings:
        p["head"] = _init(jax.random.fold_in(key, 1),
                          (cfg.d_model, cfg.vocab_padded), 0)
    return p


def embedding_specs(cfg: ModelConfig) -> Specs:
    s = {"table": ("tp", "fsdp")}
    if not cfg.tie_embeddings:
        s["head"] = ("fsdp", "tp")
    return s


def embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["table"].astype(jnp.bfloat16)[tokens]
    return shard(x, "batch", "sp", None)


def unembed(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    table = params.get("head")
    if table is None:
        table = params["table"].T
    logits = jnp.einsum("btd,dv->btv", x, table.astype(jnp.bfloat16))
    return shard(logits, "batch", None, "tp")


# ---------------------------------------------------------------- Rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 · mscale · ln(factor) + 1 (1 when
    the factor does not stretch)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_inv_freq(dim: int, theta: float, y) -> np.ndarray:
    """Hugging Face `DeepseekV2YarnRotaryEmbedding`'s inverse frequencies:
    the base ones for dims below the beta_fast correction dim, the base
    ones / factor above the beta_slow one, a linear ramp between."""
    def corr_dim(rotations: float) -> float:
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / base, 1.0 / (y.factor * base)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_angles(positions: jax.Array, dim: int, theta: float,
                scaling=None) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., dim//2).  `scaling` is a
    `config.YarnScaling` (None: plain rotary frequencies)."""
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim))
    else:
        freqs = jnp.asarray(_yarn_inv_freq(dim, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, dim); cos/sin: (..., seq, dim//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dt)


# ---------------------------------------------------------------- Gated MLP
def init_mlp(key, d_in: int, d_ff: int, gated: bool) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": _init(ks[0], (d_in, d_ff), 0),
         "w_down": _init(ks[1], (d_ff, d_in), 0)}
    if gated:
        p["w_gate"] = _init(ks[2], (d_in, d_ff), 0)
    return p


def mlp_specs(gated: bool) -> Specs:
    s = {"w_up": ("fsdp", "tp"), "w_down": ("tp", "fsdp")}
    if gated:
        s["w_gate"] = ("fsdp", "tp")
    return s


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def mlp(params: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    """x: (..., d).  Hidden activations are TP-sharded over 'tp'."""
    h = jnp.einsum("...d,df->...f", x, params["w_up"].astype(x.dtype))
    if "w_gate" in params:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(x.dtype))
        h = _act(g, act) * h
    else:
        h = _act(h, act)
    h = shard(h, "batch", None, "tp")
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(x.dtype))
