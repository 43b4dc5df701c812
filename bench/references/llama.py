"""Plain float32 reference of the dense Llama-family decoder that the
minicpm-2b and yi-34b configurations describe, in straightforward
`jax.numpy`: no cache, no kernels, no batching, nothing imported from the
program.

Equations (Hugging Face `LlamaForCausalLM`, and MiniCPM without its muP
scales, as the configuration files note): x = E[tokens]; per layer
x += Wo attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x)) with causal softmax
over head_dim^-1/2 scores, query head h reading KV head h // (H / Hkv);
x += Wdown(silu(Wgate n2(x)) * Wup n2(x)); logits = nf(x) Head, with
Head = E^T when the embeddings are tied.  n(x) = x / sqrt(mean(x^2) + eps)
* scale.  Rope rotates the two halves of each head.

It reads the benchmark's canonical weights (`bench/weights.py`), bf16
values taken exactly into float32, and runs one layer at a time over one
sequence, its queries in blocks, at `highest` matmul precision.

`mode` gives the control: "int8" or "fp8" fake-quantizes both operands of
every linear layer (weights per output column, activations per token)
before the float32 product, the step below the bf16 that the
configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_BLOCK = 256
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def _quant(x, mode, axis):
    """Fake quantization with a symmetric absmax scale per slice along
    `axis` (the contraction axis)."""
    if mode is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control mode {mode!r}")


def _linear(x, w, mode):
    """x (..., k) float32 times w (k, n)."""
    w = w.astype(jnp.float32)
    return jnp.matmul(_quant(x, mode, -1), _quant(w, mode, 0),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, D) at positions 0..S-1."""
    S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer(x, w, layer, *, dims, mode):
    H, Hkv, D, eps, theta = dims
    S = x.shape[0]

    def take(name):
        return jax.lax.dynamic_index_in_dim(w[name], layer, 0,
                                            keepdims=False)

    h = _rms(x, take("attn_norm"), eps)
    q = _rope(_linear(h, take("wq"), mode).reshape(S, H, D), theta)
    k = _rope(_linear(h, take("wk"), mode).reshape(S, Hkv, D), theta)
    v = _linear(h, take("wv"), mode).reshape(S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    kpos = jnp.arange(S)[None, None, :]
    outs = []
    for b in range(0, S, Q_BLOCK):
        qb = q[b:b + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / np.sqrt(D)
        qpos = (b + jnp.arange(qb.shape[0]))[None, :, None]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(S, H * D)
    x = x + _linear(o, take("wo"), mode)
    h = _rms(x, take("mlp_norm"), eps)
    g = _linear(h, take("w_gate"), mode)
    u = _linear(h, take("w_up"), mode)
    return x + _linear(jax.nn.silu(g) * u, take("w_down"), mode)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "mode"))
def _logits(x, final_norm, head, *, vocab, eps, mode):
    """Logits over the first `vocab` entries for rows x (n, d)."""
    return _linear(_rms(x, final_norm, eps), head, mode)[:, :vocab]


def _padded_len(n: int) -> int:
    """Sequence lengths are padded at the end to a power of two (at least
    one query block), so a handful of programs serve every request; the
    causal mask keeps the padding out of every real position."""
    size = Q_BLOCK
    while size < n:
        size *= 2
    return size


def logits(w: dict, hf: dict, tokens, positions, mode=None) -> jax.Array:
    """Float32 logits (len(positions), vocab) of the next token at each of
    `positions` of the sequence `tokens`."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(_padded_len(n), np.int32)
    padded[:n] = tokens
    d = hf["hidden_size"]
    H = hf["num_attention_heads"]
    dims = (H, hf.get("num_key_value_heads", H),
            hf.get("head_dim", d // H), float(hf["rms_norm_eps"]),
            float(hf.get("rope_theta", 10000.0)))
    layers = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "mlp_norm", "w_gate", "w_up", "w_down")}
    x = jnp.take(w["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
    for layer in range(hf["num_hidden_layers"]):
        x = _layer(x, layers, jnp.int32(layer), dims=dims, mode=mode)
    head = w["head"] if "head" in w else w["embed"].T
    m = len(positions)
    pos = np.zeros(-(-m // HEAD_BLOCK) * HEAD_BLOCK, np.int32)
    pos[:m] = positions
    rows = x[jnp.asarray(pos)]
    out = [_logits(rows[b:b + HEAD_BLOCK], w["final_norm"], head,
                   vocab=hf["vocab_size"], eps=dims[3], mode=mode)
           for b in range(0, len(pos), HEAD_BLOCK)]
    return jnp.concatenate(out, 0)[:m]
