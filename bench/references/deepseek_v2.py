"""Plain float32 reference of the DeepSeek-V2 decoder that the
deepseek-v2-lite-e8 configuration describes, in straightforward
`jax.numpy`: no cache, no kernels, no batching, nothing imported from the
program.

Equations (Hugging Face `DeepseekV2ForCausalLM`, no q-LoRA): x = E[tokens];
per layer, with h = n1(x):
  q = h Wq, split per head into q_nope (d_nope) and q_pe (d_rope);
  c = nkv(h Wdkv) (the latent, kv_lora_rank wide), k_pe = h Wkr (one head);
  k_nope = c Wuk, v = c Wuv per head (the latent expanded, not absorbed);
  q_pe, k_pe rotated by YaRN's rotary embedding; scores = [q_nope, q_pe] ·
  [k_nope, k_pe] · (d_nope + d_rope)^-1/2 · mscale(factor,
  mscale_all_dim)²; causal softmax; x += Wo (p v).
Then, with h = n2(x): the leading `first_k_dense_replace` layers add
Wdown(silu(Wgate h) * Wup h); the others route: probs = softmax(h
Wrouter) over the router's every output (float32), the greedy top-k, their
gates renormalised to sum 1 if `norm_topk_prob`, else times
`routed_scaling_factor`; x += Σ over the held experts e of gate_e ·
FFN_e(h) (0 where e was not chosen) + FFN_shared(h), each FFN a gated
SiLU MLP.  logits = nf(x) Head.  n(x) = x / sqrt(mean(x²) + eps) · scale.

YaRN (Hugging Face `DeepseekV2YarnRotaryEmbedding`): inverse frequencies
θ^(-2i/d) for dims below the β_fast correction dim, θ^(-2i/d) / factor
above the β_slow one, a linear ramp between; cos and sin times
mscale(factor, mscale) / mscale(factor, mscale_all_dim); mscale(s, m) =
0.1 m ln s + 1.

Departures, each a relabelling that random weights cannot tell apart:
Hugging Face de-interleaves q_pe's and k_pe's dims (view(d/2, 2),
transpose) before rotating halves; here the halves are rotated as they
come, as the program does, which permutes the columns of Wq's rope part
and of Wkr.  With one device's share of the experts (the configuration's
`n_routed_experts` below `router_outputs`), the held experts are the
router's outputs 0 to n - 1 and the others add nothing, exactly as in the
program.

It reads the benchmark's canonical weights (`bench/weights.py`, the
family's leaves), bf16 values taken exactly into float32, and runs one
layer at a time over one sequence, its queries in blocks, at `highest`
matmul precision.

`mode` gives the control: "int8" or "fp8" fake-quantizes both operands of
every linear layer, the router's too (weights per output column,
activations per token), before the float32 product, the step below the
bf16 that the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.llama import _linear, _rms

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_BLOCK = 256


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def _yarn(hf: dict):
    """(inverse frequencies (d_rope / 2,), cos/sin scale, softmax factor)
    of the configuration's rotary embedding."""
    d, base = hf["qk_rope_head_dim"], float(hf.get("rope_theta", 10000.0))
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling")
    if not rs:
        return inv.astype(np.float32), 1.0, 1.0
    f, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    hi = min(math.ceil(corr(rs.get("beta_slow", 1))), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = inv / f * ramp + inv * (1.0 - ramp)
    m_all = rs.get("mscale_all_dim", 0)
    cs = _mscale(f, rs.get("mscale", 1)) / _mscale(f, m_all)
    soft = _mscale(f, m_all) ** 2 if m_all else 1.0
    return inv.astype(np.float32), cs, soft


def _rope(x, inv, cs):
    """x (S, heads, D) at positions 0..S-1: each half-pair (i, i + D/2)
    rotated by position · inv[i]."""
    S, _, D = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    c, s = (jnp.cos(ang) * cs)[:, None, :], (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _ffn(h, wg, wu, wd, mode):
    return _linear(jax.nn.silu(_linear(h, wg, mode)) * _linear(h, wu, mode),
                   wd, mode)


def _layer_of(w: dict, layer) -> dict:
    """Layer `layer` of stacked weights, indexed inside the jitted caller
    (no copy of the stack per layer, one program for every layer)."""
    return {k: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for k, a in w.items()}


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _attention(x, w, layer, inv, *, dims, mode):
    """x + the attention block, layer `layer` of the stacked weights `w`."""
    H, nope, rope, vd, eps, cs, scale = dims
    S = x.shape[0]
    w = _layer_of(w, layer)
    h = _rms(x, w["attn_norm"], eps)
    q = _linear(h, w["wq"], mode).reshape(S, H, nope + rope)
    c = _rms(_linear(h, w["w_dkv"], mode), w["kv_norm"], eps)
    k_pe = _rope(_linear(h, w["w_kr"], mode)[:, None, :], inv, cs)
    k_nope = _linear(c, w["w_uk"], mode).reshape(S, H, nope)
    v = _linear(c, w["w_uv"], mode).reshape(S, H, vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    kpos = jnp.arange(S)[None, None, :]
    outs = []
    for b in range(0, S, Q_BLOCK):
        qb = q[b:b + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        qpos = (b + jnp.arange(qb.shape[0]))[None, :, None]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(S, H * vd)
    return x + _linear(o, w["wo"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _dense_mlp(x, w, layer, *, eps, mode):
    w = _layer_of(w, layer)
    h = _rms(x, w["mlp_norm"], eps)
    return x + _ffn(h, w["w_gate"], w["w_up"], w["w_down"], mode)


@functools.partial(jax.jit,
                   static_argnames=("topk", "norm", "scaling", "eps", "mode"))
def _moe(x, w, layer, *, topk, norm, scaling, eps, mode):
    """x + the expert layer: the router over all its outputs, the held
    experts (the router's first outputs) weighted by their gates,
    the shared experts."""
    w = _layer_of(w, layer)
    h = _rms(x, w["mlp_norm"], eps)
    probs = jax.nn.softmax(_linear(h, w["router"], mode), axis=-1)
    vals, idx = jax.lax.top_k(probs, topk)
    if norm:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    else:
        vals = vals * scaling
    out = x
    for e in range(w["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == e, vals, 0.0), -1)      # (S,)
        y = _ffn(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], mode)
        out = out + gate[:, None] * y
    if "shared_w_up" in w:
        out = out + _ffn(h, w["shared_w_gate"], w["shared_w_up"],
                         w["shared_w_down"], mode)
    return out


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


ATTN = ("attn_norm", "wq", "w_dkv", "kv_norm", "w_kr", "w_uk", "w_uv", "wo",
        "mlp_norm")


def logits(w: dict, hf: dict, tokens, positions, mode=None) -> jax.Array:
    """Float32 logits (len(positions), vocab) of the next token at each of
    `positions` of the sequence `tokens`."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(_padded_len(n), np.int32)
    padded[:n] = tokens
    eps = float(hf["rms_norm_eps"])
    inv, cs, soft = _yarn(hf)
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dims = (hf["num_attention_heads"], nope, rope, hf["v_head_dim"], eps,
            cs, soft / math.sqrt(nope + rope))
    inv = jnp.asarray(inv)
    route = dict(topk=hf["num_experts_per_tok"],
                 norm=bool(hf["norm_topk_prob"]),
                 scaling=float(hf.get("routed_scaling_factor", 1.0)))
    x = jnp.take(w["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
    zero = jnp.int32(0)
    for i in range(hf["first_k_dense_replace"]):
        lw = {k: w[f"dense{i}/{k}"][None]
              for k in ATTN + ("w_gate", "w_up", "w_down")}
        x = _attention(x, lw, zero, inv, dims=dims, mode=mode)
        x = _dense_mlp(x, lw, zero, eps=eps, mode=mode)
    moe = {k[len("moe/"):]: a for k, a in w.items() if k.startswith("moe/")}
    for layer in range(hf["num_hidden_layers"] - hf["first_k_dense_replace"]):
        li = jnp.int32(layer)
        x = _attention(x, moe, li, inv, dims=dims, mode=mode)
        x = _moe(x, moe, li, eps=eps, mode=mode, **route)
    head = w["head"] if "head" in w else w["embed"].T
    m = len(positions)
    pos = np.zeros(-(-m // HEAD_BLOCK) * HEAD_BLOCK, np.int32)
    pos[:m] = positions
    rows = x[jnp.asarray(pos)]
    out = [_logits(rows[b:b + HEAD_BLOCK], w["final_norm"], head,
                   vocab=hf["vocab_size"], eps=eps, mode=mode)
           for b in range(0, len(pos), HEAD_BLOCK)]
    return jnp.concatenate(out, 0)[:m]
