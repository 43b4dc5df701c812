"""Pallas flash attention (causal / full), TPU-targeted.

Grid: (batch·kv_heads·groups, Sq/bq).  Each program streams the KV sequence
in ``bk`` blocks with the online-softmax recurrence, keeping the running
(max, denom, acc) in VMEM — the standard FlashAttention-2 schedule mapped to
MXU tiles.  Causal programs skip KV blocks strictly above the diagonal via
the fori_loop upper bound (real work skipping, unlike the masked XLA path —
this is the kernel's main win at long sequence).

Contract matches `repro.nn.attention.chunked_attention` (its jnp math is the
oracle in tests).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, causal: bool,
            scale: float, seq_kv: int, seq_kv_valid: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    D = q.shape[-1]

    n_kv = seq_kv // bk
    if causal:
        # process blocks j with j*bk <= (qi+1)*bq - 1
        hi = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
    else:
        hi = n_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)   # (bk, D)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                    # (bq, bk)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos < seq_kv_valid
        if causal:
            ok = ok & (kpos <= qpos)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,      # (BH, Sq, D)
    k: jax.Array,      # (BH, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
    seq_kv_valid: int = None,
) -> jax.Array:
    BH, Sq, D = q.shape
    Skv = k.shape[1]
    assert Sq % bq == 0 and Skv % bk == 0, "pad sequences to block multiples"
    if seq_kv_valid is None:
        seq_kv_valid = Skv
    scale = 1.0 / math.sqrt(D)
    grid = (BH, Sq // bq)
    kern = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                             scale=scale, seq_kv=Skv,
                             seq_kv_valid=seq_kv_valid)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
