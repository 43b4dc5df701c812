"""Jitted wrapper; folds (B, H) into one grid axis and pads sequences."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128, bk: int = 128):
    """q: (B, Sq, H, D); k/v: (B, Skv, H, D) (same head count — expand GQA
    before calling).  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    pq, pk = (-Sq) % bq, (-Skv) % bk
    qt = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    qt = qt.reshape(B * H, Sq + pq, D)
    kt = kt.reshape(B * H, Skv + pk, D)
    vt = vt.reshape(B * H, Skv + pk, D)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, bq=bq, bk=bk,
                               interpret=interpret_mode(),
                               seq_kv_valid=Skv)
    o = o.reshape(B, H, Sq + pq, D).transpose(0, 2, 1, 3)
    return o[:, :Sq]
