"""Pure-jnp oracle for paged attention decode (gather + naive softmax,
fp32) — the same math as the paged decode path in `repro.nn.attention`."""
import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pool, v_pool, tables, pos) -> jax.Array:
    """q (B, H, D); k/v_pool (P, page, Hkv*D); tables (B, T) int32;
    pos (B,) int32.  Returns (B, H, D): one decode step attending over
    positions ≤ pos[b] gathered through each row's page table."""
    B, H, D = q.shape
    P, page, HD = k_pool.shape
    Hkv = HD // D
    T = tables.shape[1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    kc = k_pool[tables].reshape(B, T * page, Hkv, D)
    vc = v_pool[tables].reshape(B, T * page, Hkv, D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    valid = jnp.arange(T * page)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, vc.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)
