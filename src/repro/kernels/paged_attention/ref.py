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


def mla_decode_ref(q_lat, q_rope, c_pool, r_pool, tables, pos,
                   scale: float) -> jax.Array:
    """Absorbed latent attention over one layer's pages: q_lat (B, H, R),
    q_rope (B, H, Dr); c_pool (P, page, R), r_pool (P, page, Dr); tables
    (B, T) int32; pos (B,) int32.  Returns the (B, H, R) float32 context
    Σ_j softmax_j((q_lat·c_j + q_rope·k_rope_j) · scale) c_j over positions
    ≤ pos[b]."""
    B, T = tables.shape
    page = c_pool.shape[1]
    c = c_pool[tables].reshape(B, T * page, -1).astype(jnp.float32)
    kr = r_pool[tables].reshape(B, T * page, -1).astype(jnp.float32)
    s = (jnp.einsum("bhr,bkr->bhk", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhd,bkd->bhk", q_rope.astype(jnp.float32), kr))
    valid = jnp.arange(T * page)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s * scale, -1e30)
    return jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(s, axis=-1), c)
