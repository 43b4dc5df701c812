"""Wrapper: groups query heads per KV head (GQA stays native — no pool
expansion), with the execution mode plumbed in explicitly.

No `@jax.jit` here: callers (the serving decode step, the kernel tests)
jit the surrounding computation, and `interpret` must stay a trace-time
python constant they control."""
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_pallas


def paged_attention(q, k_pool, v_pool, tables, pos, layer, *,
                    interpret=None):
    """q: (B, H, D) one query token per row, heads flat in KV-major order
    (head h serves KV head h // (H/Hkv)); k/v_pool: (L, P, page, Hkv*D)
    page pools stacked over layers, KV heads flattened into the last axis;
    tables: (B, T) int32 physical page ids; pos: (B,) int32 per-row
    positions; layer: the () int32 index of the layer to read, in place.
    A single layer's pool is passed as a stack of one, at layer 0.
    Returns (B, H, D).

    `interpret=None` resolves from the platform (`repro.kernels.
    interpret_mode`: compiled on TPU, interpreted on CPU); pass an explicit
    bool to pin it.  Raises ValueError instead of silently reshaping on a
    non-divisible head count or accepting a non-int32 page table (a float
    table would truncate physical page ids)."""
    B, H, D = q.shape
    if k_pool.ndim != 4:
        raise ValueError(
            f"paged_attention: pools are (L, P, page, Hkv*D) stacked over "
            f"layers, got shape {k_pool.shape}")
    Hkv = k_pool.shape[3] // D
    if k_pool.shape[3] % D != 0:
        raise ValueError(
            f"paged_attention: pool width {k_pool.shape[3]} is not a "
            f"multiple of head_dim {D} — pools are (L, P, page, Hkv*D)")
    if H % Hkv != 0:
        raise ValueError(
            f"paged_attention: {H} query heads are not divisible by "
            f"{Hkv} KV heads — GQA grouping needs H % Hkv == 0")
    if tables.dtype != jnp.int32:
        raise ValueError(
            f"paged_attention: page table dtype {tables.dtype} must be "
            "int32 (physical page ids)")
    if interpret is None:
        interpret = interpret_mode()
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D).transpose(0, 2, 1, 3)
    o = paged_attention_pallas(qg, k_pool, v_pool, tables,
                               pos.astype(jnp.int32), layer,
                               interpret=bool(interpret))
    return o.transpose(0, 2, 1, 3).reshape(B, H, D)
