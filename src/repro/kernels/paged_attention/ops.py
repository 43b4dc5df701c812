"""Wrapper: groups query heads per KV head (GQA stays native — no pool
expansion), with the execution mode plumbed in explicitly.

No `@jax.jit` here: callers (the serving decode step, the kernel tests)
jit the surrounding computation, and `interpret` must stay a trace-time
python constant they control."""
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_pallas


def paged_attention(q, k_pool, v_pool, tables, pos, *, interpret=None):
    """q: (B, H, D) one query token per row, heads flat in KV-major order
    (head h serves KV head h // (H/Hkv)); k/v_pool: (P, page, Hkv*D) page
    pools, KV heads flattened into the last axis; tables: (B, T) int32
    physical page ids; pos: (B,) int32 per-row positions.  Returns
    (B, H, D).

    `interpret=None` resolves from the platform (`repro.kernels.
    interpret_mode`: compiled on TPU, interpreted on CPU); pass an explicit
    bool to pin it.  Raises ValueError instead of silently reshaping on a
    non-divisible head count or accepting a non-int32 page table (a float
    table would truncate physical page ids)."""
    B, H, D = q.shape
    Hkv = k_pool.shape[2] // D
    if k_pool.shape[2] % D != 0:
        raise ValueError(
            f"paged_attention: pool width {k_pool.shape[2]} is not a "
            f"multiple of head_dim {D} — pools are (P, page, Hkv*D)")
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
                               pos.astype(jnp.int32),
                               interpret=bool(interpret))
    return o.transpose(0, 2, 1, 3).reshape(B, H, D)
