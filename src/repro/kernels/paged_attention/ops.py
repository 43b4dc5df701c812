"""Wrappers: `paged_attention` hands the kernel the query heads grouped
by KV head (GQA stays native — no pool expansion); `mla_decode` checks
the latent kernel's shapes; both take the execution mode explicitly.

No `@jax.jit` here: callers (the serving decode step, the kernel tests)
jit the surrounding computation, and `interpret` must stay a trace-time
python constant they control."""
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.mla_kernel import mla_decode_pallas


def paged_attention(q, k_pool, v_pool, tables, pos, layer, *,
                    interpret=None):
    """q: (B, H, D) one query token per row, heads flat in KV-major order
    (head h serves KV head h // (H/Hkv)); k/v_pool: (L, P, page, Hkv*D)
    page pools stacked over layers, KV heads flattened into the last axis;
    tables: (B, T) int32 physical page ids; pos: (B,) int32 per-row
    positions; layer: the () int32 index of the layer to read, in place.
    A single layer's pool is passed as a stack of one, at layer 0.
    Returns (B, H, D).

    The kernel serves each KV head's H/Hkv queries (the free reshape
    (B, Hkv, H/Hkv, D)) from that head's D lanes of the pages, over blocks
    of several pages (`kernel.BLOCK_TOKENS` tokens, from the page size);
    both of its products take operands of the pool's dtype with float32
    accumulation, the probabilities as a high and a low term stacked
    into one operand.

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
    o = paged_attention_pallas(q.reshape(B, Hkv, H // Hkv, D), k_pool,
                               v_pool, tables, pos.astype(jnp.int32), layer,
                               interpret=bool(interpret))
    return o.reshape(B, H, D)


def mla_decode(q_lat, q_rope, c_pool, r_pool, tables, pos, layer, *,
               scale: float, interpret=None):
    """Absorbed latent (MLA) decode over paged latent pools: q_lat (B, H,
    R) = q_nope W_ukᵀ and q_rope (B, H, Wr), cast to the pools' dtype;
    c_pool (L, P, page, R) and r_pool (L, P, page, Wr) stacked over
    layers, read in place at `layer` (on a TPU Wr is a whole number of
    128-lane tiles: the rope keys and queries zero-padded); tables (B, T) int32; pos (B,) int32;
    `scale` the softmax scale.  Returns the (B, H, R) float32 latent
    context (the caller applies W_uv and W_o).  `interpret` as for
    `paged_attention`."""
    if c_pool.ndim != 4 or r_pool.shape[:3] != c_pool.shape[:3]:
        raise ValueError(
            f"mla_decode: pools are (L, P, page, R) and (L, P, page, Wr) "
            f"with matching leading dims, got {c_pool.shape} and "
            f"{r_pool.shape}")
    if q_lat.shape[-1] != c_pool.shape[3] or \
            q_rope.shape[-1] != r_pool.shape[3]:
        raise ValueError(
            f"mla_decode: query widths {q_lat.shape[-1]}/{q_rope.shape[-1]}"
            f" do not match pool widths {c_pool.shape[3]}/{r_pool.shape[3]}")
    if tables.dtype != jnp.int32:
        raise ValueError(
            f"mla_decode: page table dtype {tables.dtype} must be int32 "
            "(physical page ids)")
    if interpret is None:
        interpret = interpret_mode()
    return mla_decode_pallas(q_lat.astype(c_pool.dtype),
                             q_rope.astype(r_pool.dtype), c_pool, r_pool,
                             tables, pos.astype(jnp.int32), layer,
                             scale=float(scale), interpret=bool(interpret))
