"""Pallas latent (MLA) paged-attention decode kernel, TPU-targeted.

Batched single-token decode of DeepSeek-V2's multi-head latent attention
in absorbed form, over a paged latent cache.  Each token's cache entry is
its compressed KV latent c_kv (kv_lora_rank wide) and its rotary key
k_rope, kept in two page pools stacked over layers, (L, P, page, R) and
(L, P, page, Wr), addressed through a (B, T) page table
(`repro.serving.paging` builds both).  A TPU DMA moves whole 128-lane
tiles, so there the rope-key pool is Wr = 128 lanes wide, zero past the
rope dim (`nn.attention.rope_pool_width`), and the rope query is
zero-padded to match.

The caller absorbs W_uk into the query (q_lat = q_nope W_ukᵀ, (H, R)) and
keeps W_uv and W_o for after the call, so the kernel works in the latent
space alone:

    s[h, j]   = (q_lat[h] · c_kv[j] + q_rope[h] · k_rope[j]) · scale
    ctx[h]    = Σ_j softmax_j(s[h]) c_kv[j]                    (H, R)

Grid: (batch,).  Page tables, positions and the layer index arrive by
scalar prefetch, the pools stay in HBM, and each program walks only its
row's blocks of `pages_per_block` pages up to the one holding pos
(`kernel.page_copies` and `page_walk`, shared with the GQA kernel:
whole-page DMAs of the layer's c_kv and k_rope pages into a block
buffer, double-buffered).  All H heads of the row are one (H, block)
score tile per block — a block of several pages keeps the MXU's
operands wide and the loop's steps few — and an online softmax folds
each block into an (H, R) float32 accumulator; positions past pos
(the last block's tail, read from the scratch page) are masked.  Scores
and the probability-weighted sum run on the MXU in bf16 with float32
accumulation.

Contract: matches `repro.kernels.paged_attention.ref.mla_decode_ref` on
the pools' layer l.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.kernel import (NEG_INF, page_copies,
                                                  page_walk)

KERNEL_NAME = "mla_decode"
# pages a loop step reads: 8 pages of 64 tokens make a (16, 512) score tile
# and leave a row about 4 steps a layer at the reason mix's ~1,930-token
# mean context, against a tail of at most 7 masked pages read
PAGES_PER_BLOCK = 8


def _kernel(tab_ref, pos_ref, layer_ref, ql_ref, qr_ref, c_hbm, r_hbm,
            o_ref, c_buf, r_buf, sem, *, page: int, n_blocks: int,
            per_block: int, scale: float):
    # tab_ref (B*T,) / pos_ref (B,) / layer_ref (1,) in SMEM; ql_ref
    # (1, H, R) and qr_ref (1, H, Wr) queries; o_ref (1, H, R); c/r_hbm
    # (L, P, page, R|Wr) in HBM; c/r_buf (2, block, R|Wr) in VMEM
    b = pl.program_id(0)
    H, R = ql_ref.shape[1:]
    pos = pos_ref[b]
    block = per_block * page
    hi = pos // block + 1               # blocks holding positions <= pos
    copies = page_copies(tab_ref, b, n_blocks, layer_ref[0], (c_hbm, r_hbm),
                         (c_buf, r_buf), sem, per_block)
    for c in copies(0, 0):
        c.start()
    ql = ql_ref[0]
    qr = qr_ref[0]

    def step(t, pages, carry):
        c, kr = pages                   # (block, R), (block, Wr)
        m, l, acc = carry
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, dims,
                                   preferred_element_type=jnp.float32))
        s = s * scale                                         # (H, block)
        kpos = t * block + jax.lax.broadcasted_iota(jnp.int32, (H, block), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)                             # (H, 1)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.dot(p.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)      # (H, R)
        return m_new, l_new, acc * corr + pv

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, R), jnp.float32))
    _, l, acc = page_walk(copies, hi, (c_buf, r_buf), step, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def mla_decode_pallas(
    q_lat: jax.Array,    # (B, H, R) absorbed queries, pool dtype
    q_rope: jax.Array,   # (B, H, Wr) rotated rope queries, pool dtype
    c_pool: jax.Array,   # (L, P, page, R) latent pages
    r_pool: jax.Array,   # (L, P, page, Wr) rope-key pages
    tables: jax.Array,   # (B, T) int32 physical page ids
    pos: jax.Array,      # (B,) int32 current position per row
    layer: jax.Array,    # () int32 layer of the stacked pools to read
    *,
    scale: float,
    interpret: bool,
    pages_per_block: int = PAGES_PER_BLOCK,
) -> jax.Array:
    """Returns the (B, H, R) float32 latent context."""
    B, H, R = q_lat.shape
    Wr = q_rope.shape[2]
    page = c_pool.shape[2]
    T = tables.shape[1]
    n = max(1, min(pages_per_block, T))
    kern = functools.partial(_kernel, page=page, n_blocks=T, per_block=n,
                             scale=scale)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, R),
                               lambda b, tab, pos, layer: (b, 0, 0)),
                  pl.BlockSpec((1, H, Wr),
                               lambda b, tab, pos, layer: (b, 0, 0)),
                  hbm, hbm],
        out_specs=pl.BlockSpec((1, H, R),
                               lambda b, tab, pos, layer: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n * page, R), c_pool.dtype),
            pltpu.VMEM((2, n * page, Wr), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tables.reshape(-1), pos, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q_lat, q_rope, c_pool, r_pool)
