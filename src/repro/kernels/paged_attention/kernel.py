"""Pallas paged-attention decode kernel, TPU-targeted.

Batched single-token decode over a paged KV layout: K/V live in page
pools stacked over layers, (L, P, page, Hkv·D) — the KV heads flattened
into the lane axis, so a page is one lane-dense (page, Hkv·D) tile
whatever the head count, and a head_dim of 64 wastes no lanes — and each
batch row addresses its sequence through a page table (B, T) of physical
page ids (`repro.serving.paging` builds both).  The call reads one layer
of the stack, in place: the layer loop hands it the whole stacked pool
and its layer index, so no layer's pool is sliced out per step.

Grid: (batch,).  Page tables, positions and the layer index arrive
through scalar prefetch (SMEM), and the pools stay in HBM
(`memory_space=ANY`): each program walks its row's table in blocks of
several pages (`BLOCK_TOKENS` tokens: 8 pages of 32, 16 of 16), DMAing
a block's pages of K and of V of its layer into VMEM, double-buffered
so block t + 1 streams in while block t computes (`page_copies`,
`page_walk`).  The query arrives grouped by KV head, (Hkv, G, D), and
each KV head's G queries are served from that head's own lanes
[h·D, (h+1)·D) of the block: scores (G, D)·(block, D)ᵀ → (G, block),
then the probabilities times the head's V lanes, (G, block)·(block, D),
on the MXU with float32 accumulation.  Both products take operands of
the pool's dtype, as in `mla_decode` (bf16 pools: bf16 operands; float32
pools stay float32); the probabilities go in as two such terms, their
rounding and its remainder, stacked into one (2G, block) operand, so
that a single product keeps them to float32 accuracy (one bf16 rounding
moves greedy tokens off the XLA path's).  An online softmax
(FlashAttention-2 over a scattered KV layout) keeps a (G, 1) max and sum
and a (G, D) float32 accumulator per KV head.  The loop's upper bound is
the block holding pos; positions past pos in it (the row's stale or
scratch pages, and the pages past the table's end, which clamp to its
last) are masked.

Contract: a call on layer l matches
`repro.kernels.paged_attention.ref.paged_attention_ref` on the pools'
layer l (its jnp gather math is the oracle in tests, and mirrors the
paged decode path in `repro.nn.attention`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
# tokens a loop step of `paged_attention` reads: the page size sets the
# pages per block (8 of 32 tokens, 16 of 16)
BLOCK_TOKENS = 256


def page_copies(tab_ref, row, n_blocks: int, layer, pools, bufs, sem,
                pages_per_block: int = 1):
    """copies(t, slot): the DMAs of block t of batch row `row` — its
    pages [t * n, t * n + n), n = `pages_per_block` — one per page and
    pool: layer `layer` of a stacked (L, P, page, W) pool in HBM into
    rows [j * page, (j + 1) * page) of slot `slot` of that pool's
    (2, n * page, W) VMEM buffer.  `tab_ref` is the flattened
    (B * n_blocks,) page table in SMEM (n_blocks: its pages per row); a
    block's pages past the table's last clamp to it, and pages past a
    row's live ones are whatever the table names there (the scratch page):
    the caller masks them.  `sem` is a (len(pools), 2) DMA semaphore
    array.  The caller starts block 0's before `page_walk`."""
    if pages_per_block == 1:
        def copies(t, slot):
            pid = tab_ref[row * n_blocks + t]
            return [pltpu.make_async_copy(pool.at[layer, pid],
                                          buf.at[slot], sem.at[i, slot])
                    for i, (pool, buf) in enumerate(zip(pools, bufs))]
        return copies

    def copies(t, slot):
        out = []
        for j in range(pages_per_block):
            idx = jnp.minimum(t * pages_per_block + j, n_blocks - 1)
            pid = tab_ref[row * n_blocks + idx]
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                page = pool.shape[2]
                out.append(pltpu.make_async_copy(
                    pool.at[layer, pid],
                    buf.at[slot, pl.ds(j * page, page)], sem.at[i, slot]))
        return out
    return copies


def page_walk(copies, hi, bufs, step, init):
    """Fold `step(t, pages, carry)` over blocks [0, hi) (`pages`: each
    pool's block t, in pool order), block t + 1 streaming into the other
    buffer slot while block t computes.  Returns the final carry."""
    def body(t, carry):
        slot = t % 2

        @pl.when(t + 1 < hi)
        def _():
            for c in copies(t + 1, 1 - slot):
                c.start()

        for c in copies(t, slot):
            c.wait()
        return step(t, [buf[slot] for buf in bufs], carry)

    return jax.lax.fori_loop(0, hi, body, init)


def _kernel(tab_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
            v_buf, sem, *, page: int, n_blocks: int, per_block: int,
            scale: float):
    # tab_ref (B*T,) / pos_ref (B,) / layer_ref (1,) in SMEM; q_ref and
    # o_ref (1, Hkv, G, D); k/v_hbm (L, P, page, Hkv*D) in HBM; k/v_buf
    # (2, block, Hkv*D) in VMEM
    b = pl.program_id(0)
    Hkv, G, D = q_ref.shape[1:]
    pos = pos_ref[b]
    block = per_block * page
    hi = pos // block + 1               # blocks holding positions <= pos
    copies = page_copies(tab_ref, b, n_blocks, layer_ref[0], (k_hbm, v_hbm),
                         (k_buf, v_buf), sem, per_block)
    for c in copies(0, 0):
        c.start()

    def step(t, pages, carry):
        k, v = pages                                      # (block, Hkv*D)
        kpos = t * block + jax.lax.broadcasted_iota(jnp.int32, (G, block), 1)
        out = []
        for h, (m, l, acc) in enumerate(carry):
            kh = k[:, h * D:(h + 1) * D]                  # (block, D)
            vh = v[:, h * D:(h + 1) * D]
            s = jax.lax.dot_general(
                q_ref[0, h].astype(k.dtype), kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, block)
            s = jnp.where(kpos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)                        # (G, 1)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            # p as two terms of the pool's dtype, its rounding and the
            # remainder, stacked into one (2G, block) operand: one MXU
            # product with the head's V keeps p to float32 accuracy
            p_hi = p.astype(v.dtype).astype(jnp.float32)
            pv = jnp.dot(jnp.concatenate([p_hi, p - p_hi]).astype(v.dtype),
                         vh, preferred_element_type=jnp.float32)
            pv = pv[:G] + pv[G:]
            out.append((m_new, l_new, acc * corr + pv))
        return tuple(out)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, D), jnp.float32)) for _ in range(Hkv))
    final = page_walk(copies, hi, (k_buf, v_buf), step, init)
    for h, (_, l, acc) in enumerate(final):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,        # (B, Hkv, G, D) query heads, KV-major
    k_pool: jax.Array,   # (L, P, page, Hkv*D)
    v_pool: jax.Array,
    tables: jax.Array,   # (B, T) int32 physical page ids
    pos: jax.Array,      # (B,) int32 current position per row
    layer: jax.Array,    # () int32 layer of the stacked pools to read
    *,
    interpret: bool,
) -> jax.Array:
    """Returns (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    page, HD = k_pool.shape[2:]
    T = tables.shape[1]
    n = max(1, min(BLOCK_TOKENS // page, T))
    kern = functools.partial(_kernel, page=page, n_blocks=T, per_block=n,
                             scale=1.0 / math.sqrt(D))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    qo = pl.BlockSpec((1, Hkv, G, D), lambda b, tab, pos, layer: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[qo, hbm, hbm],
        out_specs=qo,
        scratch_shapes=[
            pltpu.VMEM((2, n * page, HD), k_pool.dtype),
            pltpu.VMEM((2, n * page, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.reshape(-1), pos, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_pool, v_pool)
