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
(`memory_space=ANY`): each program walks its row's table and DMAs one
whole page of K and of V of its layer into VMEM as the loop reaches it,
double-buffered so page t+1 streams in while page t computes.  Every KV
head of the row is served from the same page tile: the query arrives as a
block-diagonal (Hkv, Hkv·D) matrix per query group (row h holds head h's
query in lanes [h·D, (h+1)·D), zeros elsewhere), so scores are one MXU
matmul against the page, (Hkv, page), and the probabilities times the
page give (Hkv, Hkv·D), whose block diagonal is each head's output.  The
online-softmax recurrence (FlashAttention-2 over a scattered KV layout)
folds each page into a lane-flat (1, Hkv·D) accumulator.  The fori_loop
upper bound is pos // page + 1, so pages past the row's position are
never read.

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
            v_buf, sem, *, page: int, head_dim: int, n_blocks: int,
            scale: float):
    # tab_ref (B*T,) / pos_ref (B,) / layer_ref (1,) in SMEM; q_ref
    # (1, G, Hkv, Hkv*D) block-diagonal queries; o_ref (1, G, 1, Hkv*D);
    # k/v_hbm (L, P, page, Hkv*D) in HBM; k/v_buf (2, page, Hkv*D) in VMEM
    b = pl.program_id(0)
    G, Hkv, HD = q_ref.shape[1:]
    pos = pos_ref[b]
    layer = layer_ref[0]
    hi = pos // page + 1                # blocks holding positions <= pos
    copies = page_copies(tab_ref, b, n_blocks, layer, (k_hbm, v_hbm),
                         (k_buf, v_buf), sem)
    for c in copies(0, 0):
        c.start()

    # own[h, i]: lane i belongs to head h — picks each head's block of the
    # (Hkv, Hkv*D) products and spreads per-head scalars over their lanes
    own = (jax.lax.broadcasted_iota(jnp.int32, (Hkv, HD), 1) // head_dim
           == jax.lax.broadcasted_iota(jnp.int32, (Hkv, HD), 0))

    def spread(x):                      # (Hkv, 1) -> (1, Hkv*D)
        return jnp.sum(jnp.where(own, x, 0.0), axis=0, keepdims=True)

    def step(t, pages, carry):
        k, v = pages                                      # (page, Hkv*D)
        kpos = t * page + jax.lax.broadcasted_iota(jnp.int32, (Hkv, page), 1)
        out = []
        for g, (m, l, acc) in enumerate(carry):
            s = jax.lax.dot_general(
                q_ref[0, g].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (Hkv, page)
            s = jnp.where(kpos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)                        # (Hkv, 1)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p, v.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)  # (Hkv, Hkv*D)
            acc_new = (acc * spread(corr)
                       + jnp.sum(jnp.where(own, pv, 0.0), axis=0,
                                 keepdims=True))
            out.append((m_new, l_new, acc_new))
        return tuple(out)

    init = tuple((jnp.full((Hkv, 1), NEG_INF, jnp.float32),
                  jnp.zeros((Hkv, 1), jnp.float32),
                  jnp.zeros((1, HD), jnp.float32)) for _ in range(G))
    final = page_walk(copies, hi, (k_buf, v_buf), step, init)
    for g, (_, l, acc) in enumerate(final):
        o_ref[0, g] = (acc / spread(jnp.maximum(l, 1e-30))).astype(
            o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,        # (B, G, Hkv, D) query heads, group-major
    k_pool: jax.Array,   # (L, P, page, Hkv*D)
    v_pool: jax.Array,
    tables: jax.Array,   # (B, T) int32 physical page ids
    pos: jax.Array,      # (B,) int32 current position per row
    layer: jax.Array,    # () int32 layer of the stacked pools to read
    *,
    interpret: bool,
) -> jax.Array:
    """Returns (B, G, Hkv, D)."""
    B, G, Hkv, D = q.shape
    page, HD = k_pool.shape[2:]
    T = tables.shape[1]
    # block-diagonal queries: qd[b, g, h, h'*D + d] = q[b, g, h, d] iff h == h'
    qd = jnp.einsum("bghd,hk->bghkd", q, jnp.eye(Hkv, dtype=q.dtype))
    qd = qd.reshape(B, G, Hkv, HD)
    kern = functools.partial(_kernel, page=page, head_dim=D, n_blocks=T,
                             scale=1.0 / math.sqrt(D))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, G, Hkv, HD),
                               lambda b, tab, pos, layer: (b, 0, 0, 0)),
                  hbm, hbm],
        out_specs=pl.BlockSpec((1, G, 1, HD),
                               lambda b, tab, pos, layer: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, HD), k_pool.dtype),
            pltpu.VMEM((2, page, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, 1, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.reshape(-1), pos, jnp.reshape(layer, (1,)).astype(jnp.int32),
      qd, k_pool, v_pool)
    return o.reshape(B, G, Hkv, D)
