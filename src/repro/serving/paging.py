"""Paged KV-cache subsystem: block page tables, prefix sharing, and COW.

The slot arena (`kv_arena.py`) binds every request to a whole-sequence slot,
so `max_seq` is a per-tenant constant and a 3-token request strands a full
`max_seq` KV region.  This module brings the crossbar occupancy-map
discipline of `sim/aras.py` down to *sub-sequence* granularity:

  * `PageAllocator` — a host-side occupancy map over fixed-size KV pages:
    free-list allocation, per-request page tables, refcounted prefix sharing
    (identical token prefixes map to the same physical pages), and
    copy-on-write when a shared page is about to diverge.  A freed page
    keeps its stale device contents until the next occupant overwrites
    them — correctness comes from position masks, exactly like a released
    crossbar row.
  * `PagedKVArena` — the device side: one page-pool cache pytree per tenant
    (the `init_cache` layout with the batch axis reinterpreted as the page
    axis) plus per-row decode state.  Requests address their KV through an
    `(n_rows, n_pages)` page-table array consumed by the paged decode path
    in `nn/attention.py`; a request may span any number of pages, so the
    per-request ceiling is the whole pool, not a per-slot constant.

Device page 0 is reserved as a scratch page: inactive decode rows keep
all-zero page tables, so their (discarded) decode writes land in the
scratch page instead of corrupting a reallocated neighbor.

Prefix-sharing safety argument: a page registered under token prefix `t`
holds valid K/V for every position `< len(t)`; later appends by the owner
only add entries at *higher* positions, which any sharer masks out
(`kpos <= pos`).  Sharing therefore stays sound even when the registered
content grows — but a *write* into a page with refcount > 1 must COW first,
because two requests appending different tokens at the same page offset
would otherwise corrupt each other.

The prefix index is a `RadixPrefixCache` (serving/prefix_cache.py): a
radix tree over token-block edges.  With `retain=True` the tree also
*keeps* pages after their last live holder exits (finished requests donate
their prompt+generated pages instead of freeing them), holding one
refcount per retained page and LRU-evicting on demand when an allocation
would otherwise fail — write-avoidance extended from the weight plane
(§V-C delta installs) to the KV plane.
"""
from __future__ import annotations

import functools
import heapq
from collections import deque
from typing import Any, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.attention import pad_lanes, rope_pool_width
from repro.nn.config import ModelConfig
from repro.nn.transformer import layer_kind, stack_plan
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.tracing import NULL_TRACER


class PageAllocator:
    """Host-side occupancy map over `n_pages` KV pages of `page_size` tokens.

    Physical page ids run 1..n_pages; id 0 is the arena's reserved scratch
    page and is never handed out."""

    # structured-event sink for eviction/COW/donation decisions; the
    # engine swaps in its shared Tracer, standalone use keeps the no-op
    tracer = NULL_TRACER
    # stuck-at fault model (serving/faults.py) + the wear-plane name its
    # checks key on, injected by the engine; None = fault-free allocation
    faults = None
    fault_plane = "kv"
    # wear-plane over this pool's page ids, shared with the owning arena;
    # enable_wear_aware() switches the free structure to coldest-first
    wear = None

    def __init__(self, n_pages: int, page_size: int, *,
                 retain: bool = False, max_cached: Optional[int] = None):
        if n_pages < 1 or page_size < 1:
            raise ValueError("need n_pages >= 1 and page_size >= 1")
        self.n_pages = n_pages
        self.page_size = page_size
        self.retain = retain
        # FIFO free deque by default; enable_wear_aware() rebuilds it as a
        # (writes, page) min-heap so allocation hands out the coldest page
        # first — valid because pages only accrue writes while allocated,
        # so a free page's wear never changes under it
        self._free = deque(range(1, n_pages + 1))
        self.wear_aware = False
        # pages permanently pulled from service after a stuck-at fault —
        # never re-issued (neither free nor referenced)
        self.retired: set = set()
        self.pages_retired = 0
        self.refcount = np.zeros(n_pages + 1, np.int32)
        self.tables: Dict[int, List[int]] = {}      # rid -> physical pages
        # prefix index + retention layer: radix tree over token-block
        # edges; the tree's incremental evictable count watches our
        # refcounts, so every crossing of the ==1 boundary is reported
        # back through note_refcount (see _pin / free_page)
        self.tree = RadixPrefixCache(
            page_size, max_cached=max_cached,
            refcount_of=lambda page: int(self.refcount[page]))
        # lifetime stats
        self.pages_allocated = 0
        self.shared_hits = 0
        self.cow_copies = 0

    # ------------------------------------------------------------- sizing
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_pages - len(self._free)

    def occupancy(self) -> float:
        return self.n_used / self.n_pages

    def blocks_for(self, n_tokens: int) -> int:
        return max(-(-n_tokens // self.page_size), 1)

    # ---------------------------------------------------------- low level
    def enable_wear_aware(self, plane) -> None:
        """Switch free-page ordering from FIFO to coldest-first, steered by
        `plane` (the pool's WearPlane): the free structure becomes a
        (writes, page) min-heap, so every allocation programs the least-
        worn free page.  Ties break toward the lower page id, keeping the
        order deterministic."""
        self.wear = plane
        self.wear_aware = True
        heap = [(int(plane.writes[p - plane.first]), p) for p in self._free]
        heapq.heapify(heap)
        self._free = heap

    def _free_push(self, page: int) -> None:
        if self.wear_aware:
            heapq.heappush(
                self._free,
                (int(self.wear.writes[page - self.wear.first]), page))
        else:
            self._free.append(page)

    def _free_pop(self) -> int:
        if self.wear_aware:
            return heapq.heappop(self._free)[1]
        return self._free.popleft()

    def _take_page(self) -> Optional[int]:
        """`_alloc_page` behind program-and-verify: pop free pages until one
        takes the program cleanly; a page that faults is retired for good
        (never re-issued), the free list is topped back up via LRU eviction
        when retirement drains it, and None means no healthy page is left —
        the caller unwinds with no side effects and degrades like any other
        pool exhaustion (preempt, resume when pages free up)."""
        while True:
            if not self._free and not self.ensure_free(1):
                return None
            page = self._free_pop()
            if (self.faults is not None
                    and self.faults.check(self.fault_plane, page)):
                self.retired.add(page)
                self.pages_retired += 1
                if self.wear is not None:
                    self.wear.retire(page)
                self.tracer.instant("page_retired", page=page,
                                    plane=self.fault_plane)
                continue
            self.refcount[page] = 1
            self.pages_allocated += 1
            return page

    def free_page(self, page: int) -> None:
        """Drop one reference; the page returns to the free list (contents
        left stale on device) only when the last holder lets go.  A dying
        live page takes its tree node with it, cascading through any
        retained subtree below (whose refs come back through this very
        method — by_page is cleared first, so re-entry is a no-op)."""
        if self.refcount[page] <= 0:
            raise ValueError(f"double free of page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self.tree.drop_page(page, self.free_page)
            self._free_push(page)
        elif self.refcount[page] == 1:
            # last external holder left a retained page: it just became
            # solely tree-held, i.e. evictable — tell the tree's count
            self.tree.note_refcount(page)

    def _pin(self, page: int) -> None:
        """Take one shared reference on a resident prefix page.  The 1→2
        crossing makes a retained page non-evictable; the tree's
        incremental count hears about it here."""
        self.refcount[page] += 1
        self.shared_hits += 1
        if self.refcount[page] == 2:
            self.tree.note_refcount(page)

    def _sole(self, page: int) -> bool:
        """Nobody but the tree holds this page — the eviction predicate."""
        return self.refcount[page] == 1

    # ------------------------------------------------------ prefix sharing
    def match_prefix(self, tokens: Tuple[int, ...],
                     touch: bool = True) -> List[int]:
        """Longest chain of resident pages whose token prefixes match
        `tokens` block by block — a radix-tree walk, one dict probe per
        block (O(blocks) incremental hashing, not the old O(blocks·len)
        full-prefix tuples).  Full blocks match on block-boundary edges;
        the final partial block matches only an exact-tuple edge (a page
        holding *more* than the lookup key would require mid-page writes
        during prefill, where sharing buys nothing over writing a fresh
        page).  `touch=False` keeps pure capacity probes out of the LRU
        order."""
        return self.tree.match(tuple(tokens), touch=touch)

    def register(self, rid: int, tokens: Tuple[int, ...]) -> None:
        """Publish a freshly installed table's pages under their token
        prefixes so later requests can share them.  First writer wins; a
        page is only ever indexed under one key."""
        self.tree.register(tuple(tokens), self.tables[rid])

    # ----------------------------------------------------------- eviction
    def evictable_pages(self, exclude: FrozenSet[int] = frozenset()) -> int:
        """Pages on-demand eviction could free right now (exact, so
        admission promises only what `ensure_free` can deliver).  O(1)
        plus O(|exclude| chain) — the incrementally maintained count, not
        a tree walk (the scheduling hot path calls this per overflow)."""
        return self.tree.evictable_count(frozenset(exclude))

    def ensure_free(self, need: int) -> bool:
        """LRU-evict retained pages until `need` pages are free.  False
        when the cache cannot cover the shortfall (callers pre-check with
        `evictable_pages` to fail without side effects)."""
        evicted = 0
        while len(self._free) < need:
            if not self.tree.evict_lru(self._sole, self.free_page):
                return False
            evicted += 1
        if evicted:
            self.tracer.instant("kv_evict", n_pages=evicted,
                                cached_left=self.tree.n_cached)
        return True

    # ------------------------------------------------------ request level
    def alloc_table(self, rid: int, tokens: Tuple[int, ...]
                    ) -> Optional[Tuple[List[int], int]]:
        """Build rid's page table over `tokens`: refcount shared prefix
        pages, allocate fresh pages for the rest.  Returns (table,
        n_shared), or None *with no side effects* when the pool cannot
        cover the non-shared tail."""
        if rid in self.tables:
            raise ValueError(f"rid {rid} already holds a table")
        n_blocks = self.blocks_for(len(tokens))
        shared = self.match_prefix(tokens)
        for page in shared:          # pin first: pinned pages never evict
            self._pin(page)
        need = n_blocks - len(shared)
        if need > self.n_free + self.evictable_pages():
            for page in shared:      # unpin — no side effects on failure
                self.free_page(page)
            self.shared_hits -= len(shared)
            return None
        self.ensure_free(need)
        fresh: List[int] = []
        for _ in range(need):
            page = self._take_page()
            if page is None:         # faults drained the pool mid-build
                for p in fresh:
                    self.free_page(p)
                for p in shared:
                    self.free_page(p)
                self.shared_hits -= len(shared)
                return None
            fresh.append(page)
        table = list(shared) + fresh
        self.tables[rid] = table
        return table, len(shared)

    def begin_table(self, rid: int, tokens: Tuple[int, ...]) -> int:
        """Chunked-prefill admission: open rid's table with just the shared
        prefix pages (refcounted now — sharing is checked against the whole
        prompt, which is known up front).  Fresh pages for the non-shared
        tail are reserved chunk by chunk via grow_table — admission still
        gates on the whole footprint being free (lax admission would churn
        reservations without progress), but decode neighbors allocate and
        free pages while the prefill runs, and a reservation that loses
        that race fails cleanly at grow_table instead of corrupting
        anything.  Returns the number of shared pages."""
        if rid in self.tables:
            raise ValueError(f"rid {rid} already holds a table")
        shared = self.match_prefix(tokens)
        for page in shared:
            self._pin(page)
        self.tables[rid] = list(shared)
        return len(shared)

    def grow_table(self, rid: int, n_blocks: int) -> bool:
        """Reserve fresh pages until rid's table covers `n_blocks` blocks
        (one prefill chunk's worth at a time).  False with *no side effects*
        when the pool cannot cover the growth — the caller preempts the
        prefill and resumes it at the last completed chunk once pages free
        up."""
        need = n_blocks - len(self.tables[rid])
        if need <= 0:
            return True
        if need > self.n_free + self.evictable_pages():
            return False
        self.ensure_free(need)
        added: List[int] = []
        for _ in range(need):
            page = self._take_page()
            if page is None:         # faults drained the pool mid-growth
                for p in added:
                    self.free_page(p)
                return False
            added.append(page)
        self.tables[rid].extend(added)
        return True

    def extend(self, rid: int) -> Optional[int]:
        """Append one fresh page to rid's table (decode crossed a page
        boundary), LRU-evicting a retained page if the free list is empty.
        None when the pool is exhausted — the caller preempts."""
        if not self.ensure_free(1):
            return None
        page = self._take_page()
        if page is None:
            return None
        self.tables[rid].append(page)
        return page

    def cow(self, rid: int, block: int) -> Optional[Tuple[int, int]]:
        """Make rid's `block` exclusively owned before a write.  Returns
        (src, dst) when a device page copy is required, (page, page) when
        the page was already exclusive, None when the pool is exhausted
        (after LRU-evicting any retained pages it could)."""
        old = self.tables[rid][block]
        if self.refcount[old] <= 1:
            return old, old
        if not self.ensure_free(1):
            return None
        new = self._take_page()
        if new is None:
            return None
        self.free_page(old)          # our ref only; other holders keep it
        self.tables[rid][block] = new
        self.cow_copies += 1
        self.tracer.instant("kv_cow", rid=rid, block=block, src=old, dst=new)
        return old, new

    def free_table(self, rid: int,
                   donate_tokens: Optional[Tuple[int, ...]] = None) -> None:
        """Release rid's table.  With retention on and `donate_tokens` (the
        token sequence the table holds valid K/V for — prompt + generated
        minus the just-emitted last token), the pages enter the radix tree
        retained instead of returning to the free list: the next request
        sharing the prefix finds them resident."""
        table = self.tables.pop(rid)
        if (self.retain and donate_tokens
                and len(table) == self.blocks_for(len(donate_tokens))):
            ev0 = self.tree.evictions
            gained = self.tree.donate(tuple(donate_tokens), table,
                                      self.free_page)
            # cap-enforcement evictions happen inside donate, not
            # ensure_free — surface them here so the trace accounts for
            # every LRU eviction the summary reports
            self.tracer.instant("kv_donate", rid=rid, n_pages=len(table),
                                retained=gained,
                                cap_evictions=self.tree.evictions - ev0)
        else:
            for page in table:
                self.free_page(page)


# ---------------------------------------------------------------- device
def init_page_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                   dtype=jnp.bfloat16, platform: Optional[str] = None):
    """Page-pool cache pytree: `nn.model.init_cache` with the batch axis
    reinterpreted as the page axis, except windowed layers keep full pages
    (the paged decode path masks the window instead of ring-indexing) and
    GQA K/V pools flatten the KV heads into the last axis, (P, page,
    Hkv*D): a page is then one lane-dense tile that the Pallas decode
    kernel can DMA whole, and a head_dim of 64 is not padded to the TPU's
    128 lanes (which doubles a (..., Hkv, D) pool's device footprint).
    MLA's rope-key pool is `nn.attention.rope_pool_width` lanes wide on
    `platform` (default: JAX's default backend), its lanes past the rope
    dim zero."""

    def attn_entry():
        if cfg.attn_type == "mla":
            return {
                "c_kv": jnp.zeros((n_pages, page_size, cfg.kv_lora_rank),
                                  dtype),
                "k_rope": jnp.zeros((n_pages, page_size,
                                     rope_pool_width(cfg, platform)),
                                    dtype),
            }
        kv_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype
        width = cfg.n_kv_heads * cfg.head_dim
        out = {
            "k": jnp.zeros((n_pages, page_size, width), kv_dt),
            "v": jnp.zeros((n_pages, page_size, width), kv_dt),
        }
        if cfg.kv_cache_dtype == "int8":
            out["k_scale"] = jnp.zeros(
                (n_pages, page_size, cfg.n_kv_heads), jnp.float32)
            out["v_scale"] = jnp.zeros_like(out["k_scale"])
        return out

    caches = []
    for start, length, scanned in stack_plan(cfg):
        one: Any = {"attn": attn_entry()}
        if scanned:
            one = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (length,) + a.shape), one)
        caches.append(one)
    return caches


def _flat_width(staging_leaf: jax.Array, first: int) -> int:
    """Width of a staging leaf's per-token entry, its axes from `first`
    on flattened: the pool lanes that hold data."""
    return int(np.prod(staging_leaf.shape[first:]))


def _to_pool_lanes(chunk: jax.Array, lead: int, width: int) -> jax.Array:
    """A staging chunk with `lead` leading axes and a per-token entry of
    any shape, flattened to one axis and zero-padded to the pool's `width`
    lanes (the rope key; GQA's (Hkv, D) flattens to exactly Hkv*D)."""
    return pad_lanes(chunk.reshape(chunk.shape[:lead] + (-1,)), width)


@functools.lru_cache(maxsize=None)
def _cached_page_write(cfg: ModelConfig, page_size: int):
    """Jitted page scatter shared across arenas of one config: copy logical
    block `block` of a batch-1 prefill cache into physical page `page` of
    the pool.  The pool is donated — install() immediately rebinds
    self.caches, so the write is in place."""
    plan = stack_plan(cfg)

    def write(pool, one, block, page):
        out = []
        for seg_pool, seg_one, (_, _, scanned) in zip(pool, one, plan):
            def upd(a, o, scanned=scanned):
                # the reshape flattens staging (..., Hkv, D) K/V into the
                # pool's (..., Hkv*D) layout (a no-op for other leaves)
                # and zero-pads a staging rope key to the pool's lanes
                if scanned:  # a (L, P, ps, ...), o (L, 1, Lbuf, ...)
                    chunk = jax.lax.dynamic_slice_in_dim(
                        o[:, 0], block * page_size, page_size, axis=1)
                    chunk = _to_pool_lanes(chunk, 2, a.shape[-1]).reshape(
                        a.shape[:1] + (1,) + a.shape[2:])
                    return jax.lax.dynamic_update_slice_in_dim(
                        a, chunk.astype(a.dtype), page, axis=1)
                chunk = jax.lax.dynamic_slice_in_dim(
                    o[0], block * page_size, page_size, axis=0)
                chunk = _to_pool_lanes(chunk, 1, a.shape[-1])
                return jax.lax.dynamic_update_slice_in_dim(
                    a, chunk.reshape((1,) + a.shape[1:]).astype(a.dtype),
                    page, axis=0)
            out.append(jax.tree.map(upd, seg_pool, seg_one))
        return out

    return jax.jit(write, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _cached_page_read(cfg: ModelConfig, page_size: int):
    """Jitted pool→staging gather, the inverse of `_cached_page_write`:
    copy physical page `page` of the pool into logical block `block` of a
    batch-1 staging cache.  The chunk-skip warm path uses it to seed the
    staging carry-in from cached prefix pages (bf16 pools round-trip
    bit-exact).  int8 pools are dequantization-aware: the pool holds
    {k, v, k_scale, v_scale} while staging attends raw bf16 {k, v}, so
    the page's codes are dequantized on the way out — the warm prefix
    carries the same quantization error decode attends after install.
    Staging is donated — the caller immediately rebinds it."""
    plan = stack_plan(cfg)
    int8 = cfg.kv_cache_dtype == "int8" and cfg.attn_type != "mla"

    def read(one, pool, block, page):
        out = []
        for seg_one, seg_pool, (_, _, scanned) in zip(one, pool, plan):
            def upd(o, a, scanned=scanned):
                # the reshape splits the pool's (..., Hkv*D) K/V back into
                # staging's (..., Hkv, D) (a no-op for other leaves); a
                # rope key drops the pool's padding lanes
                a = a[..., :_flat_width(o, 3 if scanned else 2)]
                if scanned:  # a (L, P, ps, ...), o (L, 1, Lbuf, ...)
                    chunk = jax.lax.dynamic_slice_in_dim(a, page, 1, axis=1)
                    chunk = chunk.reshape(chunk.shape[:3] + o.shape[3:])
                    return jax.lax.dynamic_update_slice_in_dim(
                        o, chunk.astype(o.dtype), block * page_size, axis=2)
                chunk = jax.lax.dynamic_slice_in_dim(a, page, 1, axis=0)
                chunk = chunk.reshape(chunk.shape[:2] + o.shape[2:])
                return jax.lax.dynamic_update_slice_in_dim(
                    o, chunk.astype(o.dtype), block * page_size, axis=1)

            if int8:
                # pool entry {k, v, k_scale, v_scale} → staging {k, v}:
                # dequantize the page's codes with its per-(token, head)
                # scales (codes.f32 * scale == nn.attention._kv_dequant)
                po, pa = seg_one["attn"], seg_pool["attn"]
                ent = {}
                axis = 1 if scanned else 0
                for f in ("k", "v"):
                    c = jax.lax.dynamic_slice_in_dim(pa[f], page, 1,
                                                     axis=axis)
                    s = jax.lax.dynamic_slice_in_dim(pa[f + "_scale"], page,
                                                     1, axis=axis)
                    c = c.reshape(s.shape + (-1,))
                    deq = (c.astype(jnp.float32)
                           * s[..., None]).astype(po[f].dtype)
                    ent[f] = jax.lax.dynamic_update_slice_in_dim(
                        po[f], deq, block * page_size,
                        axis=2 if scanned else 1)
                new_seg = dict(seg_one)
                new_seg["attn"] = ent
                out.append(new_seg)
            else:
                out.append(jax.tree.map(upd, seg_one, seg_pool))
        return out

    return jax.jit(read, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _cached_page_copy(cfg: ModelConfig):
    """Jitted COW page copy: pool page `src` -> pool page `dst`."""
    plan = stack_plan(cfg)

    def copy(pool, src, dst):
        out = []
        for seg, (_, _, scanned) in zip(pool, plan):
            ax = 1 if scanned else 0
            out.append(jax.tree.map(
                lambda a, ax=ax: jax.lax.dynamic_update_slice_in_dim(
                    a, jax.lax.dynamic_slice_in_dim(a, src, 1, axis=ax),
                    dst, axis=ax),
                seg))
        return out

    return jax.jit(copy, donate_argnums=(0,))


class PagedKVArena:
    """Device page pool + per-row decode state for one tenant.

    Rows are decode-batch positions (the jitted decode step always runs
    `n_rows` rows; inactive rows decode discarded garbage against the
    reserved scratch page).  Pages are the storage unit: a request holds
    ceil(len/page_size) of them, up to the whole pool."""

    layout = "paged"
    # structured-event sink (shared with self.allocator); the engine
    # swaps in its Tracer, standalone use keeps the no-op
    tracer = NULL_TRACER
    # wear-telemetry sink, injected like the tracer: the engine's
    # WearPlane over this pool's page ids (1..n_pages; the scratch page 0
    # never takes an accounted write).  Standalone use records nothing.
    wear = None

    def __init__(self, cfg: ModelConfig, n_rows: int, n_pages: int,
                 page_size: int, *, prefix_cache: bool = False,
                 prefix_cache_pages: int = 0):
        for start, _, _ in stack_plan(cfg):
            if layer_kind(cfg, start) != "attn":
                raise ValueError(
                    "paged KV needs a pure-attention stack; "
                    f"layer {start} of {cfg.name} is "
                    f"{layer_kind(cfg, start)!r} (use kv_layout='slot')")
        self.cfg = cfg
        self.n_rows = n_rows
        self.page_size = page_size
        self.prefix_cache = bool(prefix_cache)
        # chunk-skip reloads pool pages into the staging carry-in; int8
        # pools dequantize on the way out (_cached_page_read), so int8
        # tenants skip covered chunks too — the reloaded prefix carries
        # quantization error the cold path's raw bf16 staging did not,
        # which is the same error decode already attends post-install
        self.skip_ok = self.prefix_cache
        self.allocator = PageAllocator(
            n_pages, page_size, retain=self.prefix_cache,
            max_cached=(prefix_cache_pages or None) if prefix_cache
            else None)
        self.caches = init_page_pool(cfg, n_pages + 1, page_size)
        # Device bytes one logical page write programs: a page write
        # scatters this page's slice of EVERY pool leaf (all layers — see
        # _cached_page_write), so per-page bytes = per-leaf page-axis slice
        # summed across leaves.  Feeds the kv write-energy conversion.
        self.page_bytes = int(sum(
            (int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize)
            // (n_pages + 1)
            for leaf in jax.tree.leaves(self.caches)))
        # write-side accounting: physical page programs (prefill scatter,
        # staged install, COW copies) and the programs retained-page /
        # live-prefix sharing avoided (shared pages an install skipped)
        self.kv_page_writes = 0
        self.kv_bytes_written = 0
        self.kv_page_writes_avoided = 0
        self.owner: List[Optional[int]] = [None] * n_rows
        self.pos = np.zeros(n_rows, np.int32)
        self.last_token = np.zeros(n_rows, np.int32)
        # page-table rows consumed by the decode step; 0 = scratch page
        self.tables_np = np.zeros((n_rows, n_pages), np.int32)
        self._n_shared: Dict[int, int] = {}   # rid -> shared prefix pages
        self._free_rows: deque = deque(range(n_rows))
        self._write = _cached_page_write(cfg, page_size)
        self._read = _cached_page_read(cfg, page_size)
        self._copy = _cached_page_copy(cfg)
        self.evictions = 0

    # ------------------------------------------------------------ sizing
    @property
    def max_tokens(self) -> int:
        """Per-request ceiling: the whole pool (not a per-slot constant)."""
        return self.allocator.n_pages * self.page_size

    @property
    def n_free(self) -> int:
        """Free decode rows (the scheduler's per-tenant admission count)."""
        return len(self._free_rows)

    def blocks_for(self, n_tokens: int) -> int:
        return self.allocator.blocks_for(n_tokens)

    def can_admit(self, tokens: Tuple[int, ...]) -> bool:
        """Enough free pages for the non-shared tail — counting retained
        pages LRU eviction could free on demand — and a free row."""
        if not self._free_rows:
            return False
        a = self.allocator
        need = self.blocks_for(len(tokens))
        if need <= a.n_free:
            return True     # fits even with zero sharing: skip the prefix
            # match + evictability walk on the hot scheduling path
        shared = a.match_prefix(tuple(tokens), touch=False)
        need -= len(shared)
        # matched pages are about to be pinned, not consumed — exclude
        # them from the evictable count so the promise stays exact (an
        # optimistic count would requeue-livelock the engine)
        ok = need <= a.n_free + a.evictable_pages(frozenset(shared))
        if self.tracer.enabled:
            self.tracer.instant("kv_admit", ok=ok, need=need,
                                free=a.n_free, shared=len(shared))
        return ok

    # ------------------------------------------------------------- rows
    def active_slots(self) -> List[int]:
        return [r for r, o in enumerate(self.owner) if o is not None]

    def owner_of(self, row: int) -> Optional[int]:
        return self.owner[row]

    def alloc(self, rid: int, tokens: Tuple[int, ...]) -> Optional[int]:
        """Claim a row and a page table covering `tokens`; None (no side
        effects) when rows or pages are short."""
        if not self._free_rows:
            return None
        got = self.allocator.alloc_table(rid, tuple(tokens))
        if got is None:
            return None
        table, n_shared = got
        row = self._free_rows.popleft()
        self.owner[row] = rid
        self._n_shared[rid] = n_shared
        self.tables_np[row, :] = 0
        self.tables_np[row, :len(table)] = table
        return row

    def evict(self, row: int,
              donate: Optional[Tuple[int, ...]] = None) -> Optional[int]:
        """Release a row (finish or preemption): refcounts drop, pages whose
        last holder left return to the free list with stale contents.
        `donate` (a finished request's prompt + generated tokens) instead
        retains the pages in the prefix cache: the table holds valid K/V
        for the first `pos` of them (the just-emitted last token was never
        written), so that prefix is what enters the tree."""
        rid = self.owner[row]
        if rid is None:
            return None
        tokens = None
        if donate is not None and self.prefix_cache:
            tokens = tuple(donate)[:int(self.pos[row])]
        self.allocator.free_table(rid, donate_tokens=tokens)
        self._n_shared.pop(rid, None)
        self.owner[row] = None
        self.tables_np[row, :] = 0
        self._free_rows.append(row)
        self.evictions += 1
        return rid

    # ------------------------------------------------------------ caches
    def _note_page_write(self, page: int) -> None:
        """One physical page programmed (prefill scatter, staged install,
        or COW copy) — the KV-plane analogue of a weight install."""
        self.kv_page_writes += 1
        self.kv_bytes_written += self.page_bytes
        if self.wear is not None:
            self.wear.record(page)

    def install(self, row: int, one_caches: Any, first_token: int,
                tokens: Tuple[int, ...]) -> None:
        """Scatter a freshly prefilled batch-1 cache into this row's
        non-shared pages (shared prefix pages already hold identical K/V),
        publish the pages for future sharing, and arm decode state."""
        rid = self.owner[row]
        table = self.allocator.tables[rid]
        self.kv_page_writes_avoided += self._n_shared[rid]
        for i in range(self._n_shared[rid], len(table)):
            self.caches = self._write(self.caches, one_caches,
                                      jnp.int32(i), jnp.int32(table[i]))
            self._note_page_write(table[i])
        self.allocator.register(rid, tuple(tokens))
        self.pos[row] = len(tokens)
        self.last_token[row] = first_token

    # --------------------------------------------- chunked-prefill staging
    def stage(self, rid: int, tokens: Tuple[int, ...]) -> Optional[int]:
        """Chunked-prefill admission: claim a decode row and open a
        chunk-granular page reservation (shared prefix pages refcounted now,
        fresh pages reserved per chunk via grow()).  The row's device page
        table stays aimed at the scratch page until finish_stage — the
        batched decode step may write junk through this row meanwhile, and
        it must land in the scratch page, not in reserved real pages."""
        if not self._free_rows:
            return None
        n_shared = self.allocator.begin_table(rid, tuple(tokens))
        row = self._free_rows.popleft()
        self.owner[row] = rid
        self._n_shared[rid] = n_shared
        self.tables_np[row, :] = 0
        self.pos[row] = 0
        self.last_token[row] = 0
        return row

    def grow(self, rid: int, n_blocks: int) -> bool:
        """Reserve pages for the next prefill chunk; False = pool exhausted
        (the engine preempts the prefill, staging intact)."""
        return self.allocator.grow_table(rid, n_blocks)

    # --------------------------------------------------- prefix-cache skip
    def covered_tokens(self, rid: int, n_tokens: int) -> int:
        """Prompt tokens of rid covered by its shared (cached or live)
        prefix pages — the ceiling for chunk-skip.  An exact-tuple tail
        match shares a partial page, so the cover is capped at the prompt
        itself."""
        return min(self._n_shared.get(rid, 0) * self.page_size, n_tokens)

    def load_prefix(self, rid: int, staging: Any, n_tokens: int) -> Any:
        """Seed a staging cache with rid's shared prefix pages covering the
        first `n_tokens` positions: every page overlapping [0, n_tokens)
        is gathered whole.  A sub-page boundary is safe — the tail page is
        either a full shared page or an exact-tuple match of the prompt's
        own tail, so every gathered position < covered holds the donor's
        valid K/V, and positions >= n_tokens are recomputed (overwritten)
        by the next chunk anyway.  Returns the rebound (donated)
        staging."""
        table = self.allocator.tables[rid]
        n_blocks = -(-n_tokens // self.page_size)
        assert n_blocks <= self._n_shared.get(rid, 0), (
            "load_prefix beyond the shared prefix")
        for i in range(n_blocks):
            staging = self._read(staging, self.caches,
                                 jnp.int32(i), jnp.int32(table[i]))
        return staging

    def finish_stage(self, row: int, staging: Any, first_token: int,
                     tokens: Tuple[int, ...]) -> None:
        """Last chunk done: scatter the staged K/V into the reserved
        non-shared pages (shared prefix pages already hold identical
        values), publish the prefix, point the row's device table at the
        real pages, and arm decode state."""
        rid = self.owner[row]
        table = self.allocator.tables[rid]
        assert len(table) == self.blocks_for(len(tokens)), (
            "finish_stage before the table covered the prompt")
        self.kv_page_writes_avoided += self._n_shared[rid]
        for i in range(self._n_shared[rid], len(table)):
            self.caches = self._write(self.caches, staging,
                                      jnp.int32(i), jnp.int32(table[i]))
            self._note_page_write(table[i])
        self.allocator.register(rid, tuple(tokens))
        self.tables_np[row, :] = 0
        self.tables_np[row, :len(table)] = table
        self.pos[row] = len(tokens)
        self.last_token[row] = first_token

    def prepare_decode(self, row: int) -> bool:
        """Before a decode step writes this row's token at `pos`: extend the
        table if `pos` crossed into a new block, and COW the target page if
        it is shared.  False when the pool is exhausted (caller preempts)."""
        rid = self.owner[row]
        table = self.allocator.tables[rid]
        block = int(self.pos[row]) // self.page_size
        if block >= self.tables_np.shape[1]:
            return False               # request outgrew the whole pool
        if block == len(table):
            page = self.allocator.extend(rid)
            if page is None:
                return False
            self.tables_np[row, block] = page
            return True
        got = self.allocator.cow(rid, block)
        if got is None:
            return False
        src, dst = got
        if src != dst:
            self.caches = self._copy(self.caches, jnp.int32(src),
                                     jnp.int32(dst))
            self.tables_np[row, block] = dst
            self._note_page_write(dst)
        return True

    def decode_inputs(self):
        """(tokens (R,), pos (R,), tables (R, n_pages)) covering every row;
        inactive rows carry stale state aimed at the scratch page."""
        return (jnp.asarray(self.last_token), jnp.asarray(self.pos),
                jnp.asarray(self.tables_np))

    def advance(self, row: int, token: int) -> None:
        self.pos[row] += 1
        self.last_token[row] = token

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, float]:
        a = self.allocator
        return {
            "kv_pages_total": float(a.n_pages),
            "kv_pages_used": float(a.n_used),
            "kv_page_occupancy": a.occupancy(),
            "kv_pages_allocated": float(a.pages_allocated),
            "kv_shared_page_hits": float(a.shared_hits),
            "kv_cow_copies": float(a.cow_copies),
            "kv_prefix_cached_pages": float(a.tree.n_cached),
            "kv_prefix_evictions": float(a.tree.evictions),
            "kv_page_writes": float(self.kv_page_writes),
            "kv_bytes_written": float(self.kv_bytes_written),
            "kv_page_writes_avoided": float(self.kv_page_writes_avoided),
            "kv_pages_retired": float(a.pages_retired),
        }
