"""Paged KV-cache subsystem: allocator invariants (hypothesis), paged
decode equivalence with the contiguous cache path, and end-to-end paged
engine behavior — token-for-token against the sequential oracle, requests
beyond the old per-slot max_seq, prefix sharing with COW, and preemption
under pool exhaustion."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import cached_prefill_step, cached_serve_step
from repro.nn.model import decode_step, init_params, prefill
from repro.nn.transformer import stack_plan
from repro.serving import (EngineModel, PageAllocator, PagedKVArena,
                           SchedulerConfig, ServingEngine)
from repro.serving.paging import _cached_page_write, init_page_pool
from repro.serving.request import RequestStatus

CFG = get_config("gemma-7b", smoke=True)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
PAGE, N_PAGES = 4, 16
POOL_TOKENS = PAGE * N_PAGES


# ------------------------------------------------------------- allocator
def _check_invariants(a: PageAllocator):
    """The occupancy-map conservation laws: every page is either free or
    referenced, refcounts equal table membership, and the free list never
    holds a live page."""
    counts = np.zeros(a.n_pages + 1, np.int64)
    for table in a.tables.values():
        for page in table:
            counts[page] += 1
    free = set(a._free)
    assert len(free) == len(a._free), "free list holds duplicates"
    for page in range(1, a.n_pages + 1):
        assert a.refcount[page] == counts[page], (
            f"page {page}: refcount {a.refcount[page]} != "
            f"{counts[page]} table refs")
        assert (page in free) == (a.refcount[page] == 0)
    assert a.n_free + int((a.refcount[1:] > 0).sum()) == a.n_pages


def test_allocator_double_free_raises():
    a = PageAllocator(4, 2)
    table, _ = a.alloc_table(0, (1, 2, 3))
    a.free_table(0)
    with pytest.raises(ValueError):
        a.free_page(table[0])


def test_allocator_rejects_oversize_atomically():
    a = PageAllocator(4, 2)
    a.alloc_table(0, (1, 2, 3))          # 2 pages
    assert a.alloc_table(1, tuple(range(10))) is None   # needs 5 > 2 free
    assert a.n_free == 2                  # no leak from the failed alloc
    _check_invariants(a)


def test_allocator_prefix_sharing_refcounts():
    a = PageAllocator(8, 4)
    prompt = (5, 6, 7, 8, 9, 10)          # 1 full + 1 partial page
    t0, s0 = a.alloc_table(0, prompt)
    assert s0 == 0 and len(t0) == 2
    a.register(0, prompt)
    t1, s1 = a.alloc_table(1, prompt)     # identical → both pages shared
    assert s1 == 2 and t1 == t0
    assert a.refcount[t0[0]] == 2
    # shared pages are only freed when the last holder lets go
    a.free_table(0)
    assert a.refcount[t1[0]] == 1 and a.n_free == 6
    a.free_table(1)
    assert a.n_free == 8
    _check_invariants(a)


def test_allocator_cow_keeps_parent_pages():
    a = PageAllocator(8, 4)
    prompt = (1, 2, 3, 4, 5)
    t0, _ = a.alloc_table(0, prompt)
    a.register(0, prompt)
    t1, s1 = a.alloc_table(1, prompt)
    assert s1 == 2
    src, dst = a.cow(1, 1)                # diverge on the partial page
    assert src == t0[1] and dst != src
    assert a.tables[0] == t0, "COW must not touch the parent's table"
    assert a.refcount[src] == 1 and a.refcount[dst] == 1
    _check_invariants(a)
    # an exclusive page COWs to itself (no copy, no allocation): after the
    # divergence above, block 1 of table 0 is singly held again
    before = a.n_free
    assert a.cow(0, 1) == (t0[1], t0[1]) and a.n_free == before


def test_allocator_property_random_ops():
    """Hypothesis sweep over alloc/register/extend/cow/free sequences: the
    conservation laws hold after every operation, and oversized requests
    fail atomically."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ops = st.lists(
        st.one_of(
            st.tuples(st.just("new"), st.integers(0, 5), st.integers(1, 14)),
            st.tuples(st.just("finish"), st.integers(0, 7), st.just(0)),
            st.tuples(st.just("extend"), st.integers(0, 7), st.just(0)),
            st.tuples(st.just("cow"), st.integers(0, 7), st.integers(0, 3)),
        ),
        min_size=1, max_size=60)

    @settings(max_examples=120, deadline=None)
    @given(ops=ops)
    def run(ops):
        a = PageAllocator(6, 2)
        live = []
        next_rid = 0
        for op, x, y in ops:
            if op == "new":
                # small alphabet + shared prefix lengths → real sharing
                prompt = tuple([7] * min(x + 1, 4)) + tuple(
                    range(max(y - min(x + 1, 4), 0)))
                got = a.alloc_table(next_rid, prompt)
                if got is not None:
                    a.register(next_rid, prompt)
                    live.append(next_rid)
                next_rid += 1
            elif live:
                rid = live[x % len(live)]
                if op == "finish":
                    a.free_table(rid)
                    live.remove(rid)
                elif op == "extend":
                    a.extend(rid)
                elif op == "cow":
                    a.cow(rid, y % len(a.tables[rid]))
            _check_invariants(a)
        for rid in live:
            a.free_table(rid)
        assert a.n_free == a.n_pages
        _check_invariants(a)

    run()


# ------------------------------------------------- nn-level paged decode
@pytest.mark.parametrize("arch,n_layers", [
    ("gemma-7b", 0), ("deepseek-v2-lite-16b", 0), ("yi-34b", 4),
    ("deepseek-v2-lite-16b", 5),
], ids=["gemma-7b", "deepseek-v2-lite-16b", "yi-34b-scan4",
        "deepseek-v2-lite-16b-scan4"])
def test_paged_decode_matches_contiguous(arch, n_layers):
    """decode_step over a page pool (scattered physical pages) must equal
    decode_step over the contiguous cache — GQA and MLA latent caches,
    in unscanned layers (pools of one layer) and in a scanned segment
    (pools stacked over its layers, written in place at each layer)."""
    cfg = get_config(arch, smoke=True)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers, scan_layers=True)
        assert any(scanned for *_, scanned in stack_plan(cfg))
    params = init_params(jax.random.PRNGKey(0), cfg)
    ps, n_pages, plen = 4, 8, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, plen), 0,
                              cfg.vocab).astype(jnp.int32)
    L = n_pages * ps
    logits, caches_c = prefill(params, {"tokens": toks}, cfg, cache_len=L)
    _, one = prefill(params, {"tokens": toks}, cfg, cache_len=2 * ps)
    pool = init_page_pool(cfg, n_pages + 1, ps)
    write = _cached_page_write(cfg, ps)
    table = [3, 5, 7]                     # scattered, out of order
    for i in range(2):
        pool = write(pool, one, jnp.int32(i), jnp.int32(table[i]))
    tables = np.zeros((1, n_pages), np.int32)
    tables[0, :3] = table
    tok = jnp.argmax(logits[:, :cfg.vocab], -1).astype(jnp.int32)
    pos = jnp.full((1,), plen, jnp.int32)
    for _ in range(4):                    # crosses into block 2 at pos 8
        ld_c, caches_c, _ = decode_step(params, tok, caches_c, pos, cfg)
        ld_p, pool, _ = decode_step(params, tok, pool, pos, cfg,
                                    page_table=jnp.asarray(tables))
        np.testing.assert_array_equal(np.asarray(ld_c, np.float32),
                                      np.asarray(ld_p, np.float32))
        tok = jnp.argmax(ld_c[:, :cfg.vocab], -1).astype(jnp.int32)
        pos = pos + 1


# ------------------------------------------------------- engine, paged
def sequential_tokens(prompt, n_new, cache_len=POOL_TOKENS):
    """Oracle: batch-1 prefill + decode loop at the paged gather length."""
    prefill_fn = cached_prefill_step(CFG, cache_len)
    decode = cached_serve_step(CFG)
    logits, caches = prefill_fn(
        PARAMS, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    toks = [int(jnp.argmax(logits[0, :CFG.vocab]))]
    for i in range(n_new - 1):
        logits, caches = decode(PARAMS, jnp.asarray([toks[-1]], jnp.int32),
                                caches, jnp.int32(len(prompt) + i))
        toks.append(int(jnp.argmax(logits[0, :CFG.vocab])))
    return toks


def paged_engine(n_pages=N_PAGES, rows=3, **kw):
    kw.setdefault("sched", SchedulerConfig(max_prefill_per_step=2))
    return ServingEngine(
        [EngineModel("a", PARAMS, CFG, kv_slots=rows, max_seq=16,
                     kv_layout="paged", page_size=PAGE, n_pages=n_pages)],
        **kw)


def test_paged_engine_matches_sequential_token_for_token():
    eng = paged_engine()
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(6):
        plen = int(rng.integers(3, 12))
        prompt = rng.integers(1, CFG.vocab, plen).tolist()
        reqs.append(eng.submit("a", prompt, max_new_tokens=6))
    s = eng.run()
    assert s["requests_finished"] == 6
    assert s["max_concurrent"] >= 2
    for r in reqs:
        assert r.generated == sequential_tokens(list(r.prompt),
                                                r.max_new_tokens), r.rid


def test_paged_request_exceeds_slot_max_seq():
    """The per-slot ceiling is gone: a single request may span any number
    of pages, up to the whole pool."""
    eng = paged_engine()
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, CFG.vocab, 20).tolist()
    req = eng.submit("a", prompt, max_new_tokens=24)   # 44 tokens > 16
    eng.run()
    assert req.status is RequestStatus.FINISHED
    assert req.generated == sequential_tokens(prompt, 24)
    # but the pool itself still bounds admission
    too_big = eng.submit("a", prompt, max_new_tokens=POOL_TOKENS)
    assert too_big.status is RequestStatus.REJECTED


def test_paged_prefix_sharing_and_cow_are_exact():
    """An identical prompt arriving mid-decode shares the first request's
    pages (the partial tail page included) and COWs on divergence; both
    decodes must still match the oracle exactly, and the pool must drain
    to empty when both finish."""
    eng = paged_engine(sched=SchedulerConfig(max_prefill_per_step=1))
    prompt = [7, 3, 9, 2, 5, 8, 1, 4, 6, 2]      # 2 full pages + partial
    r1 = eng.submit("a", prompt, max_new_tokens=8)
    eng.step()
    eng.step()
    r2 = eng.submit("a", prompt, max_new_tokens=8)
    eng.run()
    alloc = eng.arenas["a"].allocator
    assert alloc.shared_hits >= 3
    assert alloc.cow_copies >= 1
    ref = sequential_tokens(prompt, 8)
    assert r1.generated == ref
    assert r2.generated == ref
    assert alloc.n_free == alloc.n_pages and not alloc.tables
    s = eng.summary()
    assert s["kv_shared_page_hits"] >= 3 and s["kv_cow_copies"] >= 1


def test_paged_pool_exhaustion_preempts_and_recovers():
    """When decode outgrows the pool, the loser is preempted (pages freed,
    request requeued) and re-prefilled once pages free up — every request
    still finishes with oracle-exact tokens."""
    eng = paged_engine(n_pages=8, rows=2,
                       sched=SchedulerConfig(max_prefill_per_step=2))
    rng = np.random.default_rng(2)
    p1 = rng.integers(1, CFG.vocab, 10).tolist()
    p2 = rng.integers(1, CFG.vocab, 10).tolist()
    # each needs ceil((10+16)/4) = 7 pages to finish; the pool holds 8, so
    # the two cannot coexist to completion
    r1 = eng.submit("a", p1, max_new_tokens=16)
    r2 = eng.submit("a", p2, max_new_tokens=16)
    s = eng.run()
    assert s["requests_finished"] == 2
    assert s["preemptions"] >= 1
    assert r1.generated == sequential_tokens(p1, 16, cache_len=8 * PAGE)
    assert r2.generated == sequential_tokens(p2, 16, cache_len=8 * PAGE)


def test_paged_arena_rejects_non_attention_stack():
    with pytest.raises(ValueError):
        PagedKVArena(get_config("hymba-1.5b", smoke=True), 2, 8, 4)
