"""Radix-tree prefix cache: retained KV pages, LRU eviction, chunk-skip.

Three pillars, per the acceptance bar:
  * tree/allocator invariants — hypothesis property tests (with a
    deterministic manual-trials fallback) over random
    admit/decode/finish-with-donate/evict sequences: refcounts never go
    negative, pinned pages are never freed or evicted, lookups return
    block-aligned prefixes of resident pages, and retention conserves
    pages (free + live + cached == pool);
  * token equivalence — warm requests over a cached shared prefix are
    token-for-token identical to cold prefill while recomputing zero
    tokens of the covered chunks (asserted through the prefill_tokens /
    prefix_hit_tokens accounting), on the paged layout and against the
    slot-layout and sequential oracles, including eviction under pool
    pressure and a mid-prefill hit on a resumed request;
  * metrics/CI — the engine summary surfaces hit tokens, hit rate,
    resident cached pages, and LRU evictions; the CI-properties test
    publishes them as junit <properties> for the named workflow step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import cached_prefill_step, cached_serve_step
from repro.nn.model import init_params
from repro.serving import (EngineModel, PageAllocator, SchedulerConfig,
                           ServingEngine)
from repro.serving.request import RequestStatus

CFG = get_config("gemma-7b", smoke=True)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
PAGE = 4


# ---------------------------------------------------------- invariants
def _check_invariants(a: PageAllocator):
    """Conservation laws with retention: every page is free xor
    referenced; refcount == table references + (1 if the tree retains it);
    the free list never holds a live page; retained nodes' pages are
    alive; and the cached-page counter matches the tree."""
    counts = np.zeros(a.n_pages + 1, np.int64)
    for table in a.tables.values():
        for page in table:
            counts[page] += 1
    retained = set()
    stack = list(a.tree._root.children.values())
    n_nodes = 0
    while stack:
        node = stack.pop()
        n_nodes += 1
        assert a.tree._by_page.get(node.page) is node, "page index stale"
        if node.retained:
            retained.add(node.page)
        if len(node.edge) < a.page_size:
            assert not node.children, "partial edge with children"
        stack.extend(node.children.values())
    assert n_nodes == len(a.tree._by_page), "unreachable indexed nodes"
    assert len(retained) == a.tree.n_cached
    free = set(a._free)
    assert len(free) == len(a._free), "free list holds duplicates"
    for page in range(1, a.n_pages + 1):
        expect = counts[page] + (1 if page in retained else 0)
        assert a.refcount[page] == expect, (
            f"page {page}: refcount {a.refcount[page]} != {expect}")
        assert a.refcount[page] >= 0, "negative refcount"
        assert (page in free) == (a.refcount[page] == 0)
    assert a.n_free + int((a.refcount[1:] > 0).sum()) == a.n_pages
    # the incremental evictable count (heap-era bookkeeping) must never
    # drift from the O(tree) reference walk, with and without an exclude
    # set (can_admit excludes the prefix it is about to pin)
    assert a.tree.evictable_count() == a.tree.evictable_walk(a._sole), (
        "incremental evictable count drifted from the reference walk")
    if retained:
        excl = frozenset(list(retained)[:2])
        assert (a.tree.evictable_count(excl)
                == a.tree.evictable_walk(a._sole, excl)), (
            "evictable count with exclude drifted from the walk")
    # every retained leaf must own a live heap entry carrying its current
    # stamp — otherwise a candidate could become invisible to evict_lru
    entries = {(p, s) for s, _, p in a.tree._heap}
    for node in (a.tree._by_page[p] for p in retained):
        if not node.children:
            assert (node.page, node.stamp) in entries, (
                f"retained leaf page {node.page} missing from the "
                "candidate heap")


def _check_match_block_aligned(a: PageAllocator, tokens):
    """A lookup's cover is block-aligned: k matched pages cover exactly the
    first k blocks of `tokens`, every matched page is alive, and no page
    repeats within one match."""
    pages = a.match_prefix(tuple(tokens), touch=False)
    assert len(pages) <= a.blocks_for(len(tokens))
    assert len(set(pages)) == len(pages), "match repeats a physical page"
    for page in pages:
        assert a.refcount[page] >= 1, "match returned a dead page"
        assert page not in set(a._free)


def _random_trial(seed: int, *, n_ops: int = 60, retain: bool = True,
                  max_cached=None):
    """One random op sequence over a small pool with a tiny token alphabet
    (so prefixes really collide): admit via alloc_table or
    begin_table/grow_table, register, extend, cow, finish with or without
    donation — invariants checked after every op."""
    rng = np.random.default_rng(seed)
    a = PageAllocator(8, 2, retain=retain, max_cached=max_cached)
    live = {}               # rid -> tokens
    next_rid = 0
    for _ in range(n_ops):
        op = rng.choice(["new", "stage", "finish", "donate", "extend",
                         "cow", "match"])
        if op in ("new", "stage"):
            n = int(rng.integers(1, 9))
            tokens = tuple(int(t) for t in rng.integers(0, 3, n))
            if op == "new":
                got = a.alloc_table(next_rid, tokens)
                if got is not None:
                    a.register(next_rid, tokens)
                    live[next_rid] = tokens
            else:
                a.begin_table(next_rid, tokens)
                if a.grow_table(next_rid, a.blocks_for(n)):
                    a.register(next_rid, tokens)
                    live[next_rid] = tokens
                else:       # reservation lost the race: release
                    a.free_table(next_rid)
            next_rid += 1
        elif op == "match":
            n = int(rng.integers(1, 9))
            _check_match_block_aligned(
                a, tuple(int(t) for t in rng.integers(0, 3, n)))
        elif live:
            rid = list(live)[int(rng.integers(len(live)))]
            if op == "finish":
                a.free_table(rid)
                live.pop(rid)
            elif op == "donate":
                tokens = live.pop(rid)
                # grow the sequence like decode would, then donate the
                # prefix the table actually covers
                extra = tuple(int(t) for t in rng.integers(
                    0, 3, len(a.tables[rid]) * a.page_size - len(tokens)))
                a.free_table(rid, donate_tokens=tokens + extra)
            elif op == "extend":
                a.extend(rid)
            elif op == "cow":
                a.cow(rid, int(rng.integers(len(a.tables[rid]))))
        _check_invariants(a)
    for rid in list(live):
        a.free_table(rid)
        live.pop(rid)
    _check_invariants(a)
    # after releasing every table, only tree-retained pages stay used
    assert a.n_used == a.tree.n_cached
    if max_cached is not None:
        assert a.tree.n_cached <= max_cached
    # and the cache is fully evictable: draining it empties the pool
    assert a.ensure_free(a.n_pages)
    assert a.n_free == a.n_pages and a.tree.n_cached == 0
    _check_invariants(a)


def test_allocator_retention_manual_trials():
    """Deterministic fallback for environments without hypothesis."""
    for seed in range(25):
        _random_trial(seed)
    for seed in range(10):
        _random_trial(100 + seed, max_cached=3)
    for seed in range(5):
        _random_trial(200 + seed, retain=False)


@pytest.mark.parametrize("cap", [0, 1])
def test_cache_cap_holds_once_children_drop(cap):
    """Seed 3 retains a page while it still has live children, so the cap
    cannot evict it then; the cap must be enforced again when they drop."""
    _random_trial(3, max_cached=cap)


def test_allocator_retention_property_random_ops():
    """Hypothesis sweep over the same op machine."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=80, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           cap=st.one_of(st.none(), st.integers(0, 6)))
    def prop(seed, cap):
        _random_trial(seed, max_cached=cap)

    prop()


# ------------------------------------------------------------ tree unit
def test_donated_pages_survive_and_rematch():
    a = PageAllocator(8, 2, retain=True)
    tokens = (5, 6, 7, 8, 9)                 # 2 full pages + 1 partial
    table, _ = a.alloc_table(0, tokens)
    a.register(0, tokens)
    a.free_table(0, donate_tokens=tokens)
    assert a.tree.n_cached == 3 and a.n_used == 3
    # full-block match against a longer prompt: partial tail page of the
    # donation does not match a full block (block-aligned semantics)
    assert a.match_prefix((5, 6, 7, 8, 1, 2)) == table[:2]
    # exact match reaches the partial page too
    assert a.match_prefix(tokens) == table
    _check_invariants(a)


def test_lru_eviction_order_and_pinning():
    a = PageAllocator(4, 2, retain=True)
    a.alloc_table(0, (1, 1))
    a.register(0, (1, 1))
    a.free_table(0, donate_tokens=(1, 1))
    a.alloc_table(1, (2, 2))
    a.register(1, (2, 2))
    a.free_table(1, donate_tokens=(2, 2))
    assert a.tree.n_cached == 2
    # touch (1, 1): (2, 2) becomes the LRU victim
    assert a.match_prefix((1, 1))
    t2, s2 = a.alloc_table(2, (1, 1))       # pins the (1, 1) page
    assert s2 == 1
    # demand 3 fresh pages: 2 free + 1 evictable — the pinned (1, 1)
    # page must survive, the (2, 2) page must go
    t3, _ = a.alloc_table(3, (7, 8, 9, 0, 7, 7))
    assert t3 is not None and len(t3) == 3
    assert a.tree.evictions == 1
    assert a.match_prefix((1, 1)) == t2      # still resident, still shared
    assert a.match_prefix((2, 2)) == []      # evicted
    _check_invariants(a)


def test_donation_onto_live_nodes_transfers_refs():
    """Donating a sequence whose prefix blocks are still live transfers
    the caller's refcounts into the tree: the pages outlive the remaining
    live holder, and leaf-first eviction can fully drain the chain."""
    a = PageAllocator(8, 2, retain=True)
    a.alloc_table(0, (3, 3))
    a.register(0, (3, 3))
    t1, s1 = a.alloc_table(1, (3, 3, 4, 4))   # shares rid 0's page
    assert s1 == 1
    a.register(1, (3, 3, 4, 4))
    a.free_table(1, donate_tokens=(3, 3, 4, 4))
    assert a.tree.n_cached == 2               # both blocks retained
    a.free_table(0)                           # live holder exits
    assert a.tree.n_cached == 2 and a.n_used == 2
    _check_invariants(a)
    assert a.ensure_free(a.n_pages)           # leaf first, then parent
    assert a.n_free == a.n_pages and a.tree.n_cached == 0
    _check_invariants(a)


def test_cascade_removal_releases_unreachable_retained_pages():
    """A live (non-retained) node dying must cascade through its subtree:
    retained descendants attached below it (via a donation that collided
    on the parent block) become unreachable, so their tree refcounts are
    released — otherwise those pages leak forever."""
    a = PageAllocator(8, 2, retain=True)
    # rid 0 and rid 1 prefill the same prompt concurrently (neither
    # registered yet), so rid 1 holds its OWN page for block (3, 3)
    a.alloc_table(0, (3, 3))
    t1, s1 = a.alloc_table(1, (3, 3, 4, 4))
    assert s1 == 0, "no sharing before registration"
    a.register(0, (3, 3))                     # rid 0 wins the index
    a.register(1, (3, 3, 4, 4))               # collides on block 0: its
    #                                           (4,4) node attaches BELOW
    #                                           rid 0's live node
    a.free_table(1, donate_tokens=(3, 3, 4, 4))
    # rid 1's (3,3) page collided (freed); its (4,4) page is retained as
    # a child of rid 0's live, non-retained node
    assert a.tree.n_cached == 1
    _check_invariants(a)
    # rid 0 exits without donating: its page dies, and the retained child
    # below it is unreachable — the cascade must free it too
    a.free_table(0)
    assert a.tree.n_cached == 0
    assert a.n_free == a.n_pages
    _check_invariants(a)


def test_match_is_incremental_o_blocks():
    """The admission-path match walks one dict probe per block — resident
    chains hundreds of blocks deep stay cheap.  Structural proxy: probe
    count equals matched blocks + 1, independent of prompt length."""
    a = PageAllocator(64, 2, retain=True)
    tokens = tuple(int(x) for x in np.arange(128) % 5)
    a.begin_table(0, tokens)
    a.grow_table(0, a.blocks_for(len(tokens)))
    a.register(0, tokens)
    a.free_table(0, donate_tokens=tokens)

    probes = 0
    orig_get = dict.get

    class CountingDict(dict):
        def get(self, *args):
            nonlocal probes
            probes += 1
            return orig_get(self, *args)

    # swap every children dict for a counting one
    stack = [a.tree._root]
    while stack:
        node = stack.pop()
        node.children = CountingDict(node.children)
        stack.extend(node.children.values())
    pages = a.match_prefix(tokens, touch=False)
    assert len(pages) == 64
    assert probes == 64, f"{probes} probes for 64 blocks (not incremental)"


def test_eviction_is_heap_ordered_not_a_tree_scan():
    """The quadratic path is gone: draining N retained leaves costs O(1)
    predicate probes per eviction off the stamp-ordered candidate heap —
    not an O(tree) leaf scan each — never falls back to the reference
    walk, and still evicts in exact LRU (donation stamp) order.  The
    admission-side evictable count likewise answers from the incremental
    counter without touching the walk."""
    a = PageAllocator(64, 2, retain=True)
    n_leaves = 32
    for i in range(n_leaves):
        tokens = (i, i)                     # distinct single-block chains
        got = a.alloc_table(i, tokens)
        assert got is not None
        a.register(i, tokens)
        a.free_table(i, donate_tokens=tokens)
    assert a.tree.n_cached == n_leaves
    donated_pages = [a.tree.match((i, i), touch=False)[0]
                     for i in range(n_leaves)]

    def forbid(*args, **kwargs):            # production must not walk
        raise AssertionError("O(tree) reference scan used on the "
                             "production eviction path")

    a.tree._evictable_leaf = forbid
    a.tree.evictable_walk = forbid

    # admission count: pure counter read, no walk
    assert a.evictable_pages() == n_leaves
    assert a.evictable_pages(frozenset(donated_pages[:3])) == n_leaves - 3

    sole_calls = 0

    def counting_sole(page):
        nonlocal sole_calls
        sole_calls += 1
        return a._sole(page)

    evicted = []

    def record_free(page):
        evicted.append(donated_pages.index(page))
        a.free_page(page)

    while a.tree.evict_lru(counting_sole, record_free):
        pass
    assert a.tree.n_cached == 0
    # one structurally-valid candidate pop (= one predicate probe) per
    # eviction; the old scan paid n_leaves probes per eviction (~530 here)
    assert sole_calls <= n_leaves + 4, (
        f"{sole_calls} predicate probes draining {n_leaves} leaves — "
        "eviction is scanning, not popping the heap")
    # LRU order: donation order is stamp order
    assert evicted == list(range(n_leaves))


# ------------------------------------------------------- engine level
def sequential_tokens(prompt, n_new, cache_len):
    logits, caches = cached_prefill_step(CFG, cache_len)(
        PARAMS, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    decode = cached_serve_step(CFG)
    toks = [int(jnp.argmax(logits[0, :CFG.vocab]))]
    for i in range(n_new - 1):
        logits, caches = decode(PARAMS, jnp.asarray([toks[-1]], jnp.int32),
                                caches, jnp.int32(len(prompt) + i))
        toks.append(int(jnp.argmax(logits[0, :CFG.vocab])))
    return toks


def cache_engine(*, cache=True, chunk=4, budget=8, n_pages=24, rows=3,
                 cache_pages=0, max_prefill=2):
    return ServingEngine(
        [EngineModel("a", PARAMS, CFG, kv_slots=rows, max_seq=16,
                     kv_layout="paged", page_size=PAGE, n_pages=n_pages,
                     prefix_cache=cache, prefix_cache_pages=cache_pages)],
        sched=SchedulerConfig(max_prefill_per_step=max_prefill,
                              prefill_token_budget=budget),
        prefill_chunk=chunk)


def test_prefix_cache_requires_paged_layout():
    with pytest.raises(ValueError):
        EngineModel("a", PARAMS, CFG, kv_layout="slot", prefix_cache=True)


def test_warm_request_skips_covered_chunks_token_for_token():
    """The headline: a warm request over a cached shared prefix produces
    token-for-token identical output to cold prefill while re-prefilling
    zero tokens of the covered chunks — on the paged engine, against the
    cache-off paged engine, the slot-layout engine, and the sequential
    oracle."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, CFG.vocab, 16).tolist()
    oracle = sequential_tokens(prompt, 6, cache_len=24 * PAGE)

    cold = cache_engine(cache=False)
    c1 = cold.submit("a", prompt, max_new_tokens=6)
    cold.run()

    slot_eng = ServingEngine(
        [EngineModel("a", PARAMS, CFG, kv_slots=2, max_seq=96)],
        prefill_chunk=4)
    s1 = slot_eng.submit("a", prompt, max_new_tokens=6)
    slot_eng.run()
    s2 = slot_eng.submit("a", prompt, max_new_tokens=6)  # slot: no cache,
    slot_eng.run()                                       # plain recompute

    warm = cache_engine(cache=True)
    w1 = warm.submit("a", prompt, max_new_tokens=6)
    warm.run()
    w2 = warm.submit("a", prompt, max_new_tokens=6)
    s = warm.run()

    for r in (c1, s1, s2, w1, w2):
        assert r.generated == oracle, r.rid
    # covered = 4 full pages = 16 tokens, capped at len-1 → 15 skipped,
    # exactly 1 computed (the final token's chunk produces real logits)
    assert s["prefill_tokens"] == 16 + 1
    assert s["prefix_hit_tokens"] == 15
    assert s["prefix_hit_rate"] == pytest.approx(15 / 32)
    assert s["kv_prefix_cached_pages"] > 0
    assert warm._prefills == {}


def test_multi_turn_history_reuse():
    """The multi-turn regime the cache exists for: turn k+1's prompt is
    turn k's prompt + generated + new user tokens, so the donated pages
    (prompt AND generated) cover a growing prefix and each turn computes
    only its tail."""
    eng = cache_engine(n_pages=48, budget=None)
    rng = np.random.default_rng(4)
    hist = rng.integers(1, CFG.vocab, 8).tolist()
    total_prompt = 0
    for turn in range(3):
        total_prompt += len(hist)
        req = eng.submit("a", hist, max_new_tokens=4)
        eng.run()
        assert req.generated == sequential_tokens(hist, 4,
                                                  cache_len=48 * PAGE)
        hist = hist + req.generated + rng.integers(1, CFG.vocab, 5).tolist()
    s = eng.summary()
    # conservation: every submitted prompt token was either computed in a
    # chunk or served from the cache — and a real share came from cache
    assert s["prefill_tokens"] + s["prefix_hit_tokens"] == total_prompt
    assert s["prefix_hit_tokens"] >= 8
    assert s["prefill_tokens"] < total_prompt


def test_eviction_under_pressure_stays_exact():
    """Retained pages fill the pool; admission demanding more pages than
    the free list holds LRU-evicts cached pages on demand instead of
    failing or preempting — and the evicted-then-recomputed request is
    still oracle-exact."""
    eng = cache_engine(n_pages=8, rows=2, budget=None)
    rng = np.random.default_rng(5)
    p1 = rng.integers(1, CFG.vocab, 12).tolist()
    r1 = eng.submit("a", p1, max_new_tokens=4)
    eng.run()
    alloc = eng.arenas["a"].allocator
    cached_before = alloc.tree.n_cached
    assert cached_before >= 3                 # pool is 8; most of it cached
    # a non-matching request needing more than the free pages forces LRU
    # eviction of the retained prefix
    p2 = rng.integers(1, CFG.vocab, 16).tolist()
    r2 = eng.submit("a", p2, max_new_tokens=8)
    s = eng.run()
    assert alloc.tree.evictions >= 1
    assert s["kv_prefix_evictions"] >= 1
    assert r1.generated == sequential_tokens(p1, 4, cache_len=8 * PAGE)
    assert r2.generated == sequential_tokens(p2, 8, cache_len=8 * PAGE)
    assert s["preemptions"] == 0, "eviction should pre-empt preemption"
    # p1's prefix was (partially) evicted: a p1 rerun may re-prefill, but
    # stays exact
    r3 = eng.submit("a", p1, max_new_tokens=4)
    eng.run()
    assert r3.generated == r1.generated


def test_mid_prefill_hit_and_preempt_resume():
    """A warm request whose prefill is split over chunks: admission skips
    the covered chunks, a mid-prefill preemption keeps both the skip and
    the computed progress, and the resume re-runs neither."""
    shared = list(range(1, 17))               # 16 tokens = 4 pages
    eng = cache_engine(n_pages=32, rows=2, budget=4, max_prefill=1)
    # donor finishes first: donates shared + its generated pages
    donor = eng.submit("a", shared, max_new_tokens=4)
    eng.run()
    assert donor.status is RequestStatus.FINISHED
    hits_after_donor = eng.metrics.prefix_hit_tokens
    rng = np.random.default_rng(6)
    tail = rng.integers(1, CFG.vocab, 8).tolist()
    long_req = eng.submit("a", shared + tail, max_new_tokens=3)
    eng.step()                                # hit-skip + first real chunk
    st = eng._prefills[long_req.rid]
    assert st.skipped == 16                   # admission hit: 4 chunks
    assert st.done == 20                      # + one computed chunk
    eng.preempt(long_req.rid)
    assert long_req.status is RequestStatus.PREEMPTED
    assert eng._prefills[long_req.rid].done == 20    # staging survives
    s = eng.run()
    assert long_req.status is RequestStatus.FINISHED
    assert eng._prefills == {}
    # donor computed 16; long computed only its uncovered 8
    assert s["prefill_tokens"] == 16 + 8
    assert s["prefix_hit_tokens"] == 16
    assert long_req.generated == sequential_tokens(shared + tail, 3,
                                                   cache_len=32 * PAGE)
    assert eng.metrics.prefix_hit_tokens > hits_after_donor


def test_resume_jump_when_coverage_grows_mid_prefill():
    """The hit boundary can MOVE while a request sits preempted: pages
    donated in the meantime extend the cover past its completed chunks,
    and readmission jumps `done` forward (reloading the staging carry-in
    from the pool) instead of recomputing.  The cold first stint is
    simulated by disabling the tenant's skip eligibility — the state a
    restarted or cache-cold engine stint leaves behind."""
    eng = cache_engine(n_pages=32, rows=2, budget=4, max_prefill=1)
    arena = eng.arenas["a"]
    rng = np.random.default_rng(10)
    pref = rng.integers(1, CFG.vocab, 24).tolist()
    donor = eng.submit("a", pref, max_new_tokens=5)
    eng.run()                                 # donates pref + 4 gen tokens
    assert donor.status is RequestStatus.FINISHED
    tail = rng.integers(1, CFG.vocab, 8).tolist()
    long_req = eng.submit("a", pref + tail, max_new_tokens=2)
    arena.skip_ok = False                     # cold stint: no hit applied
    eng.step()
    st = eng._prefills[long_req.rid]
    assert st.skipped == 0 and st.done == 4   # one cold chunk
    eng.preempt(long_req.rid)
    arena.skip_ok = True
    s = eng.run()
    # readmission re-matched: covered 24, floored to chunk 24 > done 4 →
    # jump of 20; only the 8 uncovered tokens plus the already-computed
    # cold chunk ever ran
    assert long_req.status is RequestStatus.FINISHED
    assert s["prefill_tokens"] == 24 + 4 + 8
    assert s["prefix_hit_tokens"] == 20
    assert long_req.generated == sequential_tokens(pref + tail, 2,
                                                   cache_len=32 * PAGE)


def test_full_prompt_cached_still_emits_first_token():
    """An exactly-cached prompt must still run its final chunk — the
    first token comes from real logits, not the cache."""
    eng = cache_engine(n_pages=24, budget=None, chunk=4)
    prompt = list(range(2, 10))               # 8 tokens = 2 pages, 2 chunks
    r1 = eng.submit("a", prompt, max_new_tokens=3)
    eng.run()
    r2 = eng.submit("a", prompt, max_new_tokens=3)
    s = eng.run()
    # covered = 8 (exact partial/full match), capped at 7 → 1 computed
    assert s["prefill_tokens"] == 8 + 1
    assert r2.generated == r1.generated
    assert r2.generated == sequential_tokens(prompt, 3, cache_len=24 * PAGE)


def test_int8_tenant_skips_covered_chunks_token_for_token():
    """int8 tenants now *skip* covered prefix-cache chunks: the
    dequantize-aware `_cached_page_read` reloads cached pages into the
    bf16 staging (codes × scales, same values decode attends after
    install), so a warm int8 request skips its covered chunks and still
    produces the same tokens as the cold run."""
    import dataclasses as dc
    cfg8 = dc.replace(CFG, kv_cache_dtype="int8")
    params = PARAMS

    def eng8(cache):
        return ServingEngine(
            [EngineModel("a", params, cfg8, kv_slots=2, max_seq=16,
                         kv_layout="paged", page_size=PAGE, n_pages=24,
                         prefix_cache=cache)],
            sched=SchedulerConfig(max_prefill_per_step=1),
            prefill_chunk=4)

    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg8.vocab, 12).tolist()
    cold = eng8(False)
    c1 = cold.submit("a", prompt, max_new_tokens=5)
    cold.run()
    warm = eng8(True)
    w1 = warm.submit("a", prompt, max_new_tokens=5)
    warm.run()
    w2 = warm.submit("a", prompt, max_new_tokens=5)
    s = warm.run()
    assert warm.arenas["a"].skip_ok
    # w2's prompt is fully covered: skip to the len-1 cap (11 tokens
    # served from cache), only the final chunk computes
    assert s["prefix_hit_tokens"] == 11
    assert s["prefill_tokens"] < 24           # w2 did not re-prefill
    assert w1.generated == w2.generated == c1.generated


def test_cache_cap_bounds_resident_pages():
    eng = cache_engine(n_pages=32, cache_pages=2, budget=None)
    rng = np.random.default_rng(8)
    for _ in range(3):
        eng.submit("a", rng.integers(1, CFG.vocab, 12).tolist(),
                   max_new_tokens=3)
    eng.run()
    alloc = eng.arenas["a"].allocator
    assert alloc.tree.max_cached == 2
    assert alloc.tree.n_cached <= 2
    assert alloc.tree.evictions >= 1


def test_summary_and_junit_properties(record_property):
    """Metrics surface + the CI counters: a warm two-request workload
    publishes hit tokens, hit rate, resident pages, and evictions as
    junit properties (the named CI step re-runs exactly this test)."""
    eng = cache_engine(n_pages=12, budget=None)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, CFG.vocab, 12).tolist()
    r1 = eng.submit("a", prompt, max_new_tokens=4)
    eng.run()
    r2 = eng.submit("a", prompt, max_new_tokens=4)
    eng.run()
    other = eng.submit("a", rng.integers(1, CFG.vocab, 14).tolist(),
                       max_new_tokens=4)
    s = eng.run()
    assert r1.generated == r2.generated
    assert other.status is RequestStatus.FINISHED
    for key in ("prefix_hit_tokens", "prefix_hit_rate",
                "kv_prefix_cached_pages", "kv_prefix_evictions",
                "prefix_cached_pages_mean", "prefix_cached_pages_max"):
        assert key in s, key
    assert s["prefix_hit_tokens"] >= 8
    assert 0.0 < s["prefix_hit_rate"] < 1.0
    record_property("prefix_hit_tokens", int(s["prefix_hit_tokens"]))
    record_property("prefix_hit_rate", round(s["prefix_hit_rate"], 4))
    record_property("prefix_cached_pages_max",
                    int(s["prefix_cached_pages_max"]))
    record_property("prefix_evictions", int(s["kv_prefix_evictions"]))
