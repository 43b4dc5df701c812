"""Continuous-batching serving engine: continuous-batched decode must match
the sequential prefill + make_serve_step path token-for-token, KV-slot
eviction must never corrupt an in-flight request, and the multi-tenant
weight residency must account installs sanely."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import cached_prefill_step, cached_serve_step
from repro.nn.model import init_params, serving_params
from repro.serving import (EngineModel, KVArena, SchedulerConfig,
                           ServingEngine, StepScheduler, WeightResidencyManager)
from repro.serving.request import Request, RequestStatus
from repro.serving.variants import perturbed_variant

MAX_SEQ = 32
CFG = get_config("gemma-7b", smoke=True)
PARAMS_A = init_params(jax.random.PRNGKey(0), CFG)
PARAMS_B = init_params(jax.random.PRNGKey(1), CFG)


def sequential_tokens(params, cfg, prompt, n_new):
    """Oracle: the plain serve.py path — batch-1 prefill, then
    make_serve_step one token at a time, same cache length as the engine."""
    prefill = cached_prefill_step(cfg, MAX_SEQ)
    decode = cached_serve_step(cfg)
    logits, caches = prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    toks = [int(jnp.argmax(logits[0, :cfg.vocab]))]
    pos = len(prompt)
    for i in range(n_new - 1):
        logits, caches = decode(params, jnp.asarray([toks[-1]], jnp.int32),
                                caches, jnp.int32(pos + i))
        toks.append(int(jnp.argmax(logits[0, :cfg.vocab])))
    return toks


def make_engine(**kw):
    kw.setdefault("sched", SchedulerConfig(max_prefill_per_step=2))
    return ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=3, max_seq=MAX_SEQ),
         EngineModel("b", PARAMS_B, CFG, kv_slots=3, max_seq=MAX_SEQ)],
        weight_arena_slots=CFG.n_layers + 1, **kw)


def submit_mixed(eng, n, seed=0, gen=6):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 12))
        prompt = rng.integers(1, CFG.vocab, plen).tolist()
        reqs.append(eng.submit("a" if i % 2 == 0 else "b", prompt,
                               max_new_tokens=gen))
    return reqs


def test_engine_matches_sequential_decode_token_for_token():
    eng = make_engine()
    reqs = submit_mixed(eng, 8)
    s = eng.run()
    assert s["requests_finished"] == 8
    assert s["max_concurrent"] >= 4  # genuinely continuous-batched
    for r in reqs:
        params = PARAMS_A if r.model == "a" else PARAMS_B
        ref = sequential_tokens(params, CFG, list(r.prompt), r.max_new_tokens)
        assert r.generated == ref, f"rid {r.rid} diverged from sequential"


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_bf16_tenant_params_match_float32_tokens(kv_layout):
    """Serving-held weights (matmul leaves bf16, placed on the device once)
    give exactly the float32 tenants' tokens — the forward pass casts
    every matmul weight to bf16 where it is used — and no tenant keeps a
    host (numpy) leaf, which every step would copy to the device."""
    host_b = jax.tree.map(np.asarray, perturbed_variant(PARAMS_A))
    runs = {}
    for tag, (pa, pb) in {
            "f32": (PARAMS_A, host_b),
            "bf16": (serving_params(PARAMS_A), serving_params(host_b)),
    }.items():
        eng = ServingEngine(
            [EngineModel(n, p, CFG, kv_slots=3, max_seq=MAX_SEQ,
                         kv_layout=kv_layout, page_size=4)
             for n, p in (("a", pa), ("b", pb))],
            sched=SchedulerConfig(max_prefill_per_step=2),
            weight_arena_slots=CFG.n_layers + 1)
        for m in eng.models.values():
            leaves = jax.tree.leaves(m.params)
            assert all(isinstance(l, jax.Array) for l in leaves), m.name
            assert any(l.dtype == jnp.bfloat16 for l in leaves) == (
                tag == "bf16")
        reqs = submit_mixed(eng, 6)
        eng.run()
        runs[tag] = [r.generated for r in reqs]
    assert runs["bf16"] == runs["f32"]


def test_requests_join_and_leave_between_steps():
    eng = make_engine()
    short = eng.submit("a", [5, 6, 7], max_new_tokens=2)
    long = eng.submit("a", [8, 9, 10, 11], max_new_tokens=10)
    eng.step()
    late = eng.submit("a", [1, 2, 3, 4, 5], max_new_tokens=3)
    eng.run()
    # the short request left the batch while the long one kept decoding,
    # and the late arrival joined mid-flight — no head-of-line blocking
    assert short.status is RequestStatus.FINISHED
    assert late.status is RequestStatus.FINISHED
    assert long.status is RequestStatus.FINISHED
    for r in (short, long, late):
        params = PARAMS_A
        assert r.generated == sequential_tokens(params, CFG, list(r.prompt),
                                                r.max_new_tokens)


def test_eviction_never_corrupts_inflight_requests():
    eng = make_engine()
    reqs = submit_mixed(eng, 6, seed=3, gen=8)
    # run until everything is admitted and mid-decode
    for _ in range(4):
        eng.step()
    victim = next(r for r in reqs if r.status is RequestStatus.RUNNING)
    survivors = [r for r in reqs if r is not victim]
    eng.preempt(victim.rid)
    assert victim.status is RequestStatus.PREEMPTED
    eng.run()
    assert victim.status is RequestStatus.FINISHED
    assert victim.preemptions == 1
    # every request — the preempted one included — matches the oracle
    for r in reqs:
        params = PARAMS_A if r.model == "a" else PARAMS_B
        ref = sequential_tokens(params, CFG, list(r.prompt), r.max_new_tokens)
        assert r.generated == ref, (
            f"rid {r.rid} corrupted (preempted={r is victim})")


def test_slot_reuse_after_eviction_is_isolated():
    """A freed slot keeps stale KV codes (the _Occupancy discipline); a new
    occupant prefilled over it must decode as if the arena were fresh."""
    eng = ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=1, max_seq=MAX_SEQ)],
        sched=SchedulerConfig(max_prefill_per_step=1))
    first = eng.submit("a", [9, 8, 7, 6, 5, 4, 3], max_new_tokens=5)
    eng.run()
    second = eng.submit("a", [3, 1, 4], max_new_tokens=5)  # same slot 0
    eng.run()
    assert first.generated == sequential_tokens(PARAMS_A, CFG,
                                                list(first.prompt), 5)
    assert second.generated == sequential_tokens(PARAMS_A, CFG,
                                                 list(second.prompt), 5)


def test_admission_control_rejects():
    eng = make_engine(sched=SchedulerConfig(max_queue=2))
    with pytest.raises(ValueError):
        eng.submit("a", [1, 2], max_new_tokens=0)
    too_long = eng.submit("a", list(range(1, MAX_SEQ)), max_new_tokens=8)
    assert too_long.status is RequestStatus.REJECTED
    eng.submit("a", [1], max_new_tokens=1)
    eng.submit("a", [2], max_new_tokens=1)
    overflow = eng.submit("a", [3], max_new_tokens=1)
    assert overflow.status is RequestStatus.REJECTED
    s = eng.run()
    assert s["requests_rejected"] == 2
    assert s["requests_finished"] == 2


def test_turn_never_lands_on_budget_blocked_tenant():
    """Regression: with a global max_active budget exhausted by tenant a,
    the time-slice must not rotate onto queued-only tenant b (which can
    neither decode nor admit) — that livelocked the engine."""
    eng = make_engine(sched=SchedulerConfig(max_active=2,
                                            max_prefill_per_step=2,
                                            model_turn_steps=4))
    r1 = eng.submit("a", [1, 2, 3], max_new_tokens=20)
    r2 = eng.submit("a", [4, 5, 6], max_new_tokens=20)
    r3 = eng.submit("b", [7, 8, 9], max_new_tokens=4)
    s = eng.run()
    assert s["requests_finished"] == 3
    for r in (r1, r2, r3):
        params = PARAMS_A if r.model == "a" else PARAMS_B
        assert r.generated == sequential_tokens(params, CFG, list(r.prompt),
                                                r.max_new_tokens)


def test_duplicate_tenant_names_rejected():
    with pytest.raises(ValueError):
        ServingEngine([
            EngineModel("a", PARAMS_A, CFG, kv_slots=2, max_seq=MAX_SEQ),
            EngineModel("a", PARAMS_B, CFG, kv_slots=2, max_seq=MAX_SEQ)])


def test_kv_arena_slot_bookkeeping():
    arena = KVArena(CFG, n_slots=3, max_seq=8)
    s0, s1 = arena.alloc(10), arena.alloc(11)
    assert {s0, s1} == {0, 1} and arena.n_free == 1
    assert arena.owner_of(s0) == 10
    arena.evict(s0)
    assert arena.n_free == 2 and arena.owner_of(s0) is None
    # freed slot is reallocated last (FIFO free list)
    assert arena.alloc(12) == 2
    assert arena.alloc(13) == s0
    assert arena.alloc(14) is None  # full


def test_scheduler_policies():
    sched = StepScheduler(SchedulerConfig(policy="sjf",
                                          max_prefill_per_step=8))
    reqs = [Request(rid=i, model="a", prompt=tuple(range(n)),
                    max_new_tokens=1, arrival_t=0.0)
            for i, n in enumerate([5, 2, 9])]
    for r in reqs:
        sched.submit(r)
    admits = sched.next_admits({"a": 3}, 0)
    assert [r.rid for r in admits] == [1, 0, 2]  # shortest first

    # a preempted request outranks shorter fresh arrivals under sjf
    preempted = Request(rid=9, model="a", prompt=tuple(range(20)),
                        max_new_tokens=4, arrival_t=0.0)
    for r in admits:
        sched.submit(r)
    sched.requeue(preempted)
    assert sched.next_admits({"a": 1}, 0) == [preempted]


def test_residency_cross_tenant_reuse_accounting():
    models = {"a": (PARAMS_A, CFG), "b": (PARAMS_B, CFG)}
    res = WeightResidencyManager(models, CFG.n_layers + 1, reuse=True)
    assert not res.fits(["a", "b"])
    w1 = res.ensure("a", step=0)
    assert res.resident_fraction("a") == 1.0
    w2 = res.ensure("b", step=1)   # evicts a's layers via delta installs
    assert res.resident_fraction("b") == 1.0
    assert res.stats.cross_tenant_installs >= 1
    assert 0 <= res.stats.savings <= 1
    assert res.ensure("b", step=2) == 0  # already resident


def test_residency_variant_tenant_delta_is_cheap():
    """An identical second tenant must install over the first almost for
    free — the pooled §V-C offsets keep aligned tenants code-identical."""
    models = {"base": (PARAMS_A, CFG), "copy": (PARAMS_A, CFG)}
    res = WeightResidencyManager(models, CFG.n_layers, reuse=True)
    cold_wire = res.ensure("base", step=0)      # cold installs ship raw
    copy_wire = res.ensure("copy", step=1)      # delta over identical codes
    # identical codes -> delta stream is just the entropy-coder table
    assert copy_wire < 0.05 * cold_wire


def test_residency_arena_too_small_raises():
    with pytest.raises(ValueError):
        WeightResidencyManager({"a": (PARAMS_A, CFG)}, CFG.n_layers - 1)


def test_sampling_greedy_default_and_top1_match_argmax():
    from repro.serving import request_key, sample_token
    logits = jnp.asarray([0.1, 2.0, -1.0, 1.9, 0.0, 5.0])  # padded vocab 6
    # greedy ignores the padded tail beyond vocab
    assert sample_token(logits, vocab=4) == 1
    key = request_key(seed=123, rid=0)
    # top-1 sampling degenerates to argmax at any temperature
    assert sample_token(logits, vocab=4, temperature=2.0, top_k=1,
                        key=key) == 1


def test_sampling_is_seed_deterministic_and_top_k_bounded():
    from repro.serving import request_key, sample_token
    logits = jnp.asarray(np.linspace(-1.0, 1.0, 16), jnp.float32)
    key = request_key(seed=7, rid=99)
    draws = [sample_token(logits, vocab=16, temperature=1.5, top_k=4,
                          key=key, step=s) for s in range(32)]
    again = [sample_token(logits, vocab=16, temperature=1.5, top_k=4,
                          key=request_key(seed=7, rid=0), step=s)
             for s in range(32)]
    assert draws == again            # seed (not rid) drives the stream
    assert set(draws) <= {12, 13, 14, 15}   # top-4 of ascending logits
    assert len(set(draws)) > 1       # genuinely stochastic at T=1.5


# ------------------------------------------------------ metrics math
def _finished_request(rid, arrival, first, finish, max_itl=None):
    r = Request(rid=rid, model="a", prompt=(1,), max_new_tokens=1,
                arrival_t=arrival)
    r.first_token_t = first
    r.finish_t = finish
    r.max_itl = max_itl
    return r


def test_metrics_quantiles_on_known_distribution():
    """p50/p95 of latency/ttft/itl on a known uniform grid must match
    numpy's linear-interpolation percentiles exactly."""
    from repro.serving import EngineMetrics
    m = EngineMetrics()
    for i in range(1, 101):   # latencies 1..100s, ttft 0.1..10s, itl i/200
        m.record_finish(_finished_request(i, 0.0, i / 10.0, float(i),
                                          max_itl=i / 200.0))
    s = m.summary(wall_s=10.0)
    assert s["latency_p50_s"] == pytest.approx(50.5)
    assert s["latency_p95_s"] == pytest.approx(95.05)
    assert s["ttft_p50_s"] == pytest.approx(5.05)
    assert s["ttft_p95_s"] == pytest.approx(9.505)
    assert s["itl_max_p50_s"] == pytest.approx(50.5 / 200.0)
    assert s["itl_max_p95_s"] == pytest.approx(95.05 / 200.0)
    assert s["requests_finished"] == 100


def test_metrics_empty_window_edge_cases():
    """No finished requests / no steps: percentiles are NaN (not a crash,
    not a misleading zero), counters and rates are zero."""
    import math

    from repro.serving import EngineMetrics
    s = EngineMetrics().summary(wall_s=0.0)
    for k in ("latency_p50_s", "latency_p95_s", "ttft_p50_s", "ttft_p95_s",
              "itl_max_p50_s", "itl_max_p95_s"):
        assert math.isnan(s[k]), k
    assert s["tokens_generated"] == 0
    assert s["tokens_per_s"] == 0
    assert s["queue_depth_mean"] == 0.0
    assert s["queue_depth_max"] == 0.0
    assert s["install_stall_steps"] == 0.0
    assert s["overlap_hidden_bytes"] == 0.0

    # a request that never got a first token contributes latency but no ttft
    m = EngineMetrics()
    m.record_finish(_finished_request(0, 0.0, None, 2.0))
    s = m.summary(wall_s=1.0)
    assert s["latency_p50_s"] == 2.0
    assert math.isnan(s["ttft_p50_s"])
    assert math.isnan(s["itl_max_p95_s"])   # single token: no gap


def test_metrics_single_sample_percentiles_degenerate():
    from repro.serving import EngineMetrics
    m = EngineMetrics()
    m.record_finish(_finished_request(0, 1.0, 2.5, 4.0, max_itl=0.25))
    s = m.summary(wall_s=1.0)
    assert s["latency_p50_s"] == s["latency_p95_s"] == 3.0
    assert s["ttft_p50_s"] == s["ttft_p95_s"] == 1.5
    assert s["itl_max_p50_s"] == s["itl_max_p95_s"] == 0.25


def test_request_max_itl_tracks_worst_gap():
    r = Request(rid=0, model="a", prompt=(1,), max_new_tokens=4,
                arrival_t=0.0)
    for t in (1.0, 2.0, 5.5, 6.0):
        r.note_token(t)
    assert r.max_itl == pytest.approx(3.5)
    assert r.last_token_t == 6.0


# -------------------------------------- residency property tests (hypothesis)
def test_residency_victim_selection_invariants():
    """Any ensure() sequence preserves the §V-C arena invariants: a pinned
    (still-decoding) tenant never loses a resident layer, wire bytes never
    exceed raw bytes, and the slot<->layer maps stay mutually consistent."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ops_st = st.lists(st.tuples(st.sampled_from(["a", "b"]), st.booleans()),
                      min_size=1, max_size=10)

    @settings(max_examples=15, deadline=None)
    @given(ops=ops_st, spare=st.integers(min_value=0, max_value=2))
    def prop(ops, spare):
        res = WeightResidencyManager(
            {"a": (PARAMS_A, CFG), "b": (PARAMS_B, CFG)},
            CFG.n_layers + spare, reuse=True)
        for step, (model, pin_other) in enumerate(ops):
            other = "b" if model == "a" else "a"
            pinned = {model, other} if pin_other else {model}
            other_was_resident = res.is_resident(other)
            try:
                res.ensure(model, step, pinned=pinned)
            except RuntimeError:
                # infeasible only when the pinned pair exceeds the arena —
                # and the failed call must not have touched residency
                assert pin_other and not res.fits({"a", "b"})
                assert res.is_resident(other) == other_was_resident
                continue
            assert res.is_resident(model)
            # never evicts a layer still needed by the pinned decode tenant
            if pin_other and other_was_resident:
                assert res.is_resident(other)
            # slot <-> layer maps agree, one slot per layer
            for layer, slot in res.resident.items():
                assert res.slots[slot] == layer
            occupants = [l for l in res.slots if l is not None]
            assert len(occupants) == len(set(occupants))
            assert len(occupants) == len(res.resident)
            # stats invariants: the delta stream never ships more than raw,
            # skip fractions stay within [0, 1] per install
            assert 0 <= res.stats.wire_bytes <= res.stats.raw_bytes
            assert 0.0 <= res.stats.skips <= res.stats.installs
            assert res.stats.cold_installs <= res.stats.installs
            assert 0.0 <= res.stats.savings <= 1.0

    prop()


def test_residency_reuse_off_ships_raw():
    """With reuse disabled every install ships the full code stream: wire
    bytes == raw bytes and no cell is ever skipped."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(ops=st.lists(st.sampled_from(["a", "b"]), min_size=1,
                        max_size=10))
    def prop(ops):
        res = WeightResidencyManager(
            {"a": (PARAMS_A, CFG), "b": (PARAMS_B, CFG)},
            CFG.n_layers + 1, reuse=False)
        for step, model in enumerate(ops):
            res.ensure(model, step)
        assert res.stats.wire_bytes == res.stats.raw_bytes
        assert res.stats.skips == 0.0

    prop()


def test_engine_sampled_requests_are_reproducible():
    """Same seed → same continuation, across engine instances; greedy
    requests in the same batch stay oracle-exact."""
    outs = []
    for _ in range(2):
        eng = make_engine()
        sampled = eng.submit("a", [5, 6, 7, 8], max_new_tokens=6,
                             temperature=0.9, top_k=8, seed=42)
        greedy = eng.submit("a", [5, 6, 7, 8], max_new_tokens=6)
        eng.run()
        assert greedy.generated == sequential_tokens(
            PARAMS_A, CFG, list(greedy.prompt), 6)
        outs.append(list(sampled.generated))
    assert outs[0] == outs[1]
