"""Kernel-backend equivalence: the Pallas paged-attention decode route
(`kernel_backend="pallas"`, interpret mode on CPU) must produce
token-for-token identical engine output to the XLA gather path, and the
fused on-device sampler must be bitwise-identical to the per-row host
sampler — across bf16 and int8 pools, with preemption and COW in the
schedule, and across temperature/top-k/seed grids including the
padded-vocab-tail edge.  The named CI step re-runs exactly this file."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention.ops import paged_attention
from repro.nn.model import init_params
from repro.nn.transformer import stack_plan
from repro.serving import EngineModel, ServingEngine, SchedulerConfig
from repro.serving.sampling import request_key, sample_token, sample_tokens

CFG = get_config("gemma-7b", smoke=True)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
PAGE = 4


# ------------------------------------------------------------ ops contract
def _ops_inputs(H=4, Hkv=2, D=8, P=6, T=3, B=2):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(1, P, PAGE, Hkv * D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(1, P, PAGE, Hkv * D)).astype(np.float32))
    tables = jnp.asarray(rng.integers(0, P, (B, T)), jnp.int32)
    pos = jnp.asarray([3, 5], jnp.int32)
    return q, kp, vp, tables, pos


def test_ops_rejects_non_divisible_heads():
    q, kp, vp, tables, pos = _ops_inputs(H=5, Hkv=2)
    with pytest.raises(ValueError, match="not divisible"):
        paged_attention(q, kp, vp, tables, pos, 0, interpret=True)


def test_ops_rejects_non_int32_tables():
    q, kp, vp, tables, pos = _ops_inputs()
    with pytest.raises(ValueError, match="int32"):
        paged_attention(q, kp, vp, tables.astype(jnp.float32), pos, 0,
                        interpret=True)


def test_ops_explicit_interpret_runs():
    q, kp, vp, tables, pos = _ops_inputs()
    out = paged_attention(q, kp, vp, tables, pos, 0, interpret=True)
    assert out.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(out)))


def test_engine_model_validates_kernel_backend():
    with pytest.raises(ValueError, match="kernel_backend"):
        EngineModel("a", PARAMS, CFG, kernel_backend="cuda")
    with pytest.raises(ValueError, match="paged"):
        EngineModel("a", PARAMS, CFG, kv_layout="slot",
                    kernel_backend="pallas")
    with pytest.raises(ValueError, match="kernel_backend"):
        ServingEngine([EngineModel("a", PARAMS, CFG)],
                      kernel_backend="cuda")


# ----------------------------------------------------- engine equivalence
def _run_engine(backend, fuse, *, int8=False):
    """A schedule that exercises sharing, COW, and preemption: a small
    pool with the prefix cache on, shared prompts (pages shared on
    admission, COWed on first decode write), and enough concurrent load
    that the pool runs dry mid-decode."""
    cfg = dc.replace(CFG, kv_cache_dtype="int8") if int8 else CFG
    eng = ServingEngine(
        [EngineModel("a", PARAMS, cfg, kv_slots=3, max_seq=24,
                     kv_layout="paged", page_size=PAGE, n_pages=10,
                     prefix_cache=True, kernel_backend=backend)],
        sched=SchedulerConfig(max_prefill_per_step=2),
        fuse_sampling=fuse)
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab, 10).tolist()   # 2.5 pages
    # r1 runs two steps, then an identical prompt arrives mid-decode:
    # r2 shares r1's live pages including the partial tail page and COWs
    # it on its first decode write
    reqs = [eng.submit("a", shared, max_new_tokens=8)]
    eng.step()
    eng.step()
    reqs += [
        eng.submit("a", shared, max_new_tokens=8),
        eng.submit("a", rng.integers(1, cfg.vocab, 12).tolist(),
                   max_new_tokens=12),
        eng.submit("a", shared[:4], max_new_tokens=6,
                   temperature=0.9, top_k=7, seed=11),
    ]
    eng.run()
    arena = eng.arenas["a"]
    stats = {
        "cow": arena.allocator.cow_copies,
        "preempt": sum(r.preemptions for r in reqs),
        "sync_max": max((rec.sample_syncs for rec in eng.metrics.steps
                         if rec.n_decoded), default=0),
    }
    return {r.rid: tuple(r.generated) for r in reqs}, stats


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_pallas_engine_tokens_match_xla(int8):
    base, base_stats = _run_engine("xla", False, int8=int8)
    assert base_stats["cow"] > 0          # the schedule exercises COW
    assert base_stats["preempt"] > 0      # ... and pool-exhaustion preemption
    for backend, fuse in (("xla", True), ("pallas", False),
                          ("pallas", True)):
        got, stats = _run_engine(backend, fuse, int8=int8)
        assert got == base, (backend, fuse)
        assert stats["cow"] == base_stats["cow"]
        assert stats["preempt"] == base_stats["preempt"]


@pytest.mark.parametrize("n_layers", [4, 2], ids=["scanned", "unscanned"])
def test_pallas_fused_decode_matches_xla_per_stack_plan(n_layers):
    """The fused paged decode gives the same tokens on both backends for a
    scanned stack (page pools stacked over its layers, carried whole
    through the layer loop) and an unscanned one (fewer layers than
    `stack_plan`'s min_group: each layer's pools pass as a stack of
    one)."""
    cfg = dc.replace(get_config("yi-34b", smoke=True), n_layers=n_layers)
    assert [s for *_, s in stack_plan(cfg)] == (
        [True] if n_layers >= 4 else [False] * n_layers)
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 11, 3)]

    def tokens(backend):
        eng = ServingEngine(
            [EngineModel("a", params, cfg, kv_slots=3, max_seq=32,
                         kv_layout="paged", page_size=PAGE, n_pages=24,
                         kernel_backend=backend)],
            sched=SchedulerConfig(max_prefill_per_step=2))
        reqs = [eng.submit("a", p, max_new_tokens=7) for p in prompts]
        eng.run()
        return [tuple(r.generated) for r in reqs]

    base = tokens("xla")
    assert all(len(t) == 7 for t in base)
    assert tokens("pallas") == base


def test_sample_syncs_at_most_one_per_step():
    """Fused or split, sampling costs at most one host sync per decoded
    step — never one per row (the PR 9 hot-path bug)."""
    for fuse in (True, False):
        _, stats = _run_engine("pallas", fuse)
        assert stats["sync_max"] == 1, fuse


# ------------------------------------------------------- sampler identity
def test_fused_sampler_matches_host_grid():
    """`sample_tokens` is row-for-row bitwise identical to per-row
    `sample_token` across temperature/top-k/seed, with the padded vocab
    tail poisoned to +1e9 (it must be masked, not sampled)."""
    vocab, pad, B = CFG.vocab, 64, 6
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(B, vocab + pad)).astype(np.float32))
    logits = logits.at[:, vocab:].set(1e9)
    for temp in (0.0, 0.7, 1.3):
        for tk in (0, 1, 5, vocab):
            for seed in (None, 7):
                keys, steps, ref = [], [], []
                for r in range(B):
                    key = request_key(seed, r)
                    keys.append(np.asarray(key, np.uint32))
                    steps.append(r * 3)
                    ref.append(sample_token(
                        logits[r], vocab, temperature=temp, top_k=tk,
                        key=key, step=r * 3))
                got = np.asarray(sample_tokens(
                    logits, vocab,
                    temperatures=jnp.full((B,), temp, jnp.float32),
                    top_ks=jnp.full((B,), tk, jnp.int32),
                    keys=jnp.asarray(np.stack(keys)),
                    steps=jnp.asarray(steps, dtype=jnp.int32)))
                assert list(got) == ref, (temp, tk, seed)
                assert all(t < vocab for t in ref)


def test_sample_tokens_mixed_rows_one_call():
    """One batched call handles a heterogeneous batch: greedy rows,
    sampled rows, and top-k rows in the same device call."""
    vocab = CFG.vocab
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(4, vocab)).astype(np.float32))
    temps = jnp.asarray([0.0, 0.8, 1.2, 0.0], jnp.float32)
    tks = jnp.asarray([0, 3, 0, 5], jnp.int32)
    keys = jnp.asarray(np.stack([
        np.asarray(request_key(None, r), np.uint32) for r in range(4)]))
    steps = jnp.asarray([0, 1, 2, 3], jnp.int32)
    got = np.asarray(sample_tokens(logits, vocab, temperatures=temps,
                                   top_ks=tks, keys=keys, steps=steps))
    for r, (t, k) in enumerate(zip([0.0, 0.8, 1.2, 0.0], [0, 3, 0, 5])):
        want = sample_token(logits[r], vocab, temperature=t, top_k=k,
                            key=request_key(None, r), step=int(steps[r]))
        assert got[r] == want, r
