"""DeepSeek-V2 on the serving path, at a small size on the CPU, against the
benchmark's plain float32 reference (`bench/references/deepseek_v2.py`)
on seeded random weights: latent attention decoded from paged latents
(the XLA gather and the Pallas `mla_decode` kernel, interpreted), an
expert layer that holds a share of the experts, raw or renormalised
gates, YaRN rotary scaling, and the family's yardstick."""
import copy
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import families  # noqa: E402
from bench.references import deepseek_v2 as ref  # noqa: E402
from bench.weights import engine_params, make_weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels.paged_attention.mla_kernel import mla_decode_pallas  # noqa: E402
from repro.kernels.paged_attention.ops import mla_decode  # noqa: E402
from repro.kernels.paged_attention.ref import mla_decode_ref  # noqa: E402
from repro.nn.attention import mla_softmax_scale  # noqa: E402
from repro.nn.config import ModelConfig  # noqa: E402
from repro.nn.layers import mlp, rope_angles, yarn_mscale  # noqa: E402
from repro.nn.moe import _route, init_moe, moe_local, moe_reference  # noqa: E402
from repro.serving import EngineModel, SchedulerConfig, ServingEngine  # noqa: E402

# a DeepSeek-V2 configuration at a small size: one dense layer and four
# expert layers (the engine's [dense, scan·4] segments), 8 of 16 routed
# experts held, raw top-4 gates, YaRN over 16 original positions
TINY = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "moe_intermediate_size": 48, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 8, "router_outputs": 16,
    "n_shared_experts": 1, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 5, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 16,
    "vocab_size": 500,
}
CONF = {"reference": "deepseek_v2", "config": TINY}
SEED = 2**33 + 5

# Engine (bf16 weights and activations) against the float32 reference, by
# logits at every decoded position.  bf16 rounding through five layers
# reads a mean gap of 0.010-0.014 and a widest of 0.07-0.17 at this size
# over three seeds (the widest where a near-tied expert choice flips); a
# swapped W_uk/W_uv reads a mean of 1.1, renormalised gates 0.09-0.11
# (widest 0.49-0.91), no YaRN 0.19-0.26
# (`test_engine_reference_comparison_sees_each_mutation`).  The limits
# sit between, about 2x above the program and 3x below the nearest
# mutation on the mean.
LOGIT_MAX, LOGIT_MEAN = 0.35, 0.03


@pytest.fixture(scope="module")
def tiny():
    cfg = families.of(CONF).model_config("ds-tiny", TINY)
    w = make_weights(SEED, CONF)
    return cfg, w, engine_params(w, CONF, cfg)


def _serve(cfg, params, backend, prompts, n_new):
    """Serve `prompts` greedily through the paged, chunked engine; returns
    the requests and every decoded row's (request id, position, logits),
    read from the split decode step."""
    eng = ServingEngine(
        [EngineModel("m", params, cfg, kv_slots=2, kv_layout="paged",
                     page_size=8, n_pages=24, kernel_backend=backend)],
        sched=SchedulerConfig(max_prefill_per_step=2,
                              prefill_token_budget=8),
        prefill_chunk=8, bucket_min=8, fuse_sampling=False)
    arena, step = eng.arenas["m"], eng._decode["m"]
    seen = []

    def recording(p, tokens, caches, pos, tables):
        logits, caches = step(p, tokens, caches, pos, tables)
        owners = list(arena.owner)
        for row, rid in enumerate(owners):
            if rid is not None:
                seen.append((rid, int(pos[row]), np.asarray(logits[row])))
        return logits, caches

    eng._decode["m"] = recording
    reqs = [eng.submit("m", p, max_new_tokens=n_new) for p in prompts]
    eng.run()
    return reqs, seen


def _logit_gaps(reqs, seen, w, hf):
    """|engine − reference| logits at every decoded position (the rows
    still in prefill, whose output the engine discards, left out)."""
    by_rid = {r.rid: r for r in reqs}
    refs = {}
    for r in reqs:
        toks = list(r.prompt) + list(r.generated[:-1])
        refs[r.rid] = np.asarray(ref.logits(w, hf, toks,
                                            np.arange(len(toks))))
    out = []
    for rid, pos, row in seen:
        r = by_rid[rid]
        if pos < len(r.prompt) or pos >= len(r.prompt) + len(r.generated):
            continue
        out.append(np.abs(row[:hf["vocab_size"]] - refs[rid][pos]))
    return np.stack(out)


PROMPTS = [[(7 * i + 3) % 499 + 1 for i in range(20)],
           [(11 * i + 5) % 499 + 1 for i in range(13)]]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_decode_matches_reference_logits(tiny, backend):
    """Prefill in 8-token chunks, then decode through the paged latent
    cache to position 31, past YaRN's 16 original positions; every
    decoded row's logits against the reference's full forward pass."""
    cfg, w, params = tiny
    reqs, seen = _serve(cfg, params, backend, PROMPTS, 12)
    assert all(len(r.generated) == 12 for r in reqs)
    gap = _logit_gaps(reqs, seen, w, TINY)
    assert gap.shape[0] >= 20
    assert gap.max() < LOGIT_MAX and gap.mean() < LOGIT_MEAN, (
        gap.max(), gap.mean())


def _swap_uk_uv(w, hf):
    out = dict(w)
    for p in ("dense0/", "moe/"):
        out[p + "w_uk"], out[p + "w_uv"] = w[p + "w_uv"], w[p + "w_uk"]
    return out, hf


def _renormalised(w, hf):
    return w, dict(hf, norm_topk_prob=True)


def _no_yarn(w, hf):
    return w, {k: v for k, v in hf.items() if k != "rope_scaling"}


@pytest.mark.parametrize("mutate", [_swap_uk_uv, _renormalised, _no_yarn],
                         ids=["swap_w_uk_w_uv", "renormalised_gates",
                              "no_yarn"])
def test_engine_reference_comparison_sees_each_mutation(tiny, mutate):
    """The comparison is tight enough to see each equation the program
    must get right: a reference that differs in one of them misses the
    limits."""
    cfg, w, params = tiny
    reqs, seen = _serve(cfg, params, "xla", PROMPTS, 12)
    gap = _logit_gaps(reqs, seen, *mutate(w, TINY))
    assert gap.max() > LOGIT_MAX and gap.mean() > LOGIT_MEAN


# ------------------------------------------------------ the latent kernel
@pytest.mark.parametrize("layer,per_block", [(0, 8), (2, 8), (2, 1),
                                              (0, 4)])
def test_mla_decode_kernel_matches_oracle(layer, per_block):
    """Rows at page boundaries (positions 0, 7, 8, 15, 16, 39, 79 with
    8-token pages), an inactive row aimed at the scratch page, layers 0
    and 2 of stacked pools, blocks of 1, 4 and 8 pages (a 10-page table:
    the last block clamps to it); the rope-key pool 128 lanes wide, zero
    past the rope dim, as the engine keeps it."""
    B, H, R, Dr, Wr, page, P, L, T = 8, 4, 128, 64, 128, 8, 96, 3, 10
    ks = jax.random.split(jax.random.PRNGKey(layer), 4)
    q_lat = jax.random.normal(ks[0], (B, H, R))
    q_rope = jax.random.normal(ks[1], (B, H, Dr))
    c = jax.random.normal(ks[2], (L, P, page, R)).astype(jnp.bfloat16)
    r = jax.random.normal(ks[3], (L, P, page, Dr)).astype(jnp.bfloat16)
    r = jnp.pad(r, ((0, 0), (0, 0), (0, 0), (0, Wr - Dr)))
    rng = np.random.default_rng(layer)
    tables = rng.permutation(np.arange(1, P))[:B * T].reshape(B, T)
    tables[6] = 0                                   # inactive: scratch
    pos = np.array([0, 7, 8, 15, 16, 39, 0, 79], np.int32)
    tables, pos = jnp.asarray(tables, jnp.int32), jnp.asarray(pos)
    q_pad = jnp.pad(q_rope, ((0, 0), (0, 0), (0, Wr - Dr)))

    def kernel(c):
        return mla_decode_pallas(
            q_lat.astype(jnp.bfloat16), q_pad.astype(jnp.bfloat16), c, r,
            tables, pos, jnp.int32(layer), scale=0.1, interpret=True,
            pages_per_block=per_block)

    got = kernel(c)
    want = mla_decode_ref(q_lat.astype(jnp.bfloat16),
                          q_rope.astype(jnp.bfloat16), c[layer],
                          r[layer, ..., :Dr], tables, pos, 0.1)
    assert got.shape == (B, H, R) and got.dtype == jnp.float32
    # the kernel weighs latents by bf16 probabilities (the MXU's input);
    # the oracle by float32 ones
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)
    # a row sees only its own pages up to its position: page 2 onwards of
    # row 2 (position 8) changes nothing there
    c2 = c.at[layer, tables[2, 2:]].set(100.0)
    np.testing.assert_array_equal(np.asarray(kernel(c2)[2]),
                                  np.asarray(got[2]))


def test_mla_decode_refuses_mismatched_pools():
    q = jnp.zeros((1, 2, 8))
    pool = jnp.zeros((1, 3, 4, 8), jnp.bfloat16)
    rope = jnp.zeros((1, 3, 4, 128), jnp.bfloat16)
    tab = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="query widths"):
        mla_decode(q, jnp.zeros((1, 2, 64)), pool, rope, tab, pos, 0,
                   scale=1.0)
    with pytest.raises(ValueError, match="int32"):
        mla_decode(q, jnp.zeros((1, 2, 128)), pool, rope,
                   tab.astype(jnp.float32), pos, 0, scale=1.0)


# -------------------------------------------------------- the expert layer
def _moe_cfg(**kw):
    base = dict(name="share", family="moe", n_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=64, vocab=64, n_experts=64,
                moe_topk=6, d_ff_expert=16, n_shared_experts=1,
                first_dense_layers=1, norm_topk_prob=False)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("S", [1, 5], ids=["decode", "prefill"])
def test_expert_shares_add_up_to_the_whole_layer(S):
    """Eight chips, each holding 8 of a layer's 64 experts: each chip's
    share (router at full width, its experts as ids 0-7 by a relabelling
    of the router's outputs), the shared expert counted once, sums to the
    uncut layer; every token's 6 slots land on exactly one chip each."""
    whole = _moe_cfg()
    held = _moe_cfg(n_experts_held=8)
    p = init_moe(jax.random.PRNGKey(0), whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, S, 32), jnp.float32)
    want, _ = moe_reference(p, x, whole)
    shared = mlp(p["shared"], x, whole.act)
    total, slots = shared, 0
    for chip in range(8):
        mine = np.arange(8 * chip, 8 * chip + 8)
        order = np.concatenate([mine, np.setdiff1d(np.arange(64), mine)])
        share = dict(p, router=p["router"][:, order],
                     w_gate=p["w_gate"][mine], w_up=p["w_up"][mine],
                     w_down=p["w_down"][mine])
        y, _, routed = moe_local(share, x, held)
        total = total + (y - shared)
        slots = slots + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(slots) == 6)


@pytest.mark.parametrize("norm", [True, False], ids=["renormalised", "raw"])
@pytest.mark.parametrize("S", [1, 5], ids=["decode", "prefill"])
def test_held_expert_layer_matches_its_reference(norm, S):
    cfg = _moe_cfg(n_experts_held=8, norm_topk_prob=norm,
                   routed_scaling_factor=1.0 if norm else 1.25)
    p = init_moe(jax.random.PRNGKey(2), cfg)
    assert p["router"].shape == (32, 64) and p["w_up"].shape == (8, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, S, 32), jnp.float32)
    y, _, routed = moe_local(p, x, cfg)
    want, _ = moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    _, _, idx = _route(x.reshape(-1, 32), p["router"], 6)
    assert np.array_equal(np.asarray(routed).reshape(-1),
                          np.asarray((idx < 8).sum(-1)))


def test_router_gates_raw_or_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    probs, raw, idx = _route(x, r, 6, norm_topk=False, scaling=1.5)
    _, normed, idx2 = _route(x, r, 6, norm_topk=True)
    assert np.array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(
        np.asarray(raw),
        1.5 * np.take_along_axis(np.asarray(probs), np.asarray(idx), -1),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, rtol=1e-5)
    assert float(raw.sum(-1).max()) < 1.5


# ------------------------------------------------------------------ YaRN
def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """Hugging Face `DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache`'s
    inverse frequencies, in plain Python."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        inter = extra / factor
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(inter * ramp + extra * (1.0 - ramp))
    return low, high, out


def test_yarn_frequencies_and_temperature_are_hugging_faces():
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    low, high, inv = _hf_yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    assert (low, high) == (10, 23)
    cos, sin = rope_angles(jnp.asarray([1, 5000]), 64, cfg.rope_theta, y)
    got = np.arctan2(np.asarray(sin[0]), np.asarray(cos[0]))
    np.testing.assert_allclose(got, inv, rtol=1e-5)
    # at position 5000, past the 4096 original ones, the slow dims turn
    # 40 times slower than plain rotary would
    plain = rope_angles(jnp.asarray([5000]), 64, cfg.rope_theta)
    assert not np.allclose(np.asarray(cos[1]), np.asarray(plain[0][0]))
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m) == pytest.approx(
        1.2608038)
    assert mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    # mscale == mscale_all_dim: cos and sin are not rescaled
    np.testing.assert_allclose(np.asarray(cos[0] ** 2 + sin[0] ** 2), 1.0,
                               rtol=1e-6)
    # no scaling configured: the plain rotary frequencies, bit for bit
    plain_cfg = dataclasses.replace(cfg, rope_scaling=None)
    a = rope_angles(jnp.arange(9), 64, plain_cfg.rope_theta)
    b = rope_angles(jnp.arange(9), 64, 10000.0, None)
    assert all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(a, b))
    assert mla_softmax_scale(plain_cfg) == 1 / math.sqrt(192)


def test_published_config_routes_raw_gates():
    cfg = get_config("deepseek-v2-lite-16b")
    assert not cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    assert cfg.experts_held == cfg.n_experts == 64


# ------------------------------------------------------------ the yardstick
def test_family_yardstick_counts_are_pinned():
    """The deepseek-v2-lite-e8 yardstick (`bench/families/deepseek_v2.py`),
    recorded when it was written: 3.11 B parameters held; a decoded
    token's flops with the held experts at 0.75 slots a token; a 512-token
    chunk at 512; one layer's latent decode call at positions 0, 63, 64
    and 1000 with 64-token pages; the six latent pool shapes."""
    import json
    conf = json.loads(open(os.path.join(
        os.path.dirname(__file__), "..", "bench", "configs",
        "deepseek-v2-lite-e8.json")).read())
    s = families.shape(conf)
    assert s.params == 3_110_989_312
    assert s.routed_share == 0.75
    assert s.decode_token_flops(0) == 2_541_901_824
    assert s.decode_token_flops(1000) == 3_481_933_824
    assert s.prefill_chunk_flops(512, 512) == 1_195_430_641_664
    assert s.paged_attention_call([0, 63, 64, 1000], 64) == (1_679_456,
                                                             44_564_480)
    assert s.pool_dims(3073, 64) == (
        (26, 3073, 64, 512), (26, 3073, 64, 128), (3073, 64, 512),
        (3073, 64, 128), (1, 3073, 64, 512), (1, 3073, 64, 128))


def test_configuration_file_states_its_keys_once():
    """The file holds the published keys at its top level, where a reader
    of the published config looks for them, and under `config`, which is
    what the harness hands the family: the two must be the same keys with
    the same values, the cut (`reduced`) and the router's width included."""
    import json
    conf = json.loads(open(os.path.join(
        os.path.dirname(__file__), "..", "bench", "configs",
        "deepseek-v2-lite-e8.json")).read())
    hf = conf["config"]
    assert {k: conf[k] for k in hf} == hf
    assert conf["reduced"] == ["n_routed_experts"]
    assert hf["n_routed_experts"] == 8 and hf["router_outputs"] == 64


def test_family_pools_are_the_programs(tiny):
    from repro.serving.paging import init_page_pool

    cfg, _, _ = tiny
    pools = jax.eval_shape(
        lambda: init_page_pool(cfg, 25, 8, platform="tpu"))
    got = {a.shape for a in jax.tree.leaves(pools)}
    assert got <= set(families.shape(CONF).pool_dims(25, 8))
    assert (4, 25, 8, 128) in got       # the rope-key pool in lane tiles
    # off the TPU the pool is the rope dim wide
    cpu = jax.eval_shape(lambda: init_page_pool(cfg, 25, 8, platform="cpu"))
    assert (4, 25, 8, 8) in {a.shape for a in jax.tree.leaves(cpu)}


def test_family_refuses_what_it_does_not_model():
    for k, v in (("q_lora_rank", 1536), ("topk_method", "group_limited"),
                 ("scoring_func", "sigmoid")):
        with pytest.raises(ValueError, match=k):
            families.of(CONF).model_config("x", dict(TINY, **{k: v}))
    bad = copy.deepcopy(TINY)
    bad["rope_scaling"]["type"] = "linear"
    with pytest.raises(ValueError, match="yarn"):
        families.of(CONF).model_config("x", bad)


def test_cell_runs_through_the_harness():
    """A whole benchmark run of the family at this size (`bench/run.py`'s
    cell, its chip check skipped): weights, engine with the interpreted
    latent kernel, warm-up, closed-loop window, the reference found by
    name.  The judgement holds but for the gap limits, which are set for
    the cell's sizes; the int8 and fp8 controls read wider gaps than the
    program."""
    from bench.tests.conftest import SMOKE_CONF, SMOKE_MIXES
    from bench.tests.test_run_plumbing import E2E, smoke

    conf = dict(copy.deepcopy(SMOKE_CONF), reference="deepseek_v2",
                config=TINY)
    res = smoke(conf, copy.deepcopy(SMOKE_MIXES["closed"]),
                controls=["int8", "fp8"])
    checks = res["checks"]
    assert checks["served_tokens_min"]["value"] >= \
        checks["served_tokens_min"]["limit"] > 0
    for k in ("bad_finished", "rejected", "compiles_in_window"):
        assert checks[k]["value"] == checks[k]["limit"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(E2E)
    for mode in ("int8", "fp8"):
        assert (res["controls"][mode]["checks"]["gap_mean"]["value"]
                > checks["gap_mean"]["value"])


def test_engine_counts_routed_slots_and_live_pages(tiny):
    """The fused decode step returns each row's routed-slot count beside
    its tokens; the engine's tracer counters sum them over the decoded
    rows.  With every expert held, each decoded row routes all of its
    top-k slots in each of the 4 expert layers here; live pages are each
    decoded row's pages up to its position."""
    from repro.serving import Tracer

    cfg, w, params = tiny
    whole = dataclasses.replace(cfg, n_experts_held=0, n_experts=8)
    conf = {"reference": "deepseek_v2",
            "config": dict(TINY, router_outputs=8)}
    p = engine_params(make_weights(SEED, conf), conf, whole)
    tracer = Tracer()
    eng = ServingEngine(
        [EngineModel("m", p, whole, kv_slots=2, kv_layout="paged",
                     page_size=8, n_pages=24, kernel_backend="xla")],
        sched=SchedulerConfig(max_prefill_per_step=2,
                              prefill_token_budget=8),
        prefill_chunk=8, bucket_min=8, tracer=tracer)
    for prompt in PROMPTS:
        eng.submit("m", prompt, max_new_tokens=6)
    eng.run()
    by_name = {}
    for ev in tracer.events:
        if ev.get("ph") == "C" and ev["name"] in ("moe_routed_here",
                                                  "kv_live_pages"):
            by_name.setdefault(ev["name"], []).append(ev["args"]["value"])
    rows = [ev["args"]["n_slots"] for ev in tracer.events
            if ev.get("name") == "decode" and "n_slots" in ev.get("args", {})]
    assert len(by_name["moe_routed_here"]) == len(rows) > 0
    assert by_name["moe_routed_here"] == [r * 4 * 4 for r in rows]
    # 2 rows, at most ceil(32 / 8) pages each
    assert all(r <= v <= 4 * r for v, r in zip(by_name["kv_live_pages"],
                                                rows))
