"""The plain float32 reference against the engine at smoke size on the
CPU, and the control: the same reference in a precision below the bf16
the configurations state must fail the comparison the engine passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correctness, families, harness
from bench.references import llama
from bench.weights import engine_params, make_weights

# the engine's logits are bf16 (about 3 significant digits on logits of
# magnitude up to ~4) after bf16 activations through every layer
LOGIT_TOL = 0.08


def _setup(conf, seed):
    hf = conf["config"]
    w = make_weights(seed, conf)
    cfg = families.of(conf).model_config("smoke", hf)
    return hf, w, cfg, engine_params(w, conf, cfg)


# what make_weights gave the smoke configuration (seed 7, on the CPU)
# before the leaves moved into bench/families/llama.py: (name, shape,
# dtype, float64 sum) in the order whose index seeds each leaf, untied and
# tied; and which canonical leaf each engine leaf holds
PINNED_LEAVES = {
    False: [
        ("embed", (512, 64), "bfloat16", 3.088935751467943),
        ("head", (64, 512), "bfloat16", 43.54751881957054),
        ("attn_norm", (4, 64), "float32", 257.5641425251961),
        ("wq", (4, 64, 64), "bfloat16", -5.537745468318462),
        ("wk", (4, 64, 32), "bfloat16", 0.9543204307556152),
        ("wv", (4, 64, 32), "bfloat16", 10.874217808246613),
        ("wo", (4, 64, 64), "bfloat16", 6.09749636054039),
        ("mlp_norm", (4, 64), "float32", 256.28427135944366),
        ("w_gate", (4, 64, 128), "bfloat16", -4.945184230804443),
        ("w_up", (4, 64, 128), "bfloat16", -3.404157429933548),
        ("w_down", (4, 128, 64), "bfloat16", 2.9317502304911613),
        ("final_norm", (64,), "float32", 64.2809807062149),
    ],
    True: [
        ("embed", (512, 64), "bfloat16", 3.088935751467943),
        ("attn_norm", (4, 64), "float32", 255.40507769584656),
        ("wq", (4, 64, 64), "bfloat16", 28.842231638729572),
        ("wk", (4, 64, 32), "bfloat16", -9.675469748675823),
        ("wv", (4, 64, 32), "bfloat16", 0.9543204307556152),
        ("wo", (4, 64, 64), "bfloat16", 8.499573528766632),
        ("mlp_norm", (4, 64), "float32", 256.6009895205498),
        ("w_gate", (4, 64, 128), "bfloat16", -6.512359023094177),
        ("w_up", (4, 64, 128), "bfloat16", -4.945184230804443),
        ("w_down", (4, 128, 64), "bfloat16", -2.4076432287693024),
        ("final_norm", (64,), "float32", 63.34583109617233),
    ],
}
SEG = "['stack']['segments'][0]"
PINNED_TREE = {
    "['embedding']['table']": "embed",
    "['final_norm']['scale']": "final_norm",
    f"{SEG}['attn']['w_k']": "wk", f"{SEG}['attn']['w_o']": "wo",
    f"{SEG}['attn']['w_q']": "wq", f"{SEG}['attn']['w_v']": "wv",
    f"{SEG}['mlp']['w_down']": "w_down", f"{SEG}['mlp']['w_gate']": "w_gate",
    f"{SEG}['mlp']['w_up']": "w_up", f"{SEG}['norm1']['scale']": "attn_norm",
    f"{SEG}['norm2']['scale']": "mlp_norm",
}


@pytest.mark.parametrize("tied", [False, True])
def test_canonical_weights_and_engine_tree_are_pinned(smoke_conf, tied):
    smoke_conf["config"]["tie_word_embeddings"] = tied
    want = PINNED_LEAVES[tied]
    assert [(n, s) for n, s, _ in
            families.of(smoke_conf).leaves(smoke_conf["config"])] == \
        [(n, s) for n, s, _, _ in want]
    _, w, _, params = _setup(smoke_conf, 7)
    got = [(k, tuple(a.shape), str(a.dtype),
            float(np.asarray(a, np.float64).sum())) for k, a in w.items()]
    assert sorted(got) == sorted(want)
    names = {id(a): k for k, a in w.items()}
    tree = {jax.tree_util.keystr(path): names[id(a)] for path, a in
            jax.tree_util.tree_leaves_with_path(params)}
    want_tree = dict(PINNED_TREE)
    if not tied:
        want_tree["['embedding']['head']"] = "head"
    assert tree == want_tree


@pytest.mark.parametrize("seed", [1, 2**32 + 3])
def test_reference_matches_engine_logits(smoke_conf, seed):
    from repro.nn.model import forward

    hf, w, cfg, params = _setup(smoke_conf, seed)
    tokens = np.random.default_rng(seed).integers(1, hf["vocab_size"], 90)
    got, _, _ = forward(params, {"tokens": jnp.asarray(tokens)[None]}, cfg)
    got = np.asarray(got[0, :, :hf["vocab_size"]], np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(llama.logits(w, hf, tokens, np.arange(90)))
    err = np.abs(got - want).max()
    assert err < LOGIT_TOL, err
    for mode in ("int8", "fp8"):
        with jax.default_matmul_precision("highest"):
            ctl = np.asarray(llama.logits(w, hf, tokens, np.arange(90),
                                          mode=mode))
        assert np.abs(ctl - want).max() > err


def _served(conf, seed, n_requests=6):
    """Greedy requests through the engine's timed path (chunked prefill,
    paged decode kernel, fused sampler)."""
    hf, w, cfg, params = _setup(conf, seed)
    eng = harness.build_engine(conf, cfg, params)
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        prompt = rng.integers(1, hf["vocab_size"],
                              int(rng.integers(8, 70))).tolist()
        reqs.append((prompt, eng.submit(harness.tenants(conf)[0], prompt,
                                        max_new_tokens=12)))
    eng.run()
    return w, [correctness.Seq(p, list(r.generated)) for p, r in reqs]


@pytest.mark.parametrize("seed", [5, 6, 2**33 + 1])
def test_control_fails_where_the_engine_passes(smoke_conf, seed):
    w, seqs = _served(smoke_conf, seed)
    limit = smoke_conf["correctness"]["limits"]["gap_max"]
    engine = correctness.gaps(w, smoke_conf, seqs)
    assert engine.size == 6 * 12
    assert engine.max() <= limit
    control = correctness.gaps(w, smoke_conf, seqs, mode="fp8")
    assert control.max() > limit
