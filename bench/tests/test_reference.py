"""The plain float32 reference against the engine at smoke size on the
CPU, and the control: the same reference in a precision below the bf16
the configurations state must fail the comparison the engine passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correctness, harness
from bench.flops import Shape
from bench.references import llama
from bench.weights import engine_params, make_weights

# the engine's logits are bf16 (about 3 significant digits on logits of
# magnitude up to ~4) after bf16 activations through every layer
LOGIT_TOL = 0.08


def _setup(conf, seed):
    hf = conf["config"]
    w = make_weights(seed, Shape.from_config(hf))
    cfg = harness.model_config("smoke", hf)
    return hf, w, cfg, engine_params(w, cfg)


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
