"""A second model family needs new files only.  A stub family, registered
at test time as `bench.families.mla_moe_stub` beside a stub reference, is
built on the program's own MLA + MoE smoke preset with its layers in two
segments (the first dense, the rest one scanned stack of MoE layers),
unlike the Llama family's one.  It runs through the harness's dispatch on
the CPU: the engine's `ModelConfig`, the canonical weights, the engine
tree and its check, a whole run of a cell, and the yardstick that the
device readers and the pool-shape rule read."""
import dataclasses
import math
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, scopes
from bench.flops import BF16, INT32
from bench.metrics import paged_attention_roofline, step_mfu
from bench.peaks import peaks_for
from bench.record import Run, TraceWindow
from bench.tests.test_run_plumbing import E2E, smoke
from bench.trace_reduce import Event, Trace
from bench.weights import engine_params, make_weights

STUB = "mla_moe_stub"


def _model_config(name, hf):
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("deepseek-v2-lite-16b", smoke=True), name=name,
        n_layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
        scan_layers=True)


def _engine_tree(hf):
    from repro.nn.model import init_serving_params

    cfg = _model_config("stub", hf)
    return jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), cfg))


def _name(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _leaves(hf):
    """Every leaf of the engine's tree under a flat name; norm scales are
    'norm', the embedding 'embed', float32 projections (the absorbed MLA
    up-projections, the router) 'matmul_f32'."""
    out = []
    for path, a in jax.tree_util.tree_leaves_with_path(_engine_tree(hf)):
        name = _name(path)
        kind = ("norm" if name.endswith("scale") else
                "embed" if name == "embedding/table" else
                "matmul_f32" if a.dtype == jnp.float32 else "matmul")
        out.append((name, a.shape, kind))
    return out


def _engine_params(w, model_cfg):
    hf = {"num_hidden_layers": model_cfg.n_layers,
          "vocab_size": model_cfg.vocab}
    tree = jax.tree.structure(_engine_tree(hf))
    return jax.tree.unflatten(tree, [w[n] for n, _, _ in _leaves(hf)])


@dataclasses.dataclass(frozen=True)
class _Shape:
    """A stub yardstick for latent attention: every parameter through one
    multiply-add a token, and attention over the latent and rope parts
    of each cached position."""
    n_layers: int
    n_heads: int
    latent: int
    rope: int
    params: int
    scanned: int

    @classmethod
    def from_config(cls, hf):
        cfg = _model_config("stub", hf)
        n = sum(math.prod(s) for _, s, _ in _leaves(hf))
        return cls(cfg.n_layers, cfg.n_heads, cfg.kv_lora_rank,
                   cfg.qk_rope_dim, n, cfg.n_layers - cfg.first_dense_layers)

    def decode_token_flops(self, pos):
        return 2 * self.params + 4 * self.n_layers * self.n_heads * (
            self.latent + self.rope) * (pos + 1)

    def prefill_chunk_flops(self, start, n):
        return sum(self.decode_token_flops(p) for p in range(start, start + n))

    def paged_attention_call(self, positions, page):
        width = self.latent + self.rope
        nbytes = flops = 0
        for p in positions:
            pages = p // page + 1
            nbytes += pages * page * width * BF16 + (pages + 1) * INT32
            flops += 4 * self.n_heads * width * pages * page
        return nbytes, flops

    def pool_dims(self, pages, page):
        one = [(pages, page, self.latent), (pages, page, self.rope)]
        return tuple([(self.scanned,) + d for d in one] + one)


def _reference_logits(w, hf, tokens, positions, mode=None):
    """The stub's reference: the program's own forward pass (a stand-in;
    a family's real reference imports nothing of the program)."""
    from repro.nn.model import forward

    cfg = _model_config("stub", hf)
    params = _engine_params(w, cfg)
    out = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0])(
        params, jnp.asarray(tokens)[None])
    return out[0, np.asarray(positions), :hf["vocab_size"]].astype(
        jnp.float32)


@pytest.fixture
def stub(monkeypatch, smoke_conf):
    """The stub family and its reference, registered by module name only:
    no file of the benchmark names them."""
    fam = types.ModuleType(f"bench.families.{STUB}")
    fam.model_config, fam.leaves = _model_config, _leaves
    fam.engine_params, fam.Shape = _engine_params, _Shape
    ref = types.ModuleType(f"bench.references.{STUB}")
    ref.logits = _reference_logits
    monkeypatch.setitem(sys.modules, fam.__name__, fam)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    conf = dict(smoke_conf, reference=STUB,
                config={"num_hidden_layers": 5, "vocab_size": 512})
    conf["engine"] = dict(conf["engine"], kernel_backend="xla")
    return conf


def test_stub_family_builds_through_the_dispatch(stub):
    assert families.of(stub).__name__ == f"bench.families.{STUB}"
    from repro.serving.paging import stack_plan

    cfg = families.of(stub).model_config("stub", stub["config"])
    # two segments, the second scanned: not the Llama family's one
    assert [scanned for _, _, scanned in stack_plan(cfg)] == [False, True]
    w = make_weights(5, stub)
    assert w["stack/segments/1/moe/router"].dtype == jnp.float32
    assert w["stack/segments/1/moe/w_up"].dtype == jnp.bfloat16
    params = engine_params(w, stub, cfg)
    assert len(params["stack"]["segments"]) == 2
    # the generic check still holds the family to the engine's tree
    fam = families.of(stub)
    good = fam.engine_params

    def lossy(w, model_cfg):
        tree = good(w, model_cfg)
        del tree["stack"]["segments"][1]["moe"]["router"]
        return tree

    fam.engine_params = lossy
    with pytest.raises(ValueError, match="tree changed"):
        engine_params(w, stub, cfg)
    fam.engine_params = good
    w["stack/segments/1/moe/router"] = w[
        "stack/segments/1/moe/router"].astype(jnp.bfloat16)
    with pytest.raises(ValueError, match="router"):
        engine_params(w, stub, cfg)


def test_stub_family_pools_are_the_programs(stub):
    from repro.serving.paging import init_page_pool

    cfg = families.of(stub).model_config("stub", stub["config"])
    e = stub["engine"]
    pools = jax.eval_shape(
        lambda: init_page_pool(cfg, e["kv_pages"] + 1, e["page_size"]))
    assert set(scopes.pool_dims(stub)) == {a.shape for a in
                                           jax.tree.leaves(pools)}


def test_stub_family_runs_a_cell(stub):
    """The whole of a run: weights, engine, warm-up, window, the
    reference found by name, every end-to-end reader."""
    from bench.tests.conftest import SMOKE_MIXES

    res = smoke(stub, dict(SMOKE_MIXES["closed"]))
    checks = res["checks"]
    # the stub's reference is the program's own bf16 forward pass, so the
    # gaps are rounding between two of its paths: the stub sets no limit
    # on them; the rest of the judgement holds as for any cell
    assert checks["served_tokens_min"]["value"] >= \
        checks["served_tokens_min"]["limit"] > 0
    for k in ("bad_finished", "rejected", "compiles_in_window"):
        assert checks[k]["value"] == checks[k]["limit"] == 0
    assert 0 <= checks["gap_mean"]["value"] <= checks["gap_max"]["value"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(E2E)
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_device_readers_read_the_stub_yardstick(stub):
    s = families.shape(stub)
    assert isinstance(s, _Shape)
    steps = [types.SimpleNamespace(decode_positions=[3, 40],
                                   chunks=[(0, 16)])] * 2
    kernel = Event("paged_attention.1", 0, 2_000_000, "paged_attention.1")
    peaks = peaks_for("TPU v5 lite")
    run = Run(conf=stub, peaks=peaks, window=(0.0, 1.0), tracked=[],
              steps=[], setup_s=0.0, compile_events=[],
              residency_build_s=0.0,
              trace=TraceWindow(Trace([kernel], [], 1), 0, 1e7, steps))
    assert run.shape == s
    flops = 2 * (s.decode_token_flops(3) + s.decode_token_flops(40)
                 + s.prefill_chunk_flops(0, 16))
    assert step_mfu.read(run) == pytest.approx(
        100 * flops / (1e-2 * peaks.bf16_flops))
    b, f = s.paged_attention_call([3, 40], stub["engine"]["page_size"])
    n = 2 * s.n_layers          # two steps, every layer
    least = max(n * b / peaks.hbm_bytes_s, n * f / peaks.bf16_flops)
    assert paged_attention_roofline.read(run) == pytest.approx(
        100 * least / 2e-3)
