"""The whole of a run at smoke size on the CPU: `bench/run.py`'s cell
plumbing with its look for a chip skipped (`require_tpu=False`), and the
faults of the timed path that must turn `correct` false.  Also that the
command itself refuses to measure anything but a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import tenants

ROOT = Path(__file__).resolve().parents[2]
E2E = {"setup_s": "s", "ttft_p90_s": "s", "itl_p95_s": "s",
       "output_tok_s": "tokens/s"}
PER_LAYER = {"compile_s": "s", "residency_store_s": "s",
             "queue_wait_p90_s": "s", "preemptions": "requests",
             "decode_step_ms": "ms",
             "prefill_ms_per_ktok": "ms/ktok",
             "paged_attention_roofline": "%", "device_idle_share": "%",
             "step_mfu": "%"}
DEVICE_METRICS = {"paged_attention_roofline", "device_idle_share",
                  "step_mfu"}


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()


def smoke(conf, mix, **kw):
    cell = {"name": "smoke", "config": "smoke", "traffic": "smoke",
            "chips": 1}
    kw.setdefault("units", E2E)
    kw.setdefault("seed", 2**33 + 17)
    return RUN.run_cell(cell, conf, mix, kw.pop("units"), seconds=2.0,
                        require_tpu=False, **kw)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi-34b-s6.batch",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "measures a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_smoke_run_is_correct(smoke_conf, smoke_mix):
    res = smoke(smoke_conf, smoke_mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(E2E)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_traced_smoke_run_reports_no_device_metric_off_chip(smoke_conf,
                                                           smoke_mix):
    res = smoke(smoke_conf, smoke_mix, units=PER_LAYER, trace=True)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    assert got == set(PER_LAYER) - DEVICE_METRICS
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def _unchanged_state(f, vocab):
    def g(params, tokens, caches, *rest):
        kept = jax.tree.map(jnp.copy, caches)   # the step donates caches
        toks, _ = f(params, tokens, caches, *rest)
        return toks, kept
    return g


def _token_altered(f, vocab):
    def g(*args):
        toks, caches = f(*args)
        return (toks + 1) % vocab, caches
    return g


def _half_batch(f, vocab):
    def g(params, tokens, *rest):
        toks, caches = f(params, tokens, *rest)
        half = jnp.arange(toks.shape[0]) < toks.shape[0] // 2
        return jnp.where(half, toks, tokens), caches
    return g


@pytest.mark.parametrize("seed", [2**33 + 17, 11])
def test_control_in_the_programs_place_is_not_correct(smoke_conf, seed):
    """The fp8 control is judged by the run's own checks, and fails them."""
    from bench.tests.conftest import SMOKE_MIXES

    res = smoke(smoke_conf, dict(SMOKE_MIXES["closed"]), seed=seed,
                controls=["fp8"])
    assert res["correct"], res["checks"]
    ctl = res["controls"]["fp8"]
    assert not ctl["correct"]
    assert any(ctl["checks"][k]["value"] > ctl["checks"][k]["limit"]
               for k in ("gap_max", "gap_mean"))
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged_state, _token_altered,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(smoke_conf, fault):
    from bench.tests.conftest import SMOKE_MIXES

    vocab = smoke_conf["config"]["vocab_size"]

    def after_build(eng):
        name = tenants(smoke_conf)[0]
        eng._decode_fused[name] = fault(eng._decode_fused[name], vocab)

    res = smoke(smoke_conf, dict(SMOKE_MIXES["closed"]),
                after_build=after_build)
    assert not res["correct"]
    assert res["checks"]["gap_max"]["value"] > \
        res["checks"]["gap_max"]["limit"]
    json.dumps(res)
