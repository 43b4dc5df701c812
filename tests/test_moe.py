"""MoE: reference path invariants + shard_map equivalence (multi-device via
subprocess with forced host devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn.config import ModelConfig
from repro.nn.moe import init_moe, moe_reference, _route, _aux_loss

CFG = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, n_experts=8, moe_topk=2,
                  d_ff_expert=16)


def test_reference_output_finite_and_gated():
    params = init_moe(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 32), jnp.float32)
    y, aux = moe_reference(params, x, CFG)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) >= 1.0 - 1e-3  # load-balance loss lower bound is ~1


def test_router_topk_normalized():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(1), (32, 8), jnp.float32)
    probs, vals, idx = _route(x, r, 2)
    np.testing.assert_allclose(np.asarray(vals.sum(-1)), 1.0, rtol=1e-5)
    assert int(idx.max()) < 8


def test_aux_loss_balanced_is_one():
    probs = jnp.full((64, 8), 1.0 / 8)
    idx = jnp.tile(jnp.arange(8), 8)[:, None]
    aux = _aux_loss(probs, idx, 8)
    assert float(aux) == pytest.approx(1.0, rel=1e-5)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.nn.config import ModelConfig
    from repro.nn.moe import init_moe, moe, moe_reference
    from repro.parallel.sharding import use_mesh

    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      n_experts=8, moe_topk=2, d_ff_expert=16,
                      capacity_factor=8.0)  # high cf → no drops → exact match
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)
    y_ref, aux_ref = moe_reference(params, x, cfg)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    with use_mesh(mesh):
        y, aux, _ = jax.jit(lambda p, v: moe(p, v, cfg))(params, x)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err < 2e-3, f"a2a path mismatch {err}"
    with use_mesh(mesh):
        y2, _, _ = jax.jit(lambda p, v: moe(p, v, cfg, decode=True))(
            params, x[:, :1])
    y2_ref, _ = moe_reference(params, x[:, :1], cfg)
    err2 = float(jnp.max(jnp.abs(y2 - y2_ref)))
    assert err2 < 2e-3, f"replicated path mismatch {err2}"
    print("MOE_OK", err, err2)
""")


@pytest.mark.slow
def test_shard_map_paths_match_reference_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=600)
    assert "MOE_OK" in r.stdout, r.stdout + r.stderr
