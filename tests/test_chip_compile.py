"""Compile-only checks for one TPU v5e chip, with no chip attached: the TPU
compiler is installed, and it compiles for a described v5e:2x2 topology.
Interpret-mode tests cannot see what these catch — block shapes off the
(8, 128) tiling, DMA slices the chip refuses, kernels that cannot lower.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every xdist worker imports
this file."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.mla_kernel import mla_decode_pallas
from repro.launch.steps import cached_fused_paged_serve_step
from repro.nn.config import ModelConfig
from repro.nn.model import init_serving_params
from repro.serving.paging import init_page_pool

FULL_POOL_PAGES = 1025       # a deployment-sized pool of 16-token pages


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back without the chip
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("B,G,Hkv,D", [
    (8, 1, 36, 64),     # minicpm-2b: 36 heads of 64, MHA
    (8, 8, 8, 128),     # a GQA config: 8 KV heads of 128, 8 queries each
], ids=["minicpm-2b", "gqa-8x128"])
def test_paged_attention_compiles_for_v5e(one_chip, B, G, Hkv, D):
    L, page, T = 4, 16, 256
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((L, FULL_POOL_PAGES, page, Hkv * D), jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        paged_attention_pallas, interpret=False)).lower(
            s((B, G, Hkv, D), jnp.bfloat16), pool, pool,
            s((B, T), jnp.int32), s((B,), jnp.int32),
            s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mla_decode_compiles_for_v5e(one_chip):
    """The latent kernel at deepseek-v2-lite-e8's decode shapes: 64 rows,
    16 heads, 512-wide latents and 128-lane rope keys, a 26-layer stack of
    3,073 pages of 64 tokens (the rows' page tables prefetched into
    SMEM)."""
    B, H, R, Wr, L, P, page, T = 64, 16, 512, 128, 26, 3073, 64, 3072
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        mla_decode_pallas, scale=0.1, interpret=False)).lower(
            s((B, H, R), jnp.bfloat16), s((B, H, Wr), jnp.bfloat16),
            s((L, P, page, R), jnp.bfloat16),
            s((L, P, page, Wr), jnp.bfloat16), s((B, T), jnp.int32),
            s((B,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# one optimized-HLO instruction with an array result: name, dims, opcode
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]"
                    r"(?:\{[^}]*\})? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_decode_layer_loop_keeps_page_pools_in_place(one_chip):
    """The fused paged decode step of a scanned GQA stack, compiled for
    v5e, touches its deployment-sized page pools only through the
    kernel and the new token's in-place page scatter: no op outside the
    kernel copies, slices out or writes back a whole pool, stacked or
    one layer's."""
    cfg = ModelConfig(name="gqa-scan4", family="dense", n_layers=4,
                      d_model=1024, n_heads=16, n_kv_heads=8, head_dim=128,
                      d_ff=2048, vocab=1024)
    rows, page, T = 8, 16, 64

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), cfg)))
    pools = on_chip(jax.eval_shape(
        lambda: init_page_pool(cfg, FULL_POOL_PAGES, page)))
    arg = lambda dt, *shape: jax.ShapeDtypeStruct(shape or (rows,), dt,
                                                  sharding=one_chip)
    step = cached_fused_paged_serve_step.__wrapped__(cfg, "pallas", False)
    hlo = step.lower(params, arg(jnp.int32), pools, arg(jnp.int32),
                     arg(jnp.int32, rows, T), arg(jnp.float32),
                     arg(jnp.int32), arg(jnp.uint32, rows, 2),
                     arg(jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo

    layer = (FULL_POOL_PAGES, page, cfg.n_kv_heads * cfg.head_dim)
    shapes = {",".join(map(str, d)) for d in (layer, (4,) + layer)}
    passes = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in shapes and m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast"):
            name = _OP_NAME.search(line)
            passes.append((m.group(1), m.group(3), name and name.group(1)))
    # one scatter per pool (K and V), each in its own in-place fusion
    assert sorted(op for _, op, _ in passes) == [
        "fusion", "fusion", "scatter", "scatter"], passes
    assert all(name.endswith("/kv_pool/scatter") for *_, name in passes), \
        passes


def test_deepseek_decode_step_compiles_for_v5e(one_chip):
    """deepseek-v2-lite-e8's fused decode step at the cell's sizes (27
    layers at published widths, 8 experts held, 64 rows, 3,072 pages of
    64): it fits the chip beside its arguments, calls the latent kernel,
    and touches the latent pools only through the kernel and the new
    token's in-place page scatters."""
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import families

    conf = json.loads((root / "bench" / "configs" /
                       "deepseek-v2-lite-e8.json").read_text())
    cfg = families.of(conf).model_config("deepseek-v2-lite-e8",
                                         conf["config"])
    e = conf["engine"]
    rows, T = e["decode_rows"], e["kv_pages"]

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), cfg)))
    pools = on_chip(jax.eval_shape(
        lambda: init_page_pool(cfg, T + 1, e["page_size"],
                               platform="tpu")))
    arg = lambda dt, *shape: jax.ShapeDtypeStruct(shape or (rows,), dt,
                                                  sharding=one_chip)
    step = cached_fused_paged_serve_step.__wrapped__(cfg, "pallas", False)
    compiled = step.lower(params, arg(jnp.int32), pools, arg(jnp.int32),
                          arg(jnp.int32, rows, T), arg(jnp.float32),
                          arg(jnp.int32), arg(jnp.uint32, rows, 2),
                          arg(jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # arguments (weights 5.91 GiB, pools 6.33 GiB) plus temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    hlo = compiled.as_text()
    assert "mla_decode" in hlo
    pools_dims = {",".join(map(str, d)) for d in
                  families.shape(conf).pool_dims(T + 1, e["page_size"])}
    passes = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in pools_dims and m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast"):
            name = _OP_NAME.search(line)
            passes.append((m.group(1), m.group(3), name and name.group(1)))
    assert passes and all(name.endswith("/kv_pool/scatter")
                          for *_, name in passes), passes
