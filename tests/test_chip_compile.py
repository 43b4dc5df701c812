"""Compile-only checks for one TPU v5e chip, with no chip attached: the TPU
compiler is installed, and it compiles for a described v5e:2x2 topology.
Interpret-mode tests cannot see what these catch — block shapes off the
(8, 128) tiling, DMA slices the chip refuses, kernels that cannot lower.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every xdist worker imports
this file."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_pallas

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
    page, T = 16, 256
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((FULL_POOL_PAGES, page, Hkv * D), jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        paged_attention_pallas, interpret=False)).lower(
            s((B, G, Hkv, D), jnp.bfloat16), pool, pool,
            s((B, T), jnp.int32), s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
