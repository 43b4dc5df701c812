"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
shape/dtype sweeps as required for every kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.crossbar_mvm.ops import crossbar_mvm
from repro.kernels.crossbar_mvm.ref import crossbar_mvm_ref
from repro.kernels.delta_apply.ops import apply_delta
from repro.kernels.delta_apply.ref import delta_apply_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.pulse_count.ops import pulse_count
from repro.kernels.pulse_count.ref import pulse_count_ref


@pytest.mark.parametrize("n", [17, 4096, 70_001])
def test_delta_apply_sweep(n):
    rng = np.random.default_rng(n)
    old = jnp.asarray(rng.integers(0, 256, n, dtype=np.uint8))
    new = jnp.asarray(rng.integers(0, 256, n, dtype=np.uint8))
    delta = ((new.astype(jnp.int32) - old.astype(jnp.int32)) % 256).astype(jnp.uint8)
    out = apply_delta(old, delta)
    assert (out == delta_apply_ref(old, delta)).all()
    assert (out == new).all()


@pytest.mark.parametrize("n", [100, 33_000])
def test_pulse_count_sweep(n):
    rng = np.random.default_rng(n)
    old = jnp.asarray(rng.integers(0, 256, n, dtype=np.uint8))
    new = jnp.asarray(rng.integers(0, 256, n, dtype=np.uint8))
    p, s = pulse_count(old, new)
    pr, sr = pulse_count_ref(old, new)
    assert int(p) == int(pr) and int(s) == int(sr)


@pytest.mark.parametrize("m,k,n", [(8, 64, 8), (70, 300, 90), (128, 128, 128),
                                   (200, 1000, 64)])
def test_crossbar_mvm_sweep(m, k, n):
    rng = np.random.default_rng(m * k + n)
    x = jnp.asarray(rng.integers(0, 256, (m, k), dtype=np.uint8))
    w = jnp.asarray(rng.integers(0, 256, (k, n), dtype=np.uint8))
    zx = jnp.float32(rng.uniform(0, 255))
    zw = jnp.float32(rng.uniform(0, 255))
    sc = jnp.float32(10 ** rng.uniform(-5, -2))
    a = crossbar_mvm(x, w, zx, zw, sc)
    b = crossbar_mvm_ref(x, w, zx, zw, sc)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d,causal", [(128, 32, True), (200, 64, False),
                                        (256, 16, True)])
def test_flash_attention_kernel_sweep(s, d, causal, dtype):
    key = jax.random.PRNGKey(s + d)
    B, H = 2, 3
    q = jax.random.normal(key, (B, s, H, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, s, H, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, s, H, d), dtype)
    o = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, s, d)
    r = flash_attention_ref(qt, kt, vt, causal=causal)
    r = r.reshape(B, H, s, d).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,d,pool,page,t",
                         [(3, 4, 2, 32, 9, 4, 6),    # GQA, odd pool
                          (2, 6, 6, 16, 5, 8, 4),    # MHA
                          (1, 8, 2, 64, 12, 16, 8)])  # single row, big page
def test_paged_attention_kernel_sweep(b, h, hkv, d, pool, page, t, dtype):
    """Decode through scattered page tables must match the gather oracle,
    including rows whose position sits mid-page (masked tail)."""
    rng = np.random.default_rng(b * pool + page)
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((pool, page, hkv * d)), dtype)
    vp = jnp.asarray(rng.standard_normal((pool, page, hkv * d)), dtype)
    tables = jnp.asarray(
        np.stack([rng.choice(pool, t, replace=False) for _ in range(b)]),
        jnp.int32)
    pos = jnp.asarray(rng.integers(0, t * page, b), jnp.int32)
    o = paged_attention(q, kp[None], vp[None], tables, pos, 0,
                        interpret=True)
    r = paged_attention_ref(q, kp, vp, tables, pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_paged_attention_masks_stale_pages():
    """Pages past a row's position may hold arbitrary stale garbage (the
    freed-page occupancy discipline) without perturbing the output."""
    rng = np.random.default_rng(7)
    b, h, hkv, d, pool, page, t = 2, 4, 2, 16, 10, 4, 5
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kp = np.asarray(rng.standard_normal((pool, page, hkv * d)), np.float32)
    vp = np.asarray(rng.standard_normal((pool, page, hkv * d)), np.float32)
    # disjoint tables: a page stale for one row must not be live in another
    tables = rng.permutation(pool).reshape(b, t)
    pos = np.asarray([5, 9])
    base = paged_attention(q, jnp.asarray(kp)[None], jnp.asarray(vp)[None],
                           jnp.asarray(tables, np.int32),
                           jnp.asarray(pos, np.int32), 0, interpret=True)
    for row in range(b):
        for blk in range(pos[row] // page + 1, t):
            kp[tables[row, blk]] = 1e4 * rng.standard_normal((page, hkv * d))
            vp[tables[row, blk]] = 1e4
    out = paged_attention(q, jnp.asarray(kp)[None], jnp.asarray(vp)[None],
                          jnp.asarray(tables, np.int32),
                          jnp.asarray(pos, np.int32), 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


@pytest.mark.parametrize("layer", ["first", "middle", "last"])
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa"])
def test_paged_attention_reads_its_layer_of_stacked_pools(h, hkv, layer):
    """Stacked (L, P, page, Hkv*D) pools read at a layer index match the
    oracle on that layer's own pool; other layers hold other values."""
    L, b, d, pool, page, t = 5, 3, 16, 9, 8, 4
    li = {"first": 0, "middle": L // 2, "last": L - 1}[layer]
    rng = np.random.default_rng(11 + h + li)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((L, pool, page, hkv * d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, pool, page, hkv * d)),
                     jnp.float32)
    tables = jnp.asarray(
        np.stack([rng.choice(pool, t, replace=False) for _ in range(b)]),
        jnp.int32)
    pos = jnp.asarray(rng.integers(0, t * page, b), jnp.int32)
    o = paged_attention(q, kp, vp, tables, pos, jnp.int32(li),
                        interpret=True)
    r = paged_attention_ref(q, kp[li], vp[li], tables, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=3e-4,
                               atol=3e-4)
    other = paged_attention_ref(q, kp[(li + 1) % L], vp[(li + 1) % L],
                                tables, pos)
    assert not np.allclose(np.asarray(o), np.asarray(other), atol=1e-2)
