"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ------------------------------------------------------------------- gram
@pytest.mark.parametrize("n,d", [(8, 8), (64, 48), (130, 256), (257, 100),
                                 (512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_matches_ref(n, d, dtype):
    rng = np.random.default_rng(n * 1000 + d)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=dtype)
    got = ops.gram(x, block_d=128, block_n=128, interpret=True)
    want = ref.gram_ref(x)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@given(st.integers(1, 80), st.integers(1, 70), st.integers(0, 5))
@settings(max_examples=12, deadline=None)
def test_gram_property_random_shapes(n, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    got = ops.gram(x, block_d=32, block_n=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gram_ref(x)),
                               rtol=1e-5, atol=1e-4)


# -------------------------------------------------------------- gram_tall
@pytest.mark.parametrize("lead,d,k,tiles", [
    *[(lead, d, k, None)
      for lead in [(), (50,), (8, 50)]
      for d, k in [(300, 8), (300, 3), (40, 6), (7, 1), (1024, 16)]],
    ((50,), 300, 8, (16, 300)),     # ragged last block of factors
    ((3, 7), 40, 6, (5, 40)),
    ((4,), 300, 8, (3, 128)),       # d panels, the last one zero padded
], ids=str)
def test_gram_tall_matches_einsum(lead, d, k, tiles):
    from repro.kernels.gram import _gram_tall, gram_tall
    rng = np.random.default_rng(d * 100 + k)
    x = jnp.asarray(rng.standard_normal((*lead, d, k)), jnp.float32)
    if tiles is None:
        got = gram_tall(x, interpret=True)
    else:
        n = int(np.prod(lead))
        got = _gram_tall(x.reshape(n, d, k), block_f=tiles[0],
                         block_d=tiles[1], interpret=True)
        got = got.reshape(*lead, k, k)
    want = jnp.einsum("...dk,...dl->...kl", x, x,
                      precision=jax.lax.Precision.HIGHEST)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(x))) ** 2 * d
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)


# ----------------------------------------------------------- power_matmul
@pytest.mark.parametrize("d,k", [(16, 1), (64, 4), (200, 8), (256, 32),
                                 (300, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_power_matmul_matches_ref(d, k, dtype):
    rng = np.random.default_rng(d + k)
    a = jnp.asarray(rng.standard_normal((d, d)), dtype=dtype)
    w = jnp.asarray(rng.standard_normal((d, k)), dtype=dtype)
    got = ops.power_matmul(a, w, block_m=128, block_k=128, interpret=True)
    want = ref.power_matmul_ref(a, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * np.sqrt(d) * 4)


# ---------------------------------------------------------------- fastmix
@pytest.mark.parametrize("m,n,k,K", [(4, 8, 2, 1), (8, 64, 8, 6),
                                     (12, 50, 7, 8), (16, 256, 8, 4)])
def test_fastmix_fused_matches_ref(m, n, k, K):
    from repro.core.topology import ring
    topo = ring(m)
    rng = np.random.default_rng(m * 100 + K)
    s = jnp.asarray(rng.standard_normal((m, n, k)), jnp.float32)
    L = jnp.asarray(topo.mixing, jnp.float32)
    eta = 0.3
    got = ops.fastmix_fused(s, L, eta, K, block_n=128, interpret=True)
    want = ref.fastmix_ref(s, L, eta, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(2, 20), st.integers(1, 6), st.integers(0, 8))
@settings(max_examples=10, deadline=None)
def test_fastmix_fused_property_random_shapes(m, k, K):
    from repro.core.topology import complete
    topo = complete(m)
    rng = np.random.default_rng(m + k + K)
    s = jnp.asarray(rng.standard_normal((m, 10, k)), jnp.float32)
    L = jnp.asarray(topo.mixing, jnp.float32)
    got = ops.fastmix_fused(s, L, 0.25, K, block_n=128, interpret=True)
    want = ref.fastmix_ref(s, L, 0.25, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- flash_attention
@pytest.mark.parametrize("sq,skv,hd,causal", [
    (32, 32, 16, True), (32, 32, 16, False),
    (64, 64, 32, True), (40, 72, 16, False),
    (128, 128, 64, True),
])
def test_flash_single_head(sq, skv, hd, causal):
    if causal and sq != skv:
        pytest.skip("causal requires square for this test")
    rng = np.random.default_rng(sq + skv + hd)
    q = jnp.asarray(rng.standard_normal((sq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((skv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((skv, hd)), jnp.float32)
    from repro.kernels.flash_attention import flash_attention_single
    got = flash_attention_single(q, k, v, causal=causal, block_q=16,
                                 block_kv=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_gqa_batched(h, hkv, dtype):
    rng = np.random.default_rng(h * 10 + hkv)
    b, s, hd = 2, 48, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, hd)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), dtype=dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=16, block_kv=16,
                              interpret=True)
    want = ref.mha_ref(q, k, v, causal=True)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_block_shape_invariance():
    """Output must not depend on the BlockSpec tiling."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    from repro.kernels.flash_attention import flash_attention_single
    a = flash_attention_single(q, k, v, block_q=16, block_kv=16,
                               interpret=True)
    b = flash_attention_single(q, k, v, block_q=64, block_kv=32,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_kernels_used_by_deepca_path():
    """ops.gram/power_matmul glue into the DeEPCA local step correctly."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((50, 40)), jnp.float32)
    w = jnp.asarray(np.linalg.qr(rng.standard_normal((40, 4)))[0], jnp.float32)
    a = ops.gram(x, interpret=True)
    g = ops.power_matmul(a, w, interpret=True)
    want = ref.gram_ref(x) @ w
    np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=1e-4,
                               atol=1e-3)
