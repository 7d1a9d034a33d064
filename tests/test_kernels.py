"""Pallas kernel validation: sweep shapes/dtypes/norms/grids and assert
allclose (codes: exact) against the pure-jnp oracles in kernels/ref.py.
Kernels run in the Pallas interpreter on CPU; tests/test_tpu_compile.py
compiles the same kernels for a TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import code_dtype, exp_levels, ternary_levels, uniform_levels
from repro.kernels import ops, ref


SHAPES = [(8, 256), (16, 512), (8, 1024), (32, 128), (24, 256)]
LEVELS = [
    ("uniform3", uniform_levels(3)),
    ("exp4", exp_levels(4, 0.5)),
    ("ternary", ternary_levels()),
]


@pytest.mark.parametrize("nb,bs", SHAPES)
@pytest.mark.parametrize("lname,levels", LEVELS)
@pytest.mark.parametrize("norm", ["l2", "linf"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_kernel_matches_oracle(nb, bs, lname, levels, norm, dtype):
    key = jax.random.PRNGKey(nb * bs)
    v = (jax.random.normal(key, (nb, bs)) * 0.1).astype(dtype)
    u = jax.random.uniform(jax.random.PRNGKey(7), (nb, bs), jnp.float32)
    c1, n1 = ops.quantize_op(v.astype(jnp.float32), u, levels,
                             norm_type=norm, use_pallas=True)
    c2, n2 = ref.quantize_ref(v.astype(jnp.float32), u, levels, norm)
    assert jnp.all(c1 == c2), f"{lname} {norm} {nb}x{bs}"
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-6)


@pytest.mark.parametrize("nb,bs", SHAPES[:3])
@pytest.mark.parametrize("lname,levels", LEVELS)
def test_dequantize_kernel_matches_oracle(nb, bs, lname, levels):
    key = jax.random.PRNGKey(1)
    nlev = levels.shape[0]
    codes = jax.random.randint(key, (nb, bs), -(nlev - 1), nlev).astype(
        code_dtype(nlev))
    norms = jax.random.uniform(jax.random.PRNGKey(2), (nb,)) + 0.1
    d1 = ops.dequantize_op(codes, norms, levels, use_pallas=True)
    d2 = ref.dequantize_ref(codes, norms, levels)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


@pytest.mark.parametrize("nb,bs", SHAPES[:3])
@pytest.mark.parametrize("norm", ["l2", "linf"])
def test_bucket_stats_kernel_matches_oracle(nb, bs, norm):
    v = jax.random.normal(jax.random.PRNGKey(3), (nb, bs)) * 0.05
    s1 = ops.bucket_stats_op(v, norm_type=norm, use_pallas=True)
    s2 = ref.bucket_stats_ref(v, norm)
    for a, b in zip(s1, s2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_encode_decode_roundtrip_preserves_unbiasedness():
    """Kernel path: decode(encode(v)) averaged over keys converges to v."""
    levels = uniform_levels(3)
    v = jax.random.normal(jax.random.PRNGKey(4), (8, 256)) * 0.02

    def qdq(key):
        u = jax.random.uniform(key, v.shape)
        c, n = ops.quantize_op(v, u, levels, use_pallas=True)
        return ops.dequantize_op(c, n, levels, use_pallas=True)

    keys = jax.random.split(jax.random.PRNGKey(5), 256)
    qs = jax.lax.map(qdq, keys)
    err = jnp.abs(qs.mean(0) - v).max() / jnp.abs(v).std()
    assert float(err) < 0.5


@pytest.mark.parametrize("nb", [40, 72])
def test_kernels_ragged_last_block_match_oracle(nb):
    """At bucket 4096 the grid steps over 32 buckets, which do not
    divide ``nb``: the last grid block is ragged, and its rows past
    num_buckets must not disturb the result."""
    from repro.kernels import (bucket_stats_pallas, dequantize_pallas,
                               quantize_pallas)
    from repro.kernels.quantize import bucket_tile
    bs = 4096
    assert nb % bucket_tile(nb, bs) != 0
    levels = uniform_levels(3)
    v = jax.random.normal(jax.random.PRNGKey(nb), (nb, bs)) * 0.1
    u = jax.random.uniform(jax.random.PRNGKey(8), (nb, bs), jnp.float32)
    c1, n1 = quantize_pallas(v, u, levels)
    c2, n2 = ref.quantize_ref(v, u, levels, "l2")
    assert jnp.all(c1 == c2)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(dequantize_pallas(c2, n2, levels)),
        np.asarray(ref.dequantize_ref(c2, n2, levels)), rtol=1e-6)
    for a, b in zip(bucket_stats_pallas(v),
                    ref.bucket_stats_ref(v, "l2")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
