"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
They operate on already-bucketed inputs: v is (num_buckets, bucket_size).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.quantize import NORM_L2, NORM_LINF, code_dtype


def _norms(vb: jnp.ndarray, norm_type: str) -> jnp.ndarray:
    if norm_type == NORM_L2:
        return jnp.sqrt(jnp.sum(vb.astype(jnp.float32) ** 2, axis=-1))
    if norm_type == NORM_LINF:
        return jnp.max(jnp.abs(vb.astype(jnp.float32)), axis=-1)
    raise ValueError(norm_type)


def quantize_ref(
    vb: jnp.ndarray, u: jnp.ndarray, levels: jnp.ndarray, norm_type: str,
    norms: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused bucket-norm + normalize + stochastic round.

    Returns (codes int8 signed level indices, norms f32).  Given
    ``norms``, rounds against them instead of computing its own: two
    compilers may sum a bucket's squares in different orders, and a
    last-bit change of the norm can move a coordinate across a rounding
    threshold.
    """
    if norms is None:
        norms = _norms(vb, norm_type)
    safe = jnp.where(norms > 0, norms, 1.0)
    r = jnp.clip(jnp.abs(vb.astype(jnp.float32)) / safe[:, None], 0.0, 1.0)
    # tau = #levels <= r, minus one (levels sorted, levels[0]=0 so tau>=0);
    # searchsorted keeps the temp at O(nb*bucket), not O(nb*bucket*levels)
    tau = jnp.searchsorted(levels, r, side="right") - 1
    tau = jnp.clip(tau, 0, levels.shape[0] - 2)
    lo = levels[tau]
    hi = levels[tau + 1]
    rho = (r - lo) / jnp.maximum(hi - lo, 1e-30)
    idx = tau + (u < rho)
    sign = jnp.sign(vb).astype(jnp.int32)
    # int8 up to 128 levels (bits <= 7); the 8-bit edge widens to int16
    return (idx * sign).astype(code_dtype(levels.shape[0])), norms.astype(
        jnp.float32)


def dequantize_ref(
    codes: jnp.ndarray, norms: jnp.ndarray, levels: jnp.ndarray
) -> jnp.ndarray:
    """codes (signed, any int dtype) + norms -> f32 (num_buckets, bucket)."""
    idx = jnp.abs(codes.astype(jnp.int32))
    mags = jnp.take(levels.astype(jnp.float32), idx)
    return mags * jnp.sign(codes.astype(jnp.float32)) * norms[:, None]


def bucket_stats_ref(
    vb: jnp.ndarray, norm_type: str
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused sufficient statistics: per-bucket (norm, mean_r, var_r)."""
    norms = _norms(vb, norm_type)
    safe = jnp.where(norms > 0, norms, 1.0)
    r = jnp.abs(vb.astype(jnp.float32)) / safe[:, None]
    mu = jnp.mean(r, axis=-1)
    var = jnp.mean(r * r, axis=-1) - mu * mu
    return norms.astype(jnp.float32), mu, jnp.maximum(var, 0.0)
