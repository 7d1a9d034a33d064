"""Pallas TPU kernel: fused sufficient statistics (Algorithm 1, line 4).

One pass over the bucketed gradient computes, per bucket, the Lq norm and
the first two moments of the normalized magnitudes — exactly what
``repro.core.stats.fit_bucket_stats`` needs to fit the truncated-normal
mixture.  Fusing avoids a second HBM sweep over the gradient (the
adaptive methods' extra cost is this kernel once every ~10k steps).
Same bucket-tile layout as quantize.py; each statistic leaves as a
(tile, 1) block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantize import NORM_L2
from .quantize import bucket_norms, bucket_tile, pallas_per_platform


def _bucket_stats_kernel(v_ref, norms_ref, mu_ref, var_ref, *, norm_type: str):
    v = v_ref[...].astype(jnp.float32)
    norm = bucket_norms(v, norm_type)
    safe = jnp.where(norm > 0, norm, 1.0)
    r = jnp.abs(v) / safe
    mu = jnp.mean(r, axis=-1, keepdims=True)
    var = jnp.mean(r * r, axis=-1, keepdims=True) - mu * mu
    norms_ref[...] = norm
    mu_ref[...] = mu
    var_ref[...] = jnp.maximum(var, 0.0)


@functools.partial(jax.jit, static_argnames=("norm_type",))
def bucket_stats_pallas(
    vb: jnp.ndarray,
    *,
    norm_type: str = NORM_L2,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns per-bucket (norms, mean_r, var_r), each (num_buckets,)."""
    nb, bs = vb.shape
    tile = bucket_tile(nb, bs)
    kernel = functools.partial(_bucket_stats_kernel, norm_type=norm_type)
    stat = pl.BlockSpec((tile, 1), lambda i: (i, 0))

    def call(vb, *, interpret):
        return pl.pallas_call(
            kernel,
            grid=(pl.cdiv(nb, tile),),
            in_specs=[pl.BlockSpec((tile, bs), lambda i: (i, 0))],
            out_specs=[stat, stat, stat],
            out_shape=[jax.ShapeDtypeStruct((nb, 1), jnp.float32)] * 3,
            name="bucket_stats",
            interpret=interpret,
        )(vb)

    return tuple(s[:, 0] for s in pallas_per_platform(call, vb))
