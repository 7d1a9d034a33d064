"""Pallas TPU kernel: decode (DECODE_l, Algorithm 1 line 8).

codes (int8 signed level indices) + per-bucket norms -> f32 values.
Same bucket-tile layout as quantize.py: norms arrive as a (tile, 1)
block.  The level lookup is a short loop of selects over the levels in
SMEM rather than a gather — TPU-native for tiny tables.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import bucket_tile, pallas_per_platform


def _dequantize_kernel(codes_ref, norms_ref, levels_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)
    idx = jnp.abs(codes)

    def step(j, mags):
        return jnp.where(idx == j, levels_ref[j], mags)

    mags = jax.lax.fori_loop(
        1, levels_ref.shape[0], step,
        jnp.full(idx.shape, levels_ref[0], jnp.float32))
    sign = jnp.where(codes > 0, 1.0, jnp.where(codes < 0, -1.0, 0.0))
    out_ref[...] = mags * sign * norms_ref[...]


@jax.jit
def dequantize_pallas(
    codes: jnp.ndarray,
    norms: jnp.ndarray,
    levels: jnp.ndarray,
) -> jnp.ndarray:
    """(num_buckets, bucket_size) codes + (num_buckets,) norms -> f32."""
    nb, bs = codes.shape
    tile = bucket_tile(nb, bs)

    def call(codes, norms, levels, *, interpret):
        return pl.pallas_call(
            _dequantize_kernel,
            grid=(pl.cdiv(nb, tile),),
            in_specs=[
                pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((tile, bs), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nb, bs), jnp.float32),
            name="dequantize",
            interpret=interpret,
        )(codes, norms, levels)

    return pallas_per_platform(
        call, codes, norms.astype(jnp.float32).reshape(nb, 1),
        levels.astype(jnp.float32))
