"""Public entry points for the Pallas kernels.

``use_pallas`` switches between the Pallas path (interpret-mode when
lowered for the CPU, compiled by Mosaic when lowered for a TPU) and the
pure-jnp oracle — the distributed sync layer calls through here so the
whole framework runs on either.  The kernels take any bucket count: the
tile choice and the ragged last grid block live with the kernels
(``quantize.bucket_tile``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.quantize import NORM_L2
from . import ref
from .bucket_stats import bucket_stats_pallas
from .dequantize import dequantize_pallas
from .quantize import quantize_pallas


def quantize_op(
    vb: jnp.ndarray,
    u: jnp.ndarray,
    levels: jnp.ndarray,
    *,
    norm_type: str = NORM_L2,
    use_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    if use_pallas:
        return quantize_pallas(vb, u, levels, norm_type=norm_type)
    return ref.quantize_ref(vb, u, levels, norm_type)


def dequantize_op(
    codes: jnp.ndarray,
    norms: jnp.ndarray,
    levels: jnp.ndarray,
    *,
    use_pallas: bool = True,
) -> jnp.ndarray:
    if use_pallas:
        return dequantize_pallas(codes, norms, levels)
    return ref.dequantize_ref(codes, norms, levels)


def bucket_stats_op(
    vb: jnp.ndarray, *, norm_type: str = NORM_L2, use_pallas: bool = True
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    if use_pallas:
        return bucket_stats_pallas(vb, norm_type=norm_type)
    return ref.bucket_stats_ref(vb, norm_type)
