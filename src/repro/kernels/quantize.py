"""Pallas TPU kernel: fused bucket-norm + normalize + stochastic round.

This is the per-step encode hot path of Algorithm 1 (line 6).  On GPU the
paper uses a CUDA kernel; the TPU adaptation tiles *buckets* into VMEM:

  grid      = (cdiv(num_buckets, tile),)   the last block may be ragged:
              rows past num_buckets are never written back
  v block   = (tile, bucket_size)   f32 in VMEM
  u block   = (tile, bucket_size)   f32 in VMEM (pre-drawn uniforms;
              randomness is an explicit input so the kernel is a pure
              function and bit-identical to the oracle)
  levels    = (num_levels,)         whole, in SMEM (scalar reads)
  codes out = (tile, bucket_size)   int8 (int16 at 256 levels)
  norms out = (tile, 1)             f32, one sublane row per bucket

The bucket reduction (norm) runs on the VPU along lanes; the level search
is a short loop over the (static, <= 256) levels, each step one compare
and two selects on the tile — no gather, no sort, no (tile, bucket,
levels) temporary, and the MXU stays free for the backward matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import NORM_L2, NORM_LINF, code_dtype

# sublane tile of the narrowest blocked array (int8 codes: 32 rows; f32
# needs 8 and int16 16, so a multiple of 32 suits every block)
_SUBLANES = 32
# bytes of one f32 (tile, bucket_size) block: large enough that the
# per-grid-step overhead is small, small enough that the double-buffered
# inputs and the level-search temporaries stay inside the default scoped
# VMEM
_BLOCK_BYTES = 512 * 1024


def bucket_tile(num_buckets: int, bucket_size: int) -> int:
    """Buckets per grid step: the whole array when it fits one block,
    else a multiple of the int8 sublane tile (the grid's last block is
    ragged when it does not divide ``num_buckets``)."""
    t = max(_SUBLANES,
            _BLOCK_BYTES // (4 * bucket_size) // _SUBLANES * _SUBLANES)
    return num_buckets if num_buckets <= t else t


def pallas_per_platform(call, *args):
    """Run ``call(*args, interpret=...)`` compiled by Mosaic when lowered
    for a TPU and in the Pallas interpreter when lowered for the CPU.

    The choice follows the platform the program is lowered for, not the
    process's default backend, so a program compiled for a TPU always
    carries the compiled kernel.
    """
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(call, interpret=True),
        default=functools.partial(call, interpret=False))


def bucket_norms(v: jnp.ndarray, norm_type: str) -> jnp.ndarray:
    """(tile, bucket) f32 -> (tile, 1) per-bucket norms."""
    if norm_type == NORM_L2:
        return jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
    if norm_type == NORM_LINF:
        return jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    raise ValueError(norm_type)


def _level_search(r, levels_ref):
    """tau = #(levels <= r) - 1 clipped to [0, L-2], with
    lo = levels[tau] and hi = levels[tau + 1] (levels sorted, levels[0]
    <= r), as ``jnp.searchsorted`` gives in the oracle."""
    nlev = levels_ref.shape[0]

    def step(j, carry):
        tau, lo, hi = carry
        ge = r >= levels_ref[j]
        return (tau + ge.astype(jnp.int32),
                jnp.where(ge, levels_ref[j], lo),
                jnp.where(ge, levels_ref[j + 1], hi))

    init = (jnp.zeros(r.shape, jnp.int32),
            jnp.full(r.shape, levels_ref[0], jnp.float32),
            jnp.full(r.shape, levels_ref[1], jnp.float32))
    return jax.lax.fori_loop(1, nlev - 1, step, init)


def _quantize_kernel(v_ref, u_ref, levels_ref, codes_ref, norms_ref, *,
                     norm_type: str):
    v = v_ref[...].astype(jnp.float32)
    norm = bucket_norms(v, norm_type)
    safe = jnp.where(norm > 0, norm, 1.0)
    r = jnp.clip(jnp.abs(v) / safe, 0.0, 1.0)
    tau, lo, hi = _level_search(r, levels_ref)
    rho = (r - lo) / jnp.maximum(hi - lo, 1e-30)
    idx = tau + (u_ref[...] < rho).astype(jnp.int32)
    sign = jnp.where(v > 0, 1, jnp.where(v < 0, -1, 0))
    codes_ref[...] = (idx * sign).astype(codes_ref.dtype)
    norms_ref[...] = norm


@functools.partial(jax.jit, static_argnames=("norm_type",))
def quantize_pallas(
    vb: jnp.ndarray,
    u: jnp.ndarray,
    levels: jnp.ndarray,
    *,
    norm_type: str = NORM_L2,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize bucketed gradients; returns (codes int8, norms f32).

    vb, u: (num_buckets, bucket_size), any num_buckets; the grid steps
    over ``bucket_tile(num_buckets, bucket_size)`` buckets at a time.
    """
    nb, bs = vb.shape
    tile = bucket_tile(nb, bs)
    kernel = functools.partial(_quantize_kernel, norm_type=norm_type)

    def call(vb, u, levels, *, interpret):
        return pl.pallas_call(
            kernel,
            grid=(pl.cdiv(nb, tile),),
            in_specs=[
                pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((nb, bs), code_dtype(levels.shape[0])),
                jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            ],
            name="quantize",
            interpret=interpret,
        )(vb, u, levels)

    codes, norms = pallas_per_platform(
        call, vb, u.astype(jnp.float32), levels.astype(jnp.float32))
    return codes, norms[:, 0]
