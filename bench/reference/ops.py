"""Pieces that every architecture module of ``bench/reference`` shares.

``dot`` is a matrix product at the reference's precision: "highest"
(float32 products, the reference) or "fp8" (each product's operands
rounded to float8 e4m3, and the gradient flowing back into it to e5m2,
each with one scale per tensor, as float8 training does: the control
that a lower-precision path has to fail).  ``rms`` is RMSNorm and
``rope`` the rotary embedding that rotates the two halves of each head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# largest finite float8 e4m3 and e5m2 values
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _fp8(x, dtype, top):
    """x rounded to a float8 ``dtype`` under one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(spec, a, b):
    """A product as float8 training computes it: the operands in e4m3,
    and in the backward pass the incoming gradient in e5m2."""
    return _einsum(spec, _fp8(a, jnp.float8_e4m3fn, _E4M3_MAX),
                   _fp8(b, jnp.float8_e4m3fn, _E4M3_MAX))


def _fp8_dot_fwd(spec, a, b):
    a8 = _fp8(a, jnp.float8_e4m3fn, _E4M3_MAX)
    b8 = _fp8(b, jnp.float8_e4m3fn, _E4M3_MAX)
    return _einsum(spec, a8, b8), (a8, b8)


def _fp8_dot_bwd(spec, res, g):
    a8, b8 = res
    _, pullback = jax.vjp(lambda a, b: _einsum(spec, a, b), a8, b8)
    return pullback(_fp8(g, jnp.float8_e5m2, _E5M2_MAX))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def dot(spec: str, a, b, precision: str):
    if precision == "fp8":
        return _fp8_dot(spec, a, b)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return _einsum(spec, a, b)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate the first and second halves of each head (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
