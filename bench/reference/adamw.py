"""Plain AdamW (Loshchilov and Hutter 2019) with bias correction, as a
job file of ``bench/jobs`` states it."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return zeros, jax.tree.map(jnp.zeros_like, params)


def update(job: dict, params, grads, m, v, t: int):
    """Step ``t`` (0-based): returns (params, m, v)."""
    b1, b2, eps, lr = job["b1"], job["b2"], job["eps"], job["lr"]
    wd = job["weight_decay"]
    c1 = 1.0 - b1 ** (t + 1)
    c2 = 1.0 - b2 ** (t + 1)
    m = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p),
        params, m, v)
    return params, m, v
