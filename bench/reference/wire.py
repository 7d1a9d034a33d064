"""Plain reference of the paper's quantized wire on one worker.

Written from the paper (Faghri et al. 2020, "Adaptive Gradient
Quantization for Data-Parallel SGD"), Sec. 3 and App. A-C, K, with the
settings a wire file of ``bench/wires`` states.  It imports nothing of
the program under test.

- Buckets: the flat gradient, zero-padded to whole buckets; each bucket
  is normalized by its L2 norm, r = |v| / ||v||.
- Statistics: per bucket the mean and variance of r, fitted as a normal
  truncated to [0, 1] (sigma floored at ``min_sigma``), from
  ``stat_components`` buckets taken at an even stride over the full
  buckets, mixed with weights proportional to the bucket's norm^2.
- ALQ: coordinate descent from the initial grid, level j set to
  F^-1(F(l[j+1]) - int_{l[j-1]}^{l[j+1]} (r - l[j-1]) / (l[j+1] - l[j-1]) dF)
  by bisection, ``alq_sweeps`` sweeps.
- AMQ: levels [0, p^s, ..., p, 1], gradient descent on p against the
  mixture's expected variance.
- Rounding: unbiased stochastic rounding of r to its two neighbouring
  levels, with the sign kept; Q(v) = sign * level * norm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# buckets per block of the blockwise passes, so that their temporaries
# stay a block in size at any gradient length
BLOCK = 4096


def num_levels(wire: dict) -> int:
    return 2 ** wire["bits"]


def buckets(flat, bucket: int):
    """(n,) -> (nb, bucket), zero-padded."""
    nb = -(-flat.shape[0] // bucket)
    return jnp.pad(flat, (0, nb * bucket - flat.shape[0])).reshape(nb, bucket)


def blocks(flat, bucket: int):
    """(n,) -> (k, BLOCK, bucket), zero-padded whole buckets."""
    size = BLOCK * bucket
    k = -(-flat.shape[0] // size)
    return jnp.pad(flat, (0, k * size - flat.shape[0])).reshape(
        k, BLOCK, bucket)


# ---------------------------------------------------------------------------
# the mixture of truncated normals
# ---------------------------------------------------------------------------

def _Phi(z):
    return 0.5 * (1.0 + jax.lax.erf(z / jnp.sqrt(2.0)))


def _phi(z):
    return jnp.exp(-0.5 * z * z) / jnp.sqrt(2.0 * jnp.pi)


def fit(flat, wire: dict):
    """(mu, sigma, weight) of the mixture components."""
    bucket = wire["bucket"]
    full = max(flat.shape[0] // bucket, 1)
    vb = buckets(flat, bucket)[:full]
    k = wire["stat_components"]
    if full > k:
        vb = vb[jnp.arange(k) * (full // k)]
    norm = jnp.sqrt(jnp.sum(vb * vb, axis=1))
    r = jnp.abs(vb) / jnp.where(norm > 0, norm, 1.0)[:, None]
    mu = jnp.mean(r, axis=1)
    sigma = jnp.maximum(jnp.sqrt(jnp.mean((r - mu[:, None]) ** 2, axis=1)),
                        wire["min_sigma"])
    w = norm * norm
    return mu, sigma, w / jnp.maximum(jnp.sum(w), 1e-30)


def _parts(mix, x):
    """Per component: the truncated normal's CDF and density at x."""
    mu, sigma, _ = mix
    lo = _Phi(-mu / sigma)
    Z = jnp.maximum(_Phi((1.0 - mu) / sigma) - lo, 1e-12)
    z = (jnp.asarray(x)[..., None] - mu) / sigma
    cdf = jnp.clip((_Phi(z) - lo) / Z, 0.0, 1.0)
    return cdf, _phi(z) / (sigma * Z)


def cdf(mix, x):
    return jnp.sum(mix[2] * _parts(mix, x)[0], axis=-1)


def moments(mix, a, c):
    """int_a^c r^k dF(r) for k = 0, 1, 2 (a, c inside [0, 1])."""
    mu, sigma, w = mix
    Fa, pa = _parts(mix, a)
    Fc, pc = _parts(mix, c)
    m0 = Fc - Fa
    m1 = mu * m0 - sigma ** 2 * (pc - pa)
    m2 = (mu * m1 + sigma ** 2 * m0
          - sigma ** 2 * (jnp.asarray(c)[..., None] * pc
                          - jnp.asarray(a)[..., None] * pa))
    return (jnp.sum(w * m0, -1), jnp.sum(w * m1, -1), jnp.sum(w * m2, -1))


def expected_variance(mix, levels):
    """sum_j int_{l_j}^{l_{j+1}} (l_{j+1} - r)(r - l_j) dF(r)."""
    a, c = levels[:-1], levels[1:]
    m0, m1, m2 = moments(mix, a, c)
    return jnp.sum(-m2 + (a + c) * m1 - a * c * m0)


# ---------------------------------------------------------------------------
# level updates
# ---------------------------------------------------------------------------

def alq_levels(mix, wire: dict):
    n = num_levels(wire)
    levels = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)

    def solve(target, a, c):
        def body(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            below = cdf(mix, mid) < target
            return jnp.where(below, mid, lo), jnp.where(below, hi, mid)

        lo, hi = jax.lax.fori_loop(0, wire["bisect_iters"], body, (a, c))
        return 0.5 * (lo + hi)

    def level(j, lv):
        a, c = lv[j - 1], lv[j + 1]
        m0, m1, _ = moments(mix, a, c)
        target = cdf(mix, c) - (m1 - a * m0) / jnp.maximum(c - a, 1e-12)
        new = jnp.clip(solve(target, a, c), a + 1e-7, c - 1e-7)
        return lv.at[j].set(new)

    def sweep(_, lv):
        return jax.lax.fori_loop(1, n - 1, level, lv)

    return jax.lax.fori_loop(0, wire["alq_sweeps"], sweep, levels)


def amq_levels_of(p, wire: dict):
    n = num_levels(wire)
    exps = jnp.arange(n - 2, -1, -1, dtype=jnp.float32)
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), p ** exps])


def amq_levels(mix, wire: dict):
    lo, hi = wire["amq_clip"]
    grad = jax.grad(lambda p: expected_variance(mix, amq_levels_of(p, wire)))

    def body(_, p):
        return jnp.clip(p - wire["amq_lr"] * grad(p), lo, hi)

    p = jax.lax.fori_loop(0, wire["amq_steps"], body,
                          jnp.float32(wire["initial_multiplier"]))
    return amq_levels_of(p, wire)


def adapted_levels(flat, wire: dict):
    """The grid the level update makes from the gradient ``flat``."""
    mix = fit(flat, wire)
    if wire["scheme"] == "alq":
        return alq_levels(mix, wire)
    if wire["scheme"] == "amq":
        return amq_levels(mix, wire)
    raise ValueError(f"no reference level update for {wire['scheme']!r}")


# ---------------------------------------------------------------------------
# rounding
# ---------------------------------------------------------------------------

def _grid(vb, levels):
    """|v|, each bucket's norm, and the grid points below and above r."""
    a = jnp.abs(vb)
    norm = jnp.sqrt(jnp.sum(vb * vb, axis=-1, keepdims=True))
    r = jnp.clip(a / jnp.where(norm > 0, norm, 1.0), 0.0, 1.0)
    tau = jnp.clip(jnp.searchsorted(levels, r, side="right") - 1,
                   0, levels.shape[0] - 2)
    return a, norm, r, levels[tau], levels[tau + 1]


def quantize(flat, levels, key, bucket: int):
    """Q(flat): each coordinate rounded without bias to the grid scaled
    by its bucket's norm; uniforms from ``key``, one fold per block."""
    xb = blocks(flat, bucket)

    def one(args):
        i, vb = args
        a, norm, r, lo, hi = _grid(vb, levels)
        u = jax.random.uniform(jax.random.fold_in(key, i), vb.shape)
        up = u < (r - lo) / jnp.maximum(hi - lo, 1e-30)
        return jnp.sign(vb) * jnp.where(up, hi, lo) * norm

    out = jax.lax.map(one, (jnp.arange(xb.shape[0]), xb))
    return out.reshape(-1)[:flat.shape[0]]


def rounding_variance(flat, levels, bucket: int):
    """sum over coordinates of E[(Q(v) - v)^2]: a coordinate at b above
    its lower grid point and a below its upper one has variance ab."""
    xb = blocks(flat, bucket)

    def one(vb):
        a, norm, _, lo, hi = _grid(vb, levels)
        return jnp.sum((hi * norm - a) * (a - lo * norm))

    return jnp.sum(jax.lax.map(one, xb))
