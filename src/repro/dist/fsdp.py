"""Flat-parameter FSDP substrate.

Parameters of one layer slot are stored as ONE flat, zero-padded fp32
vector sharded over the data axes.  The forward materializes a slot with
a tiled ``all_gather``; the backward of that gather is a *quantized
reduce-scatter* (``custom_vjp``): each worker ENCODEs its local cotangent
through the configured ``GradientCodec`` and ships each peer only that
peer's shard as a packed ``WirePayload`` — so FSDP training moves
``b``-bit gradients in BOTH directions of the wire instead of fp32.

Layout invariants (enforced by ``padded_flat_len`` / ``chunk_plan``):

  padded length  Lp = nb_p * bucket_size
  nb_p % (M * k) == 0

so every shard holds whole buckets (the encode never straddles a shard
boundary) and the backward can run in ``k`` rounds — round c covers
slice ``[c*ppr, (c+1)*ppr)`` of every shard's buckets — letting the
encode of round c+1 overlap the all-to-all of round c.  (A
``MixedWidthCodec`` backward runs in one round: its per-bucket layout is
planned over the whole shard.)

Zero-padding is an exact fixed point of ENCODE/DECODE (sign 0 -> code 0),
so padded master parameters never drift.

The payload is moved generically (``jax.tree.map(transport.all_to_all,
payload)``), so the backward carries whatever the codec lays out — for
an ``EntropyCodec`` each round's chunks travel as per-bucket
canonical-Huffman runs with the coded-length word in the bucket header
(capacity-static arrays, so the ``k``-round overlap and the all-to-all
shapes are unchanged), decoding bit-exact against the uniform codec
(``tests/test_entropy_codec.py``).

``make_gather(algorithm=...)`` composes the backward with a stateful
``repro.compress`` algorithm: the reduce-scatter encodes
``cotangent + residual`` and the new error-feedback residual comes back
as the cotangent of an explicit ``residual`` input (see
docs/compression.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import GradientCodec, codec_for_scheme
from repro.core.schemes import QuantScheme
from repro.dist import transport as transport_lib

# ---------------------------------------------------------------------------
# flatten metadata
# ---------------------------------------------------------------------------

def flatten_meta(specs: dict, prefix: tuple = ()) -> list:
    """Param-spec tree -> deterministic flat layout.

    ``specs`` leaves are ``(shape, init_code)`` pairs (see
    ``models.transformer.slot_param_specs``).  Returns a list of
    ``(path, shape, init_code)`` in sorted-name order at every level, so
    the layout is reproducible from the spec alone.
    """
    meta = []
    for name in sorted(specs):
        sub = specs[name]
        if isinstance(sub, dict):
            meta.extend(flatten_meta(sub, prefix + (name,)))
        else:
            shape, code = sub
            meta.append((prefix + (name,), tuple(shape), code))
    return meta


def flat_size(meta: list) -> int:
    return sum(math.prod(shape) for _, shape, _ in meta)


def chunk_plan(n: int, bucket_size: int, M: int) -> tuple[int, int]:
    """(k, nb_padded) for an n-element flat vector on M workers.

    Picks the deepest chunking k in {8, 4, 2, 1} that still gives every
    worker at least one bucket per round, then pads the bucket count to a
    multiple of ``M * k`` so rounds and shards tile exactly.
    """
    nb = -(-n // bucket_size)
    k = 1
    for cand in (8, 4, 2):
        if cand * M <= nb:
            k = cand
            break
    group = M * k
    return k, -(-nb // group) * group


def padded_flat_len(meta: list, bucket_size: int, world: int,
                    shards: int | None = None) -> int:
    """Padded flat length: bucket-, round-, and shard-divisible."""
    m = world if shards is None else math.lcm(world, shards)
    _, nb_p = chunk_plan(flat_size(meta), bucket_size, m)
    return nb_p * bucket_size


def unflatten(flat: jnp.ndarray, meta: list, dtype) -> dict:
    """Flat (padded) vector -> nested param dict per ``meta``'s layout."""
    tree: dict = {}
    off = 0
    for path, shape, _ in meta:
        size = math.prod(shape)
        leaf = jax.lax.slice_in_dim(flat, off, off + size)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf.reshape(shape).astype(dtype)
        off += size
    return tree


# ---------------------------------------------------------------------------
# quantized reduce-scatter (the gather's backward)
# ---------------------------------------------------------------------------

def _rounds_for(shard_nb: int) -> int:
    # The backward only sees the (already padded) cotangent shape, so the
    # round count is re-derived here instead of threaded from chunk_plan.
    # Correctness rests solely on the divisibility check below; k may
    # legitimately exceed chunk_plan's k when the padding allows it.
    for cand in (8, 4, 2):
        if shard_nb % cand == 0 and shard_nb > cand:
            return cand
    return 1


def _quantized_reduce_scatter(g, levels, key, *, axes,
                              codec: GradientCodec, use_pallas,
                              residual=None):
    """(Lp,) per-worker cotangent -> (Lp/M,) shard of the worker MEAN.

    Runs in rounds over sub-slices of every shard so the ENCODE of round
    c+1 is independent of (and can overlap) the all-to-all of round c.
    The wire carries the codec's packed payload (words + norm words) —
    the bandwidth-optimal reduce-scatter volume at the codec's widths.

    ``residual`` enables error feedback (``repro.compress``) on this
    backward: the (Lp,)-shaped memory is added to the cotangent before
    ENCODE, and the new residual ``inp - Q(inp)`` is assembled from the
    decode of the worker's OWN sharded payloads — zero additional wire
    bytes.  Returns ``(shard_mean, new_residual)`` in that case.
    """
    transport = transport_lib.make_transport(axes)
    M = transport.size()
    # worker-distinct rounding randomness even when the caller passes a
    # replicated key: correlated rounding across workers would forfeit
    # the 1/M variance averaging of the mean
    key = jax.random.fold_in(key, transport.rank())
    if residual is not None:
        g = g + residual
    bs = codec.bucket_size
    nb = g.shape[0] // bs
    shard_nb = nb // M
    # mixed-width layouts are planned per whole shard: one round
    k = _rounds_for(shard_nb) if codec.chunkable else 1
    ppr = shard_nb // k  # buckets per shard per round
    gb = g.reshape(M, shard_nb, bs)

    pieces, own_rounds = [], []
    for c in range(k):
        sub = jax.lax.slice_in_dim(gb, c * ppr, (c + 1) * ppr, axis=1)
        vb = sub.reshape(M * ppr, bs)
        plan = codec.plan_buckets(M * ppr, shards=M)
        payload = codec.encode(vb, levels, jax.random.fold_in(key, c),
                               plan, use_pallas=use_pallas)
        if M == 1:
            payload = jax.tree.map(lambda a: a[None], payload)
        if residual is not None:
            # own round trip: segment j of the own payload is shard j's
            # round-c slice -> (M, ppr*bs), row j for shard j
            own_rounds.append(codec.decode(
                payload, levels, plan, shard=None, use_pallas=use_pallas))
        received = jax.tree.map(transport.all_to_all, payload)
        vals = codec.decode(received, levels, plan,
                            shard=transport.rank(),
                            use_pallas=use_pallas)     # (M, ppr*bs)
        pieces.append(vals.mean(0))
    shard_mean = jnp.concatenate(pieces)
    if residual is None:
        return shard_mean
    own = jnp.concatenate(
        [r.reshape(M, ppr, bs) for r in own_rounds], axis=1)  # (M,snb,bs)
    return shard_mean, g - own.reshape(-1)


def _float0_zeros(x):
    """Cotangent for a non-differentiable (integer / key) input."""
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


def _check_in_shard_map(axes):
    """Fail fast on a jax quirk: batching the gather's ``custom_vjp``
    backward (an ``all_to_all`` reduce-scatter) under a PLAIN
    ``jax.vmap`` axis mis-shapes the collective's batching rule
    (``mul got incompatible shapes for broadcasting``).  The shard_map
    path is unaffected — and is the production path — so point there
    instead of letting the broadcast error surface layers deeper.  The
    gather's axes are manual mesh axes only inside ``shard_map``.
    """
    manual = jax.sharding.get_abstract_mesh().manual_axes
    if not set(axes) <= set(manual):
        raise NotImplementedError(
            "make_gather must run inside jax.shard_map over mesh axes "
            f"{tuple(axes)!r} (see tests/test_fsdp_quantized.py for the "
            "harness): under a plain jax.vmap axis, batching the "
            "custom_vjp backward's all_to_all reduce-scatter hits a "
            "custom_vjp x all_to_all batching quirk.  Plain functions "
            "such as _quantized_reduce_scatter vmap fine.")


def make_gather(data_axes, scheme: QuantScheme, fsdp_sync: str = "quantized",
                *, use_pallas: bool = False,
                codec: GradientCodec | None = None,
                algorithm=None, guard_vmap: bool = True):
    """Returns ``gather(shard, levels, key) -> full`` for one flat slot.

    Forward: tiled all_gather of the param shard over ``data_axes``.
    Backward: reduce-scatter of the cotangent to the worker MEAN —
    quantized (the codec's packed payload on the wire) when
    ``fsdp_sync == 'quantized'`` and the scheme quantizes, else fp32
    ``psum_scatter``.  ``codec`` defaults to the scheme's uniform codec;
    a ``MixedWidthCodec`` moves per-bucket mixed widths instead, and a
    ``SparseCodec`` top-k index+value payloads.

    ``algorithm`` (a stateful ``repro.compress`` algorithm, e.g. error
    feedback) changes the signature to ``gather(shard, levels, key,
    residual) -> full``: the backward encodes ``cotangent + residual``
    through the algorithm's codec, and the NEW residual ``inp - Q(inp)``
    comes back as the cotangent of the ``residual`` input — the only
    channel a ``custom_vjp`` backward has to emit state.  Callers
    differentiate with respect to ``residual`` too and carry that
    "gradient" as next step's memory (see ``tests/test_compress.py``).
    The 4-arg contract survives the ``fsdp_sync='fp32'`` toggle (the
    residual flushes into the lossless mean and zeroes); algorithm
    ``warmup_steps`` raises here — the gather has no step counter to
    gate on.

    ``use_pallas`` defaults to False: on CPU the interpret-mode kernels
    materialize every grid block (see launch/dryrun.py); flip it on for
    real-TPU runs.  ``guard_vmap=False`` disables the fail-fast check
    for the plain-vmap batching quirk (kept only so the pinning
    xfail test can exercise the raw behavior).
    """
    axes = tuple(data_axes)
    quantized = fsdp_sync == "quantized" and scheme.quantized
    if algorithm is not None:
        codec = algorithm.codec
        if algorithm.stateful and algorithm.warmup_steps:
            raise ValueError(
                "warmup_steps is not supported on the gather-level EF "
                "path: the gather carries no step counter, so the gate "
                "cannot be evaluated here.  Gate the residual in the "
                "training loop instead (inject zeros until warmup ends).")
        if not algorithm.stateful:
            algorithm = None  # 'plain': the stateless 3-arg gather
    if codec is None:
        codec = codec_for_scheme(scheme)

    def gather(shard, levels, key):
        if guard_vmap:
            _check_in_shard_map(axes)

        @jax.custom_vjp
        def f(s, lv, k):
            return jax.lax.all_gather(s, axes, tiled=True)

        def fwd(s, lv, k):
            return jax.lax.all_gather(s, axes, tiled=True), (lv, k)

        def bwd(res, g):
            lv, k = res
            if quantized:
                ds = _quantized_reduce_scatter(
                    g, lv, k, axes=axes, codec=codec,
                    use_pallas=use_pallas)
            else:
                M = transport_lib.axes_size(axes)
                ds = jax.lax.psum_scatter(
                    g, axes, scatter_dimension=0, tiled=True) / M
            return ds, jnp.zeros_like(lv), _float0_zeros(k)

        f.defvjp(fwd, bwd)
        return f(shard, levels, key)

    def gather_ef(shard, levels, key, residual):
        if guard_vmap:
            _check_in_shard_map(axes)

        @jax.custom_vjp
        def f(s, lv, k, r):
            return jax.lax.all_gather(s, axes, tiled=True)

        def fwd(s, lv, k, r):
            return jax.lax.all_gather(s, axes, tiled=True), (lv, k, r)

        def bwd(res, g):
            lv, k, r = res
            if quantized:
                ds, new_r = _quantized_reduce_scatter(
                    g, lv, k, axes=axes, codec=codec,
                    use_pallas=use_pallas, residual=r)
            else:
                # fp32 toggle: same 4-arg contract, lossless sync ->
                # the residual is flushed into the mean and zeroed
                M = transport_lib.axes_size(axes)
                ds = jax.lax.psum_scatter(
                    g + r, axes, scatter_dimension=0, tiled=True) / M
                new_r = jnp.zeros_like(r)
            return ds, jnp.zeros_like(lv), _float0_zeros(k), new_r

        f.defvjp(fwd, bwd)
        return f(shard, levels, key, residual)

    return gather_ef if algorithm is not None else gather
