"""Quantized gradient synchronization (Algorithm 1, lines 2-9).

Everything here runs INSIDE ``shard_map``: collectives are expressed over
named mesh axes (``axes``), and what travels over the interconnect is a
``core.codec.WirePayload`` — dense uint32 words of packed level symbols
plus packed bucket norms — never dequantized fp32.  The payload layout
(including per-bucket mixed widths) is owned entirely by the
``GradientCodec``; this module only sequences ENCODE -> collective ->
DECODE -> average over a ``Transport``.

Wire modes
----------
``all_gather``  Every worker ENCODEs its local gradient, and the packed
    payload is all-gathered.  One fused decode+average pass over the M
    gathered streams yields the aggregate; since every worker decodes
    the same gathered bytes, the result is bit-identical everywhere (the
    paper's broadcast-all scheme, Sec. 5).

``two_phase``   The reduce direction is compressed with the scheme's own
    grid and moved as an all-to-all of the codec's *sharded* payload (a
    true quantized reduce-scatter: each worker ships each peer only that
    peer's shard).  Each worker then RE-quantizes its shard of the
    aggregate on a fixed 8-bit uniform/L-inf grid — fine enough that the
    second rounding does not forfeit the 1/M variance averaging (see
    benchmarks/bench_twophase) — and the packed result is all-gathered.
    Total wire is ~(b + 8/M + 9) bits/coord instead of the broadcast
    scheme's M*b.

``fp32``        Plain psum mean (SuperSGD / debugging baseline).

``compressed_allreduce`` wraps the same wire modes in the
``repro.compress`` algorithm hook (error-feedback residual injection
before ENCODE, residual update from the codec's own local decode after
DECODE) — the stateless ``plain`` algorithm is bit-exact with
``quantized_allreduce``.

``gather_stats`` is the sufficient-statistics path (Algorithm 1, line 4):
one fused ``bucket_stats`` sweep, strided subsampling to
``max_stat_components``, and a tiny cross-worker mixture merge.
``maybe_update_levels`` wraps it in ``lax.cond`` so the ~10k non-update
steps pay nothing.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codec import GradientCodec, codec_for_scheme, requant_codec
from repro.core.levels import uniform_levels
from repro.core.schemes import QuantScheme, SchemeState
from repro.core.stats import TruncNormStats, merge_stats, stats_from_moments
from repro.dist.transport import Transport, make_transport
from repro.kernels import ops

# Phase-2 grid of the two_phase mode: 8-bit uniform levels under L-inf
# bucket normalization (QSGDinf at 8 bits).  L-inf spreads the aggregate's
# normalized magnitudes over [0, 1], so the 1/255 grid step stays well
# below phase-1 noise at any bucket size.
TWO_PHASE_BITS = 8


class SyncMetrics(NamedTuple):
    """Per-step wire accounting, split by direction so asymmetric modes
    (two_phase: cheap reduce hop, 9-bit broadcast hop) are visible to
    cost models (``repro.sim``) instead of one aggregate number.

    The bits/coord fields are MEASURED for variable-volume codecs
    (``WirePlan.variable``, the entropy-coded payload family): what the
    per-bucket coded-length headers say actually needs to travel, not
    the static worst-case plan.  For fixed-layout codecs measured ==
    planned, bit for bit.

    Defaulted fields are ``jnp.float32`` SCALARS, not Python floats, so
    harnesses that build shard_map out_specs from ``metric_specs()``
    see a uniform float32 metric dtype on every path (incl. the
    no-update / stateless paths that never ``_replace`` them)."""

    comm_bits_per_coord: jnp.ndarray       # total = reduce + broadcast
    quant_error: jnp.ndarray  # local ||Q(g) - g||^2 (own encode)
    reduce_bits_per_coord: jnp.ndarray     # toward-aggregate hop (phase 1)
    broadcast_bits_per_coord: jnp.ndarray  # from-aggregate hop (phase 2 /
    #                                        the broadcast-all gather)
    entropy_bits_per_coord: jnp.ndarray = jnp.float32(0.0)  # achievable
    #   entropy-coded cost of the CURRENT grid: H(L) + Pr(sym != 0) sign
    #   bits, fit at the last level update (``SchemeState
    #   .entropy_bits``); fixed-width wire bits until the first update.
    #   With the EntropyCodec this is the target the measured
    #   comm_bits_per_coord converges onto.
    residual_norm: jnp.ndarray = jnp.float32(0.0)  # ||error-feedback
    #   residual|| after this step's feedback (repro.compress); 0 for
    #   stateless algorithms.
    kept_fraction: jnp.ndarray = jnp.float32(1.0)  # coordinates on the
    #   wire / total (static; < 1 only for the sparse payload family).
    #   The EXACT shipped sparse bits/coord are comm_bits_per_coord —
    #   every WirePlan accounts indices + values + norms + alignment.
    corrupt_fraction: jnp.ndarray = jnp.float32(0.0)  # fraction of
    #   gathered (worker, bucket) wire slots that FAILED an integrity
    #   check this step and were excluded from the aggregate; always 0
    #   without ``integrity=`` plans (nothing is checked).
    excluded_workers: jnp.ndarray = jnp.float32(0.0)  # workers whose
    #   ENTIRE payload failed integrity (dropped/zeroed rows) — they
    #   aggregate exactly like a MaskedTransport-masked worker.


# ---------------------------------------------------------------------------
# wire modes
# ---------------------------------------------------------------------------

def _allreduce_all_gather(flat, codec, levels, key, transport, use_pallas):
    d = flat.shape[0]
    plan = codec.plan(d)
    vb = codec.bucketize(flat, plan)
    payload = codec.encode(vb, levels, key, plan, use_pallas=use_pallas)

    gathered = jax.tree.map(transport.all_gather, payload)   # (M, ...)
    if plan.integrity:
        # checked decode: per-(worker, bucket) validity verdicts, with
        # detected-corrupt buckets excluded from the aggregate by the
        # per-bucket renormalization rule (a fully-invalid worker
        # aggregates bit-exactly like a transport-masked one).  ``own``
        # comes from the LOCAL payload, not the gathered row — wire
        # corruption of one's own row must not poison the error-
        # feedback residual (bit-identical when the wire is clean).
        per_worker, valid = codec.decode_checked(gathered, levels, plan,
                                                 use_pallas=use_pallas)
        out = transport.mean_workers_bucketed(
            per_worker, valid, plan.bucket_size)[:d]
        own = codec.decode(payload, levels, plan,
                           use_pallas=use_pallas)[:d]
        corrupt = jnp.mean(1.0 - valid.astype(jnp.float32))
        excluded = jnp.sum(jnp.all(~valid, axis=1).astype(jnp.float32))
    else:
        per_worker = codec.decode(gathered, levels, plan,
                                  use_pallas=use_pallas)      # (M, n)
        out = transport.mean_workers(per_worker)[:d]
        own = jnp.take(per_worker, transport.rank(), axis=0)[:d]
        corrupt = jnp.float32(0.0)
        excluded = jnp.float32(0.0)
    qerr = jnp.sum((own - flat) ** 2)
    # the single gather IS the broadcast-all hop (paper Sec. 5);
    # variable-volume codecs report what their headers say this
    # worker's payload actually ships, not the static capacity
    bits = (codec.measured_bits_per_coord(payload, plan)
            if plan.variable else jnp.float32(plan.bits_per_coord))
    return out, own, SyncMetrics(bits, qerr, jnp.float32(0.0), bits,
                                 corrupt_fraction=corrupt,
                                 excluded_workers=excluded)


def _allreduce_two_phase(flat, codec, levels, key, transport, use_pallas):
    d = flat.shape[0]
    M = transport.size()
    plan = codec.plan(d, shards=M)

    # ---- phase 1: quantized reduce-scatter (scheme grid) ----
    vb = codec.bucketize(flat, plan)
    payload = codec.encode(vb, levels, key, plan, use_pallas=use_pallas)
    if M == 1:  # unsharded payload is 1-D; the wire still sees one row
        payload = jax.tree.map(lambda a: a[None], payload)
    received = jax.tree.map(transport.all_to_all, payload)
    corrupt = jnp.float32(0.0)
    excluded = jnp.float32(0.0)
    if plan.integrity:
        shard_per_worker, valid1 = codec.decode_checked(
            received, levels, plan, shard=transport.rank(),
            use_pallas=use_pallas)                           # (M, shard_n)
        shard_mean = transport.mean_workers_bucketed(
            shard_per_worker, valid1, plan.bucket_size)
        corrupt = corrupt + jnp.sum(1.0 - valid1.astype(jnp.float32))
        excluded = jnp.sum(jnp.all(~valid1, axis=1).astype(jnp.float32))
    else:
        shard_per_worker = codec.decode(received, levels, plan,
                                        shard=transport.rank(),
                                        use_pallas=use_pallas)
        shard_mean = transport.mean_workers(shard_per_worker)
    shard_mean = shard_mean.reshape(plan.shard_nb, plan.bucket_size)

    # ---- phase 2: re-quantize the aggregate, broadcast compressed ----
    codec2 = requant_codec(codec, TWO_PHASE_BITS)
    lv2 = uniform_levels(TWO_PHASE_BITS)
    plan2 = codec2.plan_buckets(plan.shard_nb)
    pay2 = codec2.encode(shard_mean, lv2,
                         jax.random.fold_in(key, 0x2FA5E), plan2,
                         use_pallas=use_pallas)
    g2 = jax.tree.map(transport.all_gather, pay2)
    if plan2.integrity:
        # phase 2 carries each shard of the aggregate exactly once —
        # no redundancy to renormalize over, so a detected-corrupt
        # phase-2 bucket zero-fills (skips the coordinate this step)
        out, valid2 = codec2.decode_checked(g2, lv2, plan2,
                                            use_pallas=use_pallas)
        # where, not multiply: corrupt buckets can decode to NaN and
        # NaN * 0 would leak into the skipped coordinates
        out = jnp.where(valid2[..., None],
                        out.reshape(M, plan2.nb, plan2.bucket_size), 0.0)
        corrupt = corrupt + jnp.sum(1.0 - valid2.astype(jnp.float32))
        denom = jnp.float32(valid1.size + valid2.size)
        corrupt = corrupt / denom
    else:
        out = codec2.decode(g2, lv2, plan2, use_pallas=use_pallas)
    # the M shards joined into one materialized stream: a consumer that
    # reshapes slices of it (the trainer's unravel into parameters)
    # would otherwise fuse into one relayout of the (M, n) rows per
    # slice, which took the TPU compiler minutes at 170M coordinates
    out = jax.lax.optimization_barrier(out.reshape(-1))[:d]

    # own phase-1 payload, decoded shard by shard, for the error metric
    # (and for the compress layer's residual feedback)
    own = codec.decode(payload, levels, plan, shard=None,
                       use_pallas=use_pallas).reshape(-1)[:d]
    qerr = jnp.sum((own - flat) ** 2)
    bits_reduce = (codec.measured_bits_per_coord(payload, plan)
                   if plan.variable
                   else jnp.float32(plan.bits_per_coord))
    bits_bcast = jnp.float32(
        32.0 * (plan2.code_words + plan2.norm_words) / d)
    return out, own, SyncMetrics(bits_reduce + bits_bcast, qerr,
                                 bits_reduce, bits_bcast,
                                 corrupt_fraction=corrupt,
                                 excluded_workers=excluded)


def quantized_allreduce(
    flat: jnp.ndarray,
    scheme: QuantScheme,
    state: SchemeState,
    key: jax.Array,
    *,
    axes=(),
    mode: str = "all_gather",
    use_pallas: bool = True,
    transport: Transport | None = None,
    codec: GradientCodec | None = None,
    return_own: bool = False,
) -> tuple:
    """ENCODE -> collective -> DECODE -> average; replicated output.

    Args:
      flat: (d,) local gradient (call inside shard_map; no implicit psum).
      scheme / state: quantization method and its adaptive state (levels).
      key: PRNG key, REPLICATED across workers — worker-distinct
        randomness is derived by folding in the global rank.
      axes: named mesh axes to synchronize over (may be empty: M=1).
        The axes may equally be ``jax.vmap`` axis names — that is how
        ``repro.sim`` runs M logical workers on one host through this
        exact code path.
      mode: 'fp32' | 'all_gather' | 'two_phase'.
      transport: collective transport override (``dist.transport``);
        defaults to plain named-axis collectives over ``axes``.  The
        simulator injects a ``MaskedTransport`` here to drop per-worker
        payloads (worker dropout) without touching the wire-mode code.
      codec: wire codec override (``core.codec``); defaults to the
        scheme's uniform codec.  A ``MixedWidthCodec`` threads per-bucket
        widths through the same transports; a ``SparseCodec``
        (``repro.compress``) moves top-k index+value payloads.
      return_own: also return this worker's OWN lossy round trip
        ``Q(flat)`` (the decode of the bytes it put on the wire) —
        what the ``repro.compress`` error-feedback layer derives its
        residual from, at zero additional wire bytes.

    Returns (aggregate mean, SyncMetrics) — or (aggregate, own,
    SyncMetrics) with ``return_own`` — where the aggregate is
    bit-identical on every worker in all modes.
    """
    flat = flat.reshape(-1)
    axes = tuple(axes)
    if transport is None:
        transport = make_transport(axes)
    if mode == "fp32" or not scheme.quantized:
        out = transport.mean_psum(flat)
        m = SyncMetrics(jnp.float32(32.0), jnp.float32(0.0),
                        jnp.float32(32.0), jnp.float32(0.0),
                        jnp.float32(32.0))
        # fp32 sync is lossless: the own round trip is the input itself
        return (out, flat, m) if return_own else (out, m)
    if codec is None:
        codec = codec_for_scheme(scheme)

    levels = state.levels
    if transport.axes:
        key = jax.random.fold_in(key, transport.rank())
    if mode == "all_gather":
        out, own, m = _allreduce_all_gather(flat, codec, levels, key,
                                            transport, use_pallas)
    elif mode == "two_phase":
        out, own, m = _allreduce_two_phase(flat, codec, levels, key,
                                           transport, use_pallas)
    else:
        raise ValueError(f"unknown sync mode {mode!r}")
    ent = jnp.asarray(state.entropy_bits, jnp.float32)
    m = m._replace(entropy_bits_per_coord=ent)
    return (out, own, m) if return_own else (out, m)


def compressed_allreduce(
    flat: jnp.ndarray,
    scheme: QuantScheme,
    state: SchemeState,
    algorithm,
    comp_state,
    key: jax.Array,
    *,
    axes=(),
    mode: str = "all_gather",
    use_pallas: bool = True,
    transport: Transport | None = None,
) -> tuple:
    """The ``repro.compress`` algorithm hook around ENCODE/DECODE.

    Sequences ``algorithm.prepare`` (error-feedback residual injection)
    -> ``quantized_allreduce`` on the algorithm's codec ->
    ``algorithm.feedback`` (residual update from the codec's own local
    decode — zero additional wire bytes).  With the stateless ``plain``
    algorithm this is bit-for-bit ``quantized_allreduce`` on the same
    codec (``comp_state`` may then be ``None``).

    Returns (aggregate mean, new comp_state, SyncMetrics); the metrics
    carry the algorithm accounting (``residual_norm``,
    ``kept_fraction``) next to the wire accounting.
    """
    flat = flat.reshape(-1)
    inp = algorithm.prepare(flat, comp_state)
    out, own, m = quantized_allreduce(
        inp, scheme, state, key, axes=axes, mode=mode,
        use_pallas=use_pallas, transport=transport,
        codec=algorithm.codec, return_own=True)
    new_state = algorithm.feedback(comp_state, inp, own)
    m = m._replace(residual_norm=algorithm.residual_norm(new_state),
                   kept_fraction=jnp.float32(algorithm.kept_fraction))
    return out, new_state, m


# ---------------------------------------------------------------------------
# sufficient statistics + schedule-gated level update
# ---------------------------------------------------------------------------

def gather_stats(
    flat: jnp.ndarray,
    scheme: QuantScheme,
    *,
    axes=(),
    use_pallas: bool = True,
) -> TruncNormStats:
    """One-sweep sufficient statistics of the local gradient, merged
    across workers (Algorithm 1, line 4).

    A single fused ``bucket_stats`` pass emits per-bucket (norm, mean_r,
    var_r); only ``max_stat_components`` scalars per worker travel in the
    merge — this is the only communication the adaptive methods add.
    """
    flat = flat.reshape(-1)
    axes = tuple(axes)
    codec = codec_for_scheme(scheme)
    vb = codec.bucketize(flat, codec.plan(flat.shape[0]))
    norms, mu, var = ops.bucket_stats_op(vb, norm_type=scheme.norm_type,
                                         use_pallas=use_pallas)
    # keep only fully-populated buckets: alignment padding is all-zero,
    # and a trailing partial bucket's intra-bucket zeros would bias its
    # (mu, sigma) toward 0 — drop it unless it is the only bucket
    nb_valid = max(flat.shape[0] // scheme.bucket_size, 1)
    stats = stats_from_moments(
        mu[:nb_valid], var[:nb_valid], norms[:nb_valid],
        weighted=scheme.weighted_stats,
        max_components=scheme.max_stat_components)
    if axes:
        stats = merge_stats(stats, axes)
    return stats


def maybe_update_levels(
    flat: jnp.ndarray,
    scheme: QuantScheme,
    state: SchemeState,
    do_update,
    *,
    axes=(),
    use_pallas: bool = True,
) -> SchemeState:
    """Run the scheme's level adaptation iff ``do_update`` (traced bool).

    ``lax.cond``-gated: on non-update steps neither the stats sweep nor
    the (tiny) merge collective executes — the adaptive methods' extra
    cost lands only on the paper's sparse schedule (App. K).
    """
    if not scheme.adaptive:
        return state
    flat = jax.lax.stop_gradient(flat.reshape(-1))

    def upd(s):
        stats = gather_stats(flat, scheme, axes=axes, use_pallas=use_pallas)
        return scheme.update_state(s, stats)

    return jax.lax.cond(do_update, upd, lambda s: s, state)
