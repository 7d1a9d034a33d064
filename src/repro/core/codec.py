"""GradientCodec: the composable ENCODE -> pack -> wire layer.

Every byte that travels during quantized synchronization — the
``all_gather`` / ``two_phase`` collectives in ``dist.sync``, the FSDP
backward reduce-scatter in ``dist.fsdp``, and all ``repro.sim``
topologies — is produced and consumed here.  A codec owns three things:

``plan(d)``      The static wire layout of a ``d``-coordinate gradient:
                 padded bucket count, per-segment packed-word counts,
                 per-bucket wire widths, and the exact bits/coordinate
                 accounting.  Plans are hashable ``NamedTuple``s so
                 layouts are computed once per (shape, codec).

``encode``       (nb, bucket_size) values + levels + PRNG key ->
                 ``WirePayload``: a pytree of dense uint32 words (packed
                 level symbols) + uint32 norm words.  Transports move
                 payloads generically (``jax.tree.map(transport.f, p)``).

``decode``       The inverse: one fused pass over M gathered payload
                 streams -> (M, n) values.

Three codecs ship here (plus ``repro.compress.SparseCodec``):

``UniformCodec``     one global (bits, bucket_size) — the paper's wire
    format, bit-for-bit identical to the pre-codec implementation
    (pinned by ``tests/test_codec_goldens.py``).

``EntropyCodec``     the uniform symbol stream entropy-coded per bucket
    with a static canonical-Huffman table fit to the closed-form level
    occupancies (Thm 3's achievable cost, realized as bytes).  Payload
    arrays stay worst-case shape-static; the *measured* volume is read
    off per-bucket length headers (``WirePlan.variable``).

``MixedWidthCodec``  per-bucket wire widths inside one tensor.  The
    static width assignment comes from ``assign_mixed_widths``: given
    per-bucket truncated-normal statistics (the same ``TruncNormStats``
    the adaptive schemes fit), buckets with larger norm·sigma — where
    rounding noise costs the most — get more levels, under a mean
    bits/coordinate budget (cf. NUQSGD / DQ-SGD: *where* the bits go
    matters as much as how many).  The payload is ragged across width
    groups but statically planned, so it rides the same gather /
    all-to-all transports as the uniform payload.

Sharded plans (``shards=M``) describe payloads split per destination
worker (two_phase phase 1, the FSDP reduce-scatter): segment ``s`` of
every worker's payload holds buckets ``[s*shard_nb, (s+1)*shard_nb)``.
Mixed-width segments may differ in true word count; all are padded to
the static max so collectives see rectangular arrays.  Decoding the
(traced) own-shard segment inside SPMD code dispatches over the static
per-shard layouts with ``lax.switch``.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import packing
from .levels import num_levels as _num_levels_for_bits
from .quantize import pad_to_buckets
from .stats import TruncNormStats, expected_variance


class WirePayload(NamedTuple):
    """What actually travels: packed level symbols + packed bucket norms.

    Leaves are uint32.  Unsharded payloads are 1-D (``(code_words,)`` /
    ``(norm_words,)``); sharded payloads carry a leading segment axis
    ``(shards, ...)``; gathered payloads a leading stream axis.
    """

    words: jnp.ndarray
    norm_words: jnp.ndarray


class WirePlan(NamedTuple):
    """Static layout of one tensor's wire payload (hashable)."""

    d: int                 # original (unpadded) coordinate count
    bucket_size: int
    nb: int                # padded bucket count (tile/shard aligned)
    shards: int            # payload segments (1 = whole tensor)
    code_words: int        # uint32 words per segment (max over segments)
    norm_words: int        # norm words per segment
    widths: tuple | None   # per-bucket scheme bits (len nb); None=uniform
    bits_per_coord: float  # shipped wire bits (codes+norms) per coord
    # variable-volume accounting mode (the entropy-coded payload
    # family): the payload ARRAYS are still the static worst-case
    # capacity above (shape-static under jit/shard_map), but the bytes
    # that actually need to travel are data-dependent — read them off
    # the payload with ``codec.measured_bits_per_coord``.  For
    # ``variable=False`` codecs measured == planned by construction.
    variable: bool = False
    # wire-integrity mode: the payload carries one checksum word per
    # bucket (``packing.bucket_checksums`` over the bucket's symbols +
    # norm bit pattern, laid at the head of each segment's word
    # stream), and ``codec.decode_checked`` returns a per-stream
    # per-bucket validity mask next to the values.  Off by default —
    # the integrity-off layout is byte-identical to the pre-fault wire
    # (pinned by the codec goldens).
    integrity: bool = False

    @property
    def n(self) -> int:
        return self.nb * self.bucket_size

    @property
    def shard_nb(self) -> int:
        return self.nb // self.shards

    @property
    def shard_n(self) -> int:
        return self.shard_nb * self.bucket_size

    @property
    def payload_bytes(self) -> float:
        """Bytes of ONE (padded) segment payload."""
        return 4.0 * (self.code_words + self.norm_words)


def resample_levels(levels: jnp.ndarray, num_out: int) -> jnp.ndarray:
    """Re-grid a level vector to ``num_out`` points on [0, 1].

    Linear interpolation in level-index space: the resampled grid keeps
    the endpoints (0, 1) and the *shape* of the adaptive grid, so a
    mixed-width codec inherits ALQ/AMQ adaptation at every width from
    the single base level vector carried in ``SchemeState``.
    """
    L = levels.shape[0]
    if num_out == L:
        return levels
    pos = jnp.linspace(0.0, float(L - 1), num_out, dtype=jnp.float32)
    return jnp.interp(pos, jnp.arange(L, dtype=jnp.float32),
                      levels.astype(jnp.float32))


def _align_up(x: int, m: int) -> int:
    return -(-x // m) * m


# buckets per segment are padded to a multiple of this: part of the
# wire layout (pinned by the codec goldens), not a kernel constraint
PLAN_BUCKET_ALIGN = 8


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GradientCodec:
    """Base codec: bucketing + norm side-channel; subclasses own the
    symbol layout.  All layout decisions are static (trace-time)."""

    bucket_size: int = 8192
    norm_type: str = "l2"
    norm_dtype: str = "float32"
    # opt-in wire integrity: lay a per-bucket checksum word into the
    # payload and expose ``decode_checked`` (values + validity mask).
    # Supported by the dense single-alphabet codecs (uniform, entropy);
    # mixed-width / sparse payload families raise.
    integrity: bool = False

    @property
    def chunkable(self) -> bool:
        """Whether payloads may be re-planned over arbitrary bucket
        sub-ranges (the FSDP round-overlap chunking).  Mixed-width
        layouts are planned per whole shard and are not."""
        return True

    @property
    def _norm_bits_per_coord(self) -> float:
        return (32.0 if self.norm_dtype == "float32" else
                16.0) / self.bucket_size

    @property
    def nominal_bits_per_coord(self) -> float:
        """Asymptotic wire bits per coordinate (symbols + norm
        side-channel), ignoring word-alignment slop — for cost reporting
        where no concrete plan exists yet."""
        raise NotImplementedError

    # -- planning ---------------------------------------------------------

    def plan(self, d: int, *, shards: int = 1,
             tile: int | None = None) -> WirePlan:
        """Layout for a ``d``-coordinate tensor split into ``shards``
        segments; bucket count is padded to ``shards * tile``."""
        if tile is None:
            tile = PLAN_BUCKET_ALIGN
        nb = _align_up(-(-d // self.bucket_size), shards * tile)
        return self.plan_buckets(nb, shards=shards, d=d)

    def plan_buckets(self, nb: int, *, shards: int = 1,
                     d: int | None = None) -> WirePlan:
        """Layout for an exact (already aligned) bucket count."""
        raise NotImplementedError

    # -- value <-> wire ---------------------------------------------------

    def bucketize(self, flat: jnp.ndarray, plan: WirePlan) -> jnp.ndarray:
        """(d,) -> (nb, bucket_size) zero-padded to the plan's layout.

        Zero buckets are exact fixed points of ENCODE/DECODE (norm 0,
        code 0), so padding never leaks into aggregates.
        """
        vb = pad_to_buckets(flat.reshape(-1), self.bucket_size)
        nb = vb.shape[0]
        if plan.nb != nb:
            vb = jnp.concatenate(
                [vb, jnp.zeros((plan.nb - nb, self.bucket_size), vb.dtype)])
        return vb

    def encode(self, vb: jnp.ndarray, levels: jnp.ndarray, key: jax.Array,
               plan: WirePlan, *, use_pallas: bool = True) -> WirePayload:
        """(nb, bucket_size) -> packed payload (segmented per the plan)."""
        raise NotImplementedError

    def decode(self, payload: WirePayload, levels: jnp.ndarray,
               plan: WirePlan, *, shard=None,
               use_pallas: bool = True) -> jnp.ndarray:
        """Packed payload stream(s) -> values.

        1-D payload leaves decode to ``(segment_n,)``; leaves with a
        leading stream axis decode to ``(M, segment_n)`` in one fused
        pass.  For sharded plans, ``shard`` names the segment the
        streams carry: a static int, a traced index (SPMD rank —
        dispatched via ``lax.switch`` over the static per-shard
        layouts), or ``None`` meaning stream ``i`` carries segment ``i``
        (decoding one's own sharded payload).
        """
        raise NotImplementedError

    def decode_checked(self, payload: WirePayload, levels: jnp.ndarray,
                       plan: WirePlan, *, shard=None,
                       use_pallas: bool = True):
        """``decode`` plus a per-stream per-bucket validity verdict.

        Returns ``(vals, valid)`` where ``valid`` is a bool array of
        shape ``(snb,)`` for a 1-D payload / ``(M, snb)`` for gathered
        streams: ``True`` iff the bucket's wire words passed every
        integrity check (checksum word, entropy header sanity).  For
        ``plan.integrity=False`` codecs everything is vacuously valid
        — this default keeps codecs without an integrity layout usable
        behind the same call.
        """
        vals = self.decode(payload, levels, plan, shard=shard,
                           use_pallas=use_pallas)
        if payload.words.ndim == 1:
            shape: tuple = (plan.shard_nb,)
        else:
            shape = (payload.words.shape[0], plan.shard_nb)
        return vals, jnp.ones(shape, bool)

    def requantize(self, vb: jnp.ndarray, levels: jnp.ndarray,
                   key: jax.Array, plan: WirePlan, *, chunk: int = 0,
                   use_pallas: bool = True) -> jnp.ndarray:
        """Value-space wire round trip Q(vb) of one plan segment —
        what a per-hop re-quantizing topology (sim ring) injects.
        ``vb`` holds segment ``chunk``'s buckets; norms take the packed
        wire round trip so values match the byte accounting.
        """
        raise NotImplementedError

    # -- accounting -------------------------------------------------------

    def measured_bits_per_coord(self, payload: WirePayload,
                                plan: WirePlan) -> jnp.ndarray:
        """Wire bits per original coordinate that ``payload`` actually
        needs to ship — the whole tensor's cost when ``payload`` is this
        worker's own (1-D or ``(shards, ...)``-sharded) encode.

        Fixed-layout codecs ship exactly the plan (``WirePlan
        .bits_per_coord``); variable-volume codecs
        (``plan.variable=True``) override this to read the per-bucket
        coded lengths out of the payload headers, so the number is a
        traced, data-dependent float32.
        """
        del payload
        return jnp.float32(plan.bits_per_coord)


def _unpack_norm_rows(nwords: jnp.ndarray, nb: int,
                      norm_dtype: str) -> jnp.ndarray:
    return jax.vmap(
        lambda w: packing.unpack_norms(w, nb, norm_dtype))(nwords)


# Decoded streams move between (M, n) rows and (M * nb, bucket) buckets
# one worker's row at a time: reshaping all M rows at once is a relayout
# the TPU compiler takes time over that grows with n (about 100 s for
# four rows of 16M coordinates), the per-row reshape takes seconds.

def _streams_to_buckets(words: jnp.ndarray, unpack, nb: int,
                        bucket: int) -> jnp.ndarray:
    """(M, words) -> (M * nb, bucket) symbols, ``unpack`` per row."""
    rows = jax.lax.map(lambda w: unpack(w).reshape(nb, bucket), words)
    return rows.reshape(-1, bucket)


def _buckets_to_streams(vals: jnp.ndarray, M: int) -> jnp.ndarray:
    """(M * nb, bucket) -> (M, nb * bucket)."""
    nb, bucket = vals.shape[0] // M, vals.shape[1]
    return jax.lax.map(lambda x: x.reshape(nb * bucket),
                       vals.reshape(M, nb, bucket))


# ---------------------------------------------------------------------------
# uniform codec: one global width (the paper's wire format)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UniformCodec(GradientCodec):
    """One (num_levels, bucket_size) for every bucket.

    This is the production codec: encode is one fused quantize kernel,
    the symbol stream is one fixed-width pack per segment, and decode is
    one fused dequantize over all gathered streams.  Bit-identical to
    the pre-codec ``dist.sync`` / ``dist.fsdp`` wire paths.
    """

    num_levels: int = 8

    @property
    def nominal_bits_per_coord(self) -> float:
        return (packing.wire_bits_for(self.num_levels)
                + self._norm_bits_per_coord)

    def plan_buckets(self, nb: int, *, shards: int = 1,
                     d: int | None = None) -> WirePlan:
        if nb % shards:
            raise ValueError(f"nb={nb} not divisible by shards={shards}")
        if d is None:
            d = nb * self.bucket_size
        wb = packing.wire_bits_for(self.num_levels)
        snb = nb // shards
        cw = packing.packed_words(snb * self.bucket_size, wb)
        if self.integrity:
            cw += snb                     # per-bucket checksum words
        nw = packing.norm_words(snb, self.norm_dtype)
        return WirePlan(d=d, bucket_size=self.bucket_size, nb=nb,
                        shards=shards, code_words=cw, norm_words=nw,
                        widths=None,
                        bits_per_coord=32.0 * shards * (cw + nw) / d,
                        integrity=self.integrity)

    def encode(self, vb, levels, key, plan, *, use_pallas=True):
        from repro.kernels import ops
        u = jax.random.uniform(key, vb.shape, jnp.float32)
        codes, norms = ops.quantize_op(vb, u, levels,
                                       norm_type=self.norm_type,
                                       use_pallas=use_pallas)
        L = levels.shape[0]
        snb = plan.shard_nb
        if self.integrity:
            csum = packing.bucket_checksums(
                packing.bias_codes(codes, L),
                packing.norm_bit_patterns(norms, self.norm_dtype))

        def seg_words(j):
            w = packing.pack_signed(
                jax.lax.slice_in_dim(codes, j * snb, (j + 1) * snb), L)
            if self.integrity:
                w = jnp.concatenate(
                    [jax.lax.slice_in_dim(csum, j * snb, (j + 1) * snb),
                     w])
            return w

        if plan.shards == 1:
            return WirePayload(
                words=seg_words(0),
                norm_words=packing.pack_norms(norms, self.norm_dtype))
        words = jnp.stack([seg_words(j) for j in range(plan.shards)])
        nwords = jax.vmap(
            lambda x: packing.pack_norms(x, self.norm_dtype))(
                norms.reshape(plan.shards, snb))
        return WirePayload(words=words, norm_words=nwords)

    def _decode_uniform(self, payload, levels, plan, use_pallas,
                        want_valid):
        from repro.kernels import ops
        words, nwords = payload
        single = words.ndim == 1
        if single:
            words, nwords = words[None], nwords[None]
        snb = plan.shard_nb
        n = plan.shard_n
        stored = None
        if plan.integrity:
            stored = jax.lax.slice_in_dim(words, 0, snb, axis=1)
            words = jax.lax.slice_in_dim(words, snb, words.shape[1],
                                         axis=1)
        norms = _unpack_norm_rows(nwords, snb, self.norm_dtype)
        L = levels.shape[0]
        M = norms.shape[0]
        wb = packing.wire_bits_for(L)
        usym = _streams_to_buckets(
            words, lambda w: packing.unpack(w, n, wb), snb,
            self.bucket_size)                          # (M * snb, bucket)
        vals = ops.dequantize_op(
            packing.unbias_codes(usym, L), norms.reshape(-1), levels,
            use_pallas=use_pallas)
        vals = _buckets_to_streams(vals, M)
        valid = None
        if want_valid:
            if stored is None:
                valid = jnp.ones((M, snb), bool)
            else:
                calc = jax.vmap(packing.bucket_checksums)(
                    usym.reshape(M, snb, self.bucket_size),
                    jax.vmap(lambda x: packing.norm_bit_patterns(
                        x, self.norm_dtype))(norms))
                valid = calc == stored
        if single:
            vals = vals[0]
            valid = None if valid is None else valid[0]
        return vals, valid

    def decode(self, payload, levels, plan, *, shard=None, use_pallas=True):
        vals, _ = self._decode_uniform(payload, levels, plan, use_pallas,
                                       want_valid=False)
        return vals

    def decode_checked(self, payload, levels, plan, *, shard=None,
                       use_pallas=True):
        return self._decode_uniform(payload, levels, plan, use_pallas,
                                    want_valid=True)

    def requantize(self, vb, levels, key, plan, *, chunk=0,
                   use_pallas=True):
        from repro.kernels import ops
        u = jax.random.uniform(key, vb.shape, jnp.float32)
        codes, norms = ops.quantize_op(vb, u, levels,
                                       norm_type=self.norm_type,
                                       use_pallas=use_pallas)
        wn = packing.unpack_norms(
            packing.pack_norms(norms, self.norm_dtype), norms.shape[0],
            self.norm_dtype)
        return ops.dequantize_op(codes, wn, levels, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# entropy codec: the metered H(L) cost realized as actual coded bytes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EntropyCodec(UniformCodec):
    """Canonical-Huffman entropy coding of the uniform symbol stream.

    Since PR 3 the achievable entropy-coded cost of the adaptive grid is
    *metered* (``SchemeState.entropy_bits`` -> ``SyncMetrics
    .entropy_bits_per_coord``) while every wire word stays fixed-width.
    This codec closes that gap: the same quantize kernel and the same
    key schedule as ``UniformCodec`` (so decoded values are bit-exact
    with it — pinned in ``tests/test_entropy_codec.py``), but each
    bucket's symbol run travels as variable-length canonical-Huffman
    codewords (QSGD's Elias trick, upgraded to the closed-form level
    occupancies ``coding.level_probabilities`` the adaptive schemes
    already fit).

    Wire layout of one payload segment (``shard_nb`` buckets)::

        [ header: shard_nb words — bit 31 = fixed-width fallback flag,
                  bits 0..30 = coded bit length of the bucket          ]
        [ bucket 0 region: cap_words words (worst-case capacity)       ]
        ...
        [ bucket shard_nb-1 region: cap_words words                    ]
        norm side-channel: unchanged (packed bucket norms)

    ``cap_words = packed_words(bucket_size, wire_bits)`` is the
    fixed-width budget of one bucket, so the payload arrays are
    shape-static (jit/shard_map/vmap-safe) and every segment has the
    SAME static layout — sharded decode needs no ``lax.switch``, and
    the FSDP chunked reduce-scatter re-plans freely (``chunkable``).
    A bucket whose coded run would overflow its capacity falls back to
    fixed-width packing in place (one flag bit in its header), so the
    codec never ships MORE than ``capacity + one header word`` per
    bucket and decode never reads past the region.

    The *measured* wire volume — what ``measured_bits_per_coord`` reads
    back out of the headers and what ``dist.sync`` / ``repro.sim`` bill
    — is ``ceil(coded_bits/32)`` words per bucket, not the capacity:
    the number that converges onto the metered
    ``entropy_bits_per_coord`` curve as the grid adapts.

    ``huff_lengths`` / ``huff_codes`` are the static per-symbol table
    over the ``2L - 1`` signed-symbol alphabet (``coding
    .entropy_table``), LSB-first wire codewords.  Like a mixed-width
    pattern, the table is static trace-time configuration: it is built
    host-side from ``level_probabilities`` at level-update time (the
    sim's ``entropy_coded`` scenario re-fits it at every milestone) and
    any staleness costs only bytes, never correctness — decodability
    depends on the prefix code alone, not on the data distribution.
    """

    huff_lengths: tuple = ()
    huff_codes: tuple = ()

    def __post_init__(self):
        from .coding import MAX_CODE_BITS
        S = 2 * self.num_levels - 1
        if len(self.huff_lengths) != S or len(self.huff_codes) != S:
            raise ValueError(
                f"entropy table must cover the {S}-symbol signed "
                f"alphabet, got {len(self.huff_lengths)} lengths / "
                f"{len(self.huff_codes)} codes (build one with "
                "coding.entropy_table or entropy_wrap)")
        bad = [l for l in self.huff_lengths
               if not 1 <= int(l) <= MAX_CODE_BITS]
        if bad:
            raise ValueError(
                f"codeword lengths must be in [1, {MAX_CODE_BITS}], "
                f"got {bad}")

    # -- static layout ----------------------------------------------------

    @property
    def _wire_bits(self) -> int:
        return packing.wire_bits_for(self.num_levels)

    @property
    def cap_words(self) -> int:
        """Worst-case capacity of one bucket's coded region (== its
        fixed-width word count, so the fallback always fits)."""
        return packing.packed_words(self.bucket_size, self._wire_bits)

    @property
    def nominal_bits_per_coord(self) -> float:
        # worst-case (capacity) accounting: header + fixed-width budget
        return (32.0 * (1 + self.cap_words) / self.bucket_size
                + self._norm_bits_per_coord)

    def plan_buckets(self, nb: int, *, shards: int = 1,
                     d: int | None = None) -> WirePlan:
        if nb % shards:
            raise ValueError(f"nb={nb} not divisible by shards={shards}")
        if d is None:
            d = nb * self.bucket_size
        snb = nb // shards
        cw = snb * (1 + self.cap_words)
        if self.integrity:
            cw += snb                     # per-bucket checksum words
        nw = packing.norm_words(snb, self.norm_dtype)
        return WirePlan(d=d, bucket_size=self.bucket_size, nb=nb,
                        shards=shards, code_words=cw, norm_words=nw,
                        widths=None,
                        bits_per_coord=32.0 * shards * (cw + nw) / d,
                        variable=True, integrity=self.integrity)

    # -- table as device constants ---------------------------------------

    def _table(self):
        lens = jnp.asarray(self.huff_lengths, jnp.uint32)
        codes = jnp.asarray(self.huff_codes, jnp.uint32)
        masks = jnp.where(lens >= 32, jnp.uint32(0xFFFFFFFF),
                          (jnp.uint32(1) << lens) - jnp.uint32(1))
        return lens, codes, masks

    # -- value <-> wire ---------------------------------------------------

    def encode(self, vb, levels, key, plan, *, use_pallas=True):
        from repro.kernels import ops
        u = jax.random.uniform(key, vb.shape, jnp.float32)
        codes, norms = ops.quantize_op(vb, u, levels,
                                       norm_type=self.norm_type,
                                       use_pallas=use_pallas)
        L = levels.shape[0]
        sym = packing.bias_codes(codes, L)              # (nb, bs)
        wb = self._wire_bits
        cap = self.cap_words
        bs = self.bucket_size
        len_t, code_t, _ = self._table()

        lens = len_t[sym]                               # (nb, bs)
        tot = jnp.sum(lens, axis=1)                     # coded bits
        fallback = tot > jnp.uint32(32 * cap)

        # fallback region: the plain fixed-width pack of each bucket
        fixed = jax.vmap(lambda c: packing.pack(c, wb))(sym)  # (nb, cap)

        # huffman region: scatter codeword fragments at cumulative bit
        # offsets (same two-scatter low/spill scheme as packing.pack;
        # codewords are <= 32 bits so each spills into at most one
        # following word).  Overflowing buckets scatter out of range
        # with mode='drop' — their region is replaced by `fixed` anyway.
        pos = jnp.cumsum(lens, axis=1) - lens
        cw_sym = code_t[sym]
        widx = (pos >> 5).astype(jnp.int32)
        off = pos & jnp.uint32(31)
        lo = (cw_sym << off).astype(jnp.uint32)
        spill = jnp.where(off > 0, jnp.uint32(32) - off, jnp.uint32(31))
        hi = jnp.where(off > 0, cw_sym >> spill, jnp.uint32(0))

        def pack_var(w_idx, lo_b, hi_b):
            out = jnp.zeros((cap + 1,), jnp.uint32)
            out = out.at[w_idx].add(lo_b, mode="drop")
            out = out.at[w_idx + 1].add(hi_b, mode="drop")
            return out[:cap]

        var = jax.vmap(pack_var)(widx, lo, hi)          # (nb, cap)

        used = jnp.where(fallback, jnp.uint32(bs * wb), tot)
        header = used | (fallback.astype(jnp.uint32) << 31)
        region = jnp.where(fallback[:, None], fixed, var)

        snb = plan.shard_nb
        if self.integrity:
            csum = packing.bucket_checksums(
                sym, packing.norm_bit_patterns(norms, self.norm_dtype))

        def seg(s):
            h = jax.lax.slice_in_dim(header, s * snb, (s + 1) * snb)
            r = jax.lax.slice_in_dim(region, s * snb,
                                     (s + 1) * snb).reshape(-1)
            parts = [h, r]
            if self.integrity:
                parts.insert(0, jax.lax.slice_in_dim(
                    csum, s * snb, (s + 1) * snb))
            return jnp.concatenate(parts)

        if plan.shards == 1:
            return WirePayload(
                words=seg(0),
                norm_words=packing.pack_norms(norms, self.norm_dtype))
        words = jnp.stack([seg(s) for s in range(plan.shards)])
        nwords = jax.vmap(
            lambda x: packing.pack_norms(x, self.norm_dtype))(
                norms.reshape(plan.shards, snb))
        return WirePayload(words=words, norm_words=nwords)

    def _decode_entropy(self, payload, levels, plan, use_pallas,
                        want_valid):
        from repro.kernels import ops
        words, nwords = payload
        single = words.ndim == 1
        if single:
            words, nwords = words[None], nwords[None]
        snb = plan.shard_nb
        bs = self.bucket_size
        cap = self.cap_words
        wb = self._wire_bits
        L = levels.shape[0]
        M = words.shape[0]
        norms = _unpack_norm_rows(nwords, snb, self.norm_dtype)
        stored = None
        off = 0
        if plan.integrity:
            stored = jax.lax.slice_in_dim(words, 0, snb, axis=1)
            off = snb
        headers = jax.lax.slice_in_dim(words, off, off + snb, axis=1)
        regions = jax.lax.slice_in_dim(
            words, off + snb, off + snb * (1 + cap),
            axis=1).reshape(M, snb, cap)
        fallback = (headers >> 31) > 0                  # (M, snb)

        # fixed-width path (vectorized; selected per bucket by the flag)
        sym_fixed = jax.vmap(jax.vmap(
            lambda r: packing.unpack(r, bs, wb)))(regions)

        # huffman path: sequential prefix decode, one lax.scan of
        # bucket_size symbols per bucket.  At each bit position the next
        # <=32 bits are matched against the whole codeword table at
        # once; prefix-freeness guarantees a unique hit.
        len_t, code_t, mask_t = self._table()

        def dec_bucket(region):
            w = jnp.concatenate([region, jnp.zeros((1,), jnp.uint32)])

            def body(pos, _):
                wi = (pos >> 5).astype(jnp.int32)
                o = pos & jnp.uint32(31)
                sp = jnp.where(o > 0, jnp.uint32(32) - o, jnp.uint32(31))
                u = (w[wi] >> o) | jnp.where(
                    o > 0, w[jnp.minimum(wi + 1, cap)] << sp,
                    jnp.uint32(0))
                s = jnp.argmax((u & mask_t) == code_t)
                return pos + len_t[s], s.astype(jnp.int32)

            _, syms = jax.lax.scan(body, jnp.uint32(0), None, length=bs)
            return syms

        sym_var = jax.vmap(jax.vmap(dec_bucket))(regions)
        sym = jnp.where(fallback[..., None], sym_fixed, sym_var)
        vals = ops.dequantize_op(
            packing.unbias_codes(sym.reshape(M * snb, bs), L),
            norms.reshape(-1), levels, use_pallas=use_pallas)
        vals = _buckets_to_streams(vals, M)
        valid = None
        if want_valid:
            if stored is None:
                valid = jnp.ones((M, snb), bool)
            else:
                # checksum over the decoded symbols + norm bits ...
                calc = jax.vmap(packing.bucket_checksums)(
                    sym.astype(jnp.uint32),
                    jax.vmap(lambda x: packing.norm_bit_patterns(
                        x, self.norm_dtype))(norms))
                valid = calc == stored
                # ... AND header sanity: a fallback bucket's length is
                # exactly the fixed-width run; a coded bucket's length
                # fits its capacity.  (A corrupt header word can only
                # inflate the billed volume or misroute the decode —
                # both are caught here.)
                used = headers & jnp.uint32(0x7FFFFFFF)
                sane = jnp.where(fallback,
                                 used == jnp.uint32(bs * wb),
                                 used <= jnp.uint32(32 * cap))
                valid = valid & sane
        if single:
            vals = vals[0]
            valid = None if valid is None else valid[0]
        return vals, valid

    def decode(self, payload, levels, plan, *, shard=None,
               use_pallas=True):
        # every segment has the same static layout, so `shard` (static
        # or traced) never changes the decode — accepted for protocol
        # compatibility, like SparseCodec
        vals, _ = self._decode_entropy(payload, levels, plan, use_pallas,
                                       want_valid=False)
        return vals

    def decode_checked(self, payload, levels, plan, *, shard=None,
                       use_pallas=True):
        return self._decode_entropy(payload, levels, plan, use_pallas,
                                    want_valid=True)

    # requantize: inherited from UniformCodec — the value-space round
    # trip is identical (entropy coding is lossless on the symbols).

    def measured_bits_per_coord(self, payload, plan):
        words = payload.words
        if words.ndim == 1:
            words = words[None]
        snb = plan.shard_nb
        off = snb if plan.integrity else 0
        headers = jax.lax.slice_in_dim(words, off, off + snb, axis=1)
        used = headers & jnp.uint32(0x7FFFFFFF)
        # a corrupt header cannot bill more than the bucket's capacity
        used = jnp.minimum(used, jnp.uint32(32 * self.cap_words))
        coded = jnp.sum((used + jnp.uint32(31)) >> 5)   # ceil words
        overhead = snb + off                            # header (+ csum)
        total = (coded.astype(jnp.float32)
                 + words.shape[0] * (overhead + plan.norm_words))
        return 32.0 * total / plan.d


def entropy_wrap(base: GradientCodec, level_probs=None) -> EntropyCodec:
    """Wrap a base codec's wire in the canonical-Huffman entropy coder.

    ``level_probs`` are magnitude-level occupancies
    (``coding.level_probabilities`` of the current grid under the
    fitted stats); ``None`` installs the cold-start table (uniform
    joint occupancies — decodable from step 0, measured ~ fixed width
    until a real fit arrives).  Only the uniform symbol stream is
    entropy-codable today: mixed-width / sparse payload families raise.
    """
    from .coding import entropy_table
    if type(base) not in (UniformCodec, EntropyCodec):
        raise ValueError(
            "entropy coding wraps the uniform symbol stream; got "
            f"{type(base).__name__} (mixed-width and sparse payloads "
            "have no single-alphabet symbol run to code)")
    lengths, codes = entropy_table(
        None if level_probs is None else np.asarray(level_probs),
        base.num_levels)
    return EntropyCodec(bucket_size=base.bucket_size,
                        norm_type=base.norm_type,
                        norm_dtype=base.norm_dtype,
                        integrity=base.integrity,
                        num_levels=base.num_levels,
                        huff_lengths=lengths, huff_codes=codes)


def entropy_codec_for_scheme(scheme) -> EntropyCodec:
    """The scheme's entropy codec with the *gaussian-prior* table.

    Before any statistics exist, normalized bucket magnitudes of an
    i.i.d.-gaussian gradient are well modelled in closed form:
    ``E r ~ 1/sqrt(bucket_size)`` under L2 normalization, ``~
    1/sqrt(2 ln bucket_size)`` under L-inf.  Fitting the table to that
    one-component prior (instead of uniform occupancies) makes
    ``codec='entropy'`` compress from step 0 on near-gaussian
    gradients; a mismatch costs only the per-bucket fallback.  The sim
    / probe paths replace this with a table fit to real occupancies.
    """
    from .coding import level_probabilities
    from .quantize import NORM_LINF
    if scheme.norm_type == NORM_LINF:
        scale = 1.0 / np.sqrt(2.0 * np.log(max(scheme.bucket_size, 2)))
    else:
        scale = 1.0 / np.sqrt(scheme.bucket_size)
    prior = TruncNormStats(
        mu=jnp.asarray([scale], jnp.float32),
        sigma=jnp.asarray([scale], jnp.float32),
        gamma=jnp.asarray([1.0], jnp.float32))
    probs = level_probabilities(
        jnp.asarray(scheme.init_levels(), jnp.float32), prior)
    return entropy_wrap(codec_for_scheme(scheme), np.asarray(probs))


def entropy_codec_from_gradient(flat, scheme, levels=None, *,
                                use_pallas: bool = False) -> EntropyCodec:
    """The probe-step protocol for the entropy wire: one gradient -> a
    fitted canonical-Huffman table.

    One fused ``bucket_stats`` sweep, the same ``stats_from_moments``
    reduction the level updates consume, then ``level_probabilities``
    of the (current) grid -> ``entropy_wrap``.  Shared by the
    simulator's ``entropy_coded`` scenario (re-run at every level-update
    milestone) and ``benchmarks/bench_entropy.py``.
    """
    from repro.kernels import ops
    from .coding import level_probabilities
    from .stats import stats_from_moments
    flat = jnp.asarray(flat).reshape(-1)
    base = codec_for_scheme(scheme)
    vb = base.bucketize(flat, base.plan(flat.shape[0]))
    norms, mu, var = ops.bucket_stats_op(vb, norm_type=scheme.norm_type,
                                         use_pallas=use_pallas)
    nb_valid = max(flat.shape[0] // scheme.bucket_size, 1)
    stats = stats_from_moments(
        mu[:nb_valid], var[:nb_valid], norms[:nb_valid],
        weighted=scheme.weighted_stats,
        max_components=scheme.max_stat_components)
    if levels is None:
        levels = scheme.init_levels()
    probs = level_probabilities(jnp.asarray(levels, jnp.float32), stats)
    return entropy_wrap(base, np.asarray(probs))


# ---------------------------------------------------------------------------
# mixed-width codec: per-bucket widths, one tensor, one wire
# ---------------------------------------------------------------------------

class _Group(NamedTuple):
    """One width group inside one segment (all static)."""

    bits: int            # scheme bits of the group's grid
    nlev: int            # 2**bits levels
    local_idx: tuple     # bucket indices local to the segment
    word_off: int        # offset into the segment's word stream
    word_cnt: int


@functools.lru_cache(maxsize=256)
def _segment_layouts(widths: tuple, shards: int,
                     bucket_size: int) -> tuple:
    """Per-segment width-group layouts: ``layouts[s]`` is a tuple of
    ``_Group`` covering segment ``s``'s buckets, words concatenated in
    ascending-width order, each group word-aligned."""
    nb = len(widths)
    snb = nb // shards
    layouts = []
    for s in range(shards):
        seg = np.asarray(widths[s * snb:(s + 1) * snb])
        groups, off = [], 0
        for b in sorted(set(seg.tolist())):
            loc = tuple(np.nonzero(seg == b)[0].tolist())
            nlev = _num_levels_for_bits(b)
            cnt = packing.packed_words(len(loc) * bucket_size,
                                      packing.wire_bits_for(nlev))
            groups.append(_Group(bits=b, nlev=nlev, local_idx=loc,
                                 word_off=off, word_cnt=cnt))
            off += cnt
        layouts.append(tuple(groups))
    return tuple(layouts)


@dataclasses.dataclass(frozen=True)
class MixedWidthCodec(GradientCodec):
    """Per-bucket wire widths inside one tensor.

    ``widths`` is a static per-bucket scheme-bits pattern, tiled
    cyclically over the plan's bucket count (a full ``nb``-length
    assignment from ``assign_mixed_widths`` is the common case).  Each
    width group encodes on ``resample_levels(levels, 2**bits)`` — the
    adaptive base grid re-sampled to the group's resolution — so level
    adaptation still happens once, on the base grid.

    The symbol stream of a segment is the concatenation of its width
    groups' fixed-width packs (ascending width, each word-aligned);
    segments are zero-padded to the plan's ``code_words`` so sharded
    collectives stay rectangular.
    """

    widths: tuple = ()

    def __post_init__(self):
        if self.integrity:
            raise ValueError(
                "MixedWidthCodec has no integrity layout (the ragged "
                "width-group stream carries no per-bucket checksum "
                "slot); use the uniform or entropy codec for "
                "fault-tolerant wires")
        if not self.widths:
            raise ValueError("MixedWidthCodec needs a non-empty widths "
                             "pattern (per-bucket scheme bits)")
        bad = [b for b in self.widths if not 1 <= int(b) <= 8]
        if bad:
            raise ValueError(f"widths must be in [1, 8], got {bad}")

    @property
    def chunkable(self) -> bool:
        return False

    @property
    def mean_scheme_bits(self) -> float:
        return float(np.mean(self.widths))

    @property
    def nominal_bits_per_coord(self) -> float:
        wire = np.mean([packing.wire_bits_for(_num_levels_for_bits(int(b)))
                        for b in self.widths])
        return float(wire) + self._norm_bits_per_coord

    def plan_buckets(self, nb: int, *, shards: int = 1,
                     d: int | None = None) -> WirePlan:
        if nb % shards:
            raise ValueError(f"nb={nb} not divisible by shards={shards}")
        if d is None:
            d = nb * self.bucket_size
        widths = tuple(int(b) for b in np.resize(
            np.asarray(self.widths, np.int64), nb))
        layouts = _segment_layouts(widths, shards, self.bucket_size)
        cw = max(sum(g.word_cnt for g in seg) for seg in layouts)
        nw = packing.norm_words(nb // shards, self.norm_dtype)
        return WirePlan(d=d, bucket_size=self.bucket_size, nb=nb,
                        shards=shards, code_words=cw, norm_words=nw,
                        widths=widths,
                        bits_per_coord=32.0 * shards * (cw + nw) / d)

    # -- helpers ----------------------------------------------------------

    def _group_levels(self, levels: jnp.ndarray, nlev: int) -> jnp.ndarray:
        return resample_levels(levels, nlev)

    def _quantize_groups(self, vb, u, levels, plan, use_pallas):
        """Quantize each width group once, globally.

        Returns (codes by width {bits: (cnt, bs)}, row index into the
        width's code block for every absolute bucket, full-order norms).
        """
        from repro.kernels import ops
        widths = np.asarray(plan.widths)
        nb = plan.nb
        codes_by, row_of = {}, np.zeros(nb, np.int64)
        norms_full = jnp.zeros((nb,), jnp.float32)
        for b in sorted(set(widths.tolist())):
            idx = np.nonzero(widths == b)[0]
            row_of[idx] = np.arange(len(idx))
            lv = self._group_levels(levels, _num_levels_for_bits(b))
            c, nrm = ops.quantize_op(vb[idx], u[idx], lv,
                                     norm_type=self.norm_type,
                                     use_pallas=use_pallas)
            codes_by[b] = c
            norms_full = norms_full.at[idx].set(nrm)
        return codes_by, row_of, norms_full

    def encode(self, vb, levels, key, plan, *, use_pallas=True):
        u = jax.random.uniform(key, vb.shape, jnp.float32)
        codes_by, row_of, norms = self._quantize_groups(
            vb, u, levels, plan, use_pallas)
        layouts = _segment_layouts(plan.widths, plan.shards,
                                   self.bucket_size)
        snb = plan.shard_nb
        rows = []
        for s, seg in enumerate(layouts):
            parts = []
            for g in seg:
                rows_g = row_of[np.asarray(g.local_idx) + s * snb]
                parts.append(packing.pack_signed(
                    codes_by[g.bits][rows_g], g.nlev))
            w = jnp.concatenate(parts) if parts else jnp.zeros(
                (0,), jnp.uint32)
            pad = plan.code_words - w.shape[0]
            if pad:
                w = jnp.concatenate([w, jnp.zeros((pad,), jnp.uint32)])
            rows.append(w)
        nrows = [packing.pack_norms(norms[s * snb:(s + 1) * snb],
                                    self.norm_dtype)
                 for s in range(plan.shards)]
        if plan.shards == 1:
            return WirePayload(words=rows[0], norm_words=nrows[0])
        return WirePayload(words=jnp.stack(rows),
                           norm_words=jnp.stack(nrows))

    def _decode_segment(self, words, norms, levels, seg, use_pallas):
        """(M, code_words) streams of ONE segment -> (M, shard_n)."""
        from repro.kernels import ops
        M = words.shape[0]
        bs = self.bucket_size
        snb = norms.shape[1]
        out = jnp.zeros((M, snb, bs), jnp.float32)
        for g in seg:
            cnt = len(g.local_idx)
            sl = jax.lax.slice_in_dim(words, g.word_off,
                                      g.word_off + g.word_cnt, axis=1)
            sym = jax.vmap(
                lambda w: packing.unpack_signed(w, cnt * bs, g.nlev))(sl)
            lv = self._group_levels(levels, g.nlev)
            loc = np.asarray(g.local_idx)
            vals = ops.dequantize_op(
                sym.reshape(M * cnt, bs), norms[:, loc].reshape(-1), lv,
                use_pallas=use_pallas)
            out = out.at[:, loc].set(vals.reshape(M, cnt, bs))
        return _buckets_to_streams(out.reshape(M * snb, bs), M)

    def decode(self, payload, levels, plan, *, shard=None,
               use_pallas=True):
        words, nwords = payload
        single = words.ndim == 1
        if single:
            words, nwords = words[None], nwords[None]
        norms = _unpack_norm_rows(nwords, plan.shard_nb, self.norm_dtype)
        layouts = _segment_layouts(plan.widths, plan.shards,
                                   self.bucket_size)
        if plan.shards == 1:
            vals = self._decode_segment(words, norms, levels, layouts[0],
                                        use_pallas)
            return vals[0] if single else vals
        if shard is None:
            # stream i carries segment i (own sharded payload)
            if words.shape[0] != plan.shards:
                raise ValueError(
                    f"diagonal decode needs {plan.shards} streams, got "
                    f"{words.shape[0]}")
            return jnp.stack([
                self._decode_segment(words[s][None], norms[s][None],
                                     levels, layouts[s], use_pallas)[0]
                for s in range(plan.shards)])
        if isinstance(shard, (int, np.integer)):
            return self._decode_segment(words, norms, levels,
                                        layouts[int(shard)], use_pallas)
        # traced segment index (SPMD rank): dispatch over static layouts
        return jax.lax.switch(
            jnp.asarray(shard, jnp.int32),
            [functools.partial(self._decode_segment, seg=seg,
                               use_pallas=use_pallas)
             for seg in layouts],
            words, norms, levels)

    def requantize(self, vb, levels, key, plan, *, chunk=0,
                   use_pallas=True):
        from repro.kernels import ops
        seg = _segment_layouts(plan.widths, plan.shards,
                               self.bucket_size)[int(chunk)]
        u = jax.random.uniform(key, vb.shape, jnp.float32)
        out = jnp.zeros_like(vb)
        for g in seg:
            loc = np.asarray(g.local_idx)
            lv = self._group_levels(levels, g.nlev)
            codes, nrm = ops.quantize_op(vb[loc], u[loc], lv,
                                         norm_type=self.norm_type,
                                         use_pallas=use_pallas)
            wn = packing.unpack_norms(
                packing.pack_norms(nrm, self.norm_dtype), nrm.shape[0],
                self.norm_dtype)
            out = out.at[loc].set(
                ops.dequantize_op(codes, wn, lv, use_pallas=use_pallas))
        return out


# ---------------------------------------------------------------------------
# width assignment: where should the bits go?
# ---------------------------------------------------------------------------

def assign_mixed_widths(
    mu, sigma, bucket_norms, base_levels,
    *,
    mean_bits: int,
    min_bits: int = 1,
    max_bits: int = 8,
) -> tuple:
    """Greedy per-bucket bit allocation under a mean-bits budget.

    For every candidate width ``b`` the expected quantization error of
    bucket ``i`` is ``||v_i||^2 * Psi_i(resample_levels(levels, 2**b))``
    (Eq. 3 with a single truncated-normal component) — closed form in
    the same sufficient statistics the adaptive schemes already fit.
    Allocation starts everywhere at ``min_bits`` and greedily grants
    +1 scheme bit to the bucket with the largest error reduction per
    wire bit until the budget ``nb * wire_bits(2**mean_bits)`` is
    spent.  High-variance / high-norm buckets end up with more levels.

    Returns a per-bucket scheme-bits tuple for ``MixedWidthCodec``.
    """
    mu = np.asarray(mu, np.float64)
    sigma = np.asarray(sigma, np.float64)
    w2 = np.asarray(bucket_norms, np.float64) ** 2
    nb = mu.shape[0]
    base = jnp.asarray(base_levels, jnp.float32)

    err = {}
    for b in range(min_bits, max_bits + 1):
        lv = resample_levels(base, _num_levels_for_bits(b))
        psi = jax.vmap(lambda m, s: expected_variance(
            TruncNormStats(mu=m[None], sigma=s[None],
                           gamma=jnp.ones((1,), jnp.float32)), lv))(
            jnp.asarray(mu, jnp.float32), jnp.asarray(sigma, jnp.float32))
        err[b] = np.asarray(psi, np.float64) * w2

    def wire(b):
        return packing.wire_bits_for(_num_levels_for_bits(b))

    budget = nb * wire(mean_bits)
    widths = np.full(nb, min_bits, np.int64)
    cost = nb * wire(min_bits)

    heap = []
    for i in range(nb):
        if min_bits < max_bits:
            dw = wire(min_bits + 1) - wire(min_bits)
            gain = (err[min_bits][i] - err[min_bits + 1][i]) / max(dw, 1)
            heapq.heappush(heap, (-gain, i, min_bits + 1, dw))
    while heap:
        neg_gain, i, b_next, dw = heapq.heappop(heap)
        if widths[i] != b_next - 1 or cost + dw > budget:
            continue
        widths[i] = b_next
        cost += dw
        if b_next < max_bits:
            dw2 = wire(b_next + 1) - wire(b_next)
            gain = (err[b_next][i] - err[b_next + 1][i]) / max(dw2, 1)
            heapq.heappush(heap, (-gain, i, b_next + 1, dw2))
    return tuple(int(b) for b in widths)


def mixed_widths_from_gradient(flat, scheme, *,
                               use_pallas: bool = False) -> tuple:
    """The probe-step protocol: one gradient -> a width assignment.

    One fused ``bucket_stats`` sweep over the (codec-aligned) buckets of
    ``flat``, a conditioning floor on sigma, then ``assign_mixed_widths``
    under the scheme's own mean-bits budget.  Shared by the simulator's
    ``mixed_width`` scenario and ``benchmarks/bench_mixed_bits.py`` so
    the committed benchmark measures exactly what the scenario runs.
    """
    from repro.kernels import ops
    flat = jnp.asarray(flat).reshape(-1)
    codec = codec_for_scheme(scheme)
    vb = codec.bucketize(flat, codec.plan(flat.shape[0]))
    norms, mu, var = ops.bucket_stats_op(vb, norm_type=scheme.norm_type,
                                         use_pallas=use_pallas)
    # alignment padding is all-zero; keep only fully-populated buckets
    nb_valid = max(flat.shape[0] // scheme.bucket_size, 1)
    return assign_mixed_widths(
        np.asarray(mu[:nb_valid]),
        np.clip(np.sqrt(np.asarray(var[:nb_valid])), 1e-4, None),
        np.asarray(norms[:nb_valid]),
        scheme.init_levels(), mean_bits=scheme.bits)


# ---------------------------------------------------------------------------
# factories
# ---------------------------------------------------------------------------

def codec_for_scheme(scheme) -> UniformCodec:
    """The production codec of a ``QuantScheme``: its global width."""
    return UniformCodec(num_levels=scheme.num_levels,
                        bucket_size=scheme.bucket_size,
                        norm_type=scheme.norm_type,
                        norm_dtype=scheme.norm_dtype)


def requant_codec(codec: GradientCodec, bits: int) -> UniformCodec:
    """The fixed re-quantization grid layered on top of a base codec:
    uniform ``bits``-bit levels under L-inf bucket norms, same bucketing
    and norm side-channel as the base.  Used by the two_phase broadcast
    hop and the param_server downlink."""
    from .quantize import NORM_LINF
    return UniformCodec(num_levels=_num_levels_for_bits(bits),
                        bucket_size=codec.bucket_size,
                        norm_type=NORM_LINF,
                        norm_dtype=codec.norm_dtype,
                        integrity=codec.integrity)


def make_codec(scheme, kind: str = "uniform",
               widths: tuple = (), *,
               integrity: bool = False) -> GradientCodec:
    """Codec selection as configured on ``TrainConfig`` / sim scenarios.

    ``kind='mixed_width'`` with an empty ``widths`` falls back to the
    budget-neutral ``(bits-1, bits+1)`` alternating pattern: wire widths
    are ``scheme_bits + 1``, so the two-bucket cycle ships exactly the
    same mean bits/coordinate as the uniform codec at ``scheme.bits``.
    At the range edges (bits 1 or 8), where no symmetric cycle exists,
    the fallback degenerates to the uniform-width ``(bits,)`` pattern —
    still budget-exact.

    ``kind='entropy[:base]'`` wraps the base codec (only ``uniform``
    today) in the canonical-Huffman entropy coder with the
    gaussian-prior cold-start table (``entropy_codec_for_scheme``) —
    decodable and already compressing from step 0; a table fitted to
    real occupancies is installed by the probe protocols
    (``entropy_codec_from_gradient`` / the sim's ``entropy_coded``
    scenario).
    """
    if kind == "uniform":
        codec = codec_for_scheme(scheme)
        if integrity:
            codec = dataclasses.replace(codec, integrity=True)
        return codec
    if kind == "entropy" or kind.startswith("entropy:"):
        base_kind = kind.partition(":")[2] or "uniform"
        if base_kind != "uniform":
            raise ValueError(
                f"entropy coding supports base codec 'uniform', got "
                f"{base_kind!r} (mixed-width/sparse symbol streams are "
                "not single-alphabet)")
        codec = entropy_codec_for_scheme(scheme)
        if integrity:
            codec = dataclasses.replace(codec, integrity=True)
        return codec
    if kind == "mixed_width":
        if integrity:
            raise ValueError(
                "integrity=True is not supported for codec kind "
                "'mixed_width' (no per-bucket checksum slot in the "
                "ragged width-group stream)")
        if not widths:
            if scheme.bits - 1 < 1 or scheme.bits + 1 > 8:
                widths = (scheme.bits,)
            else:
                widths = (scheme.bits - 1, scheme.bits + 1)
        return MixedWidthCodec(bucket_size=scheme.bucket_size,
                               norm_type=scheme.norm_type,
                               norm_dtype=scheme.norm_dtype,
                               widths=tuple(int(b) for b in widths))
    raise ValueError(f"unknown codec kind {kind!r}; "
                     "known: ('uniform', 'mixed_width', "
                     "'entropy[:base]')")
