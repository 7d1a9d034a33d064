"""k-bit <-> uint32 bit packing for the collective wire format.

Signed level indices in [-(L-1), +(L-1)] are biased to unsigned symbols
in [0, 2L-2] and packed ``wire_bits`` per symbol into a dense uint32
stream.  This is what actually travels over ICI in the quantized
allreduce: ``ceil(n * wire_bits / 32)`` words instead of n fp32 words.

Symbol ``i`` sits at bit ``i * wire_bits`` of the little-endian word
stream, so the word and the shift of every symbol are static functions
of its position.  ``unpack`` uses only that: static shifts, masks and
reshapes, no data-dependent gather.  ``pack`` still scatter-adds twice
per stream (the low fragment of each symbol, and the fragment spilling
into the next word).  Both lower under jit/shard_map on any backend, but
the TPU runs a gather or scatter over every symbol element by element:
a gather over 452.7M symbols took 10 s on a v5e.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def wire_bits_for(num_levels: int) -> int:
    """Bits per symbol for signed indices over `num_levels` magnitudes.

    Symbols: 2*num_levels - 1 (zero is shared between signs).
    """
    n_sym = 2 * num_levels - 1
    return max(1, math.ceil(math.log2(n_sym)))


def packed_words(n: int, bits: int) -> int:
    return -(-(n * bits) // 32)


def pack(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack unsigned symbols (int32 in [0, 2**bits)) into uint32 words."""
    codes = codes.reshape(-1).astype(jnp.uint32) & jnp.uint32((1 << bits) - 1)
    n = codes.shape[0]
    nwords = packed_words(n, bits)
    i = jnp.arange(n, dtype=jnp.uint32)
    bitpos = i * jnp.uint32(bits)
    widx = (bitpos >> 5).astype(jnp.int32)
    off = bitpos & jnp.uint32(31)
    lo = (codes << off).astype(jnp.uint32)
    # fragment spilling into the next word; shift (32-off) is invalid for
    # off == 0, so route through a masked shift.
    spill_shift = jnp.where(off > 0, jnp.uint32(32) - off, jnp.uint32(31))
    hi = jnp.where(off > 0, codes >> spill_shift, jnp.uint32(0))
    # scatter into nwords+1 (spill slot), then drop the spill word — it is
    # always zero when the stream length is exact.  The word indices rise
    # with the symbol index; told so, the TPU compiler takes 2 s instead
    # of 35 s at 113M symbols when ``bits`` does not divide 32.
    out = jnp.zeros((nwords + 1,), jnp.uint32)
    out = out.at[widx].add(lo, mode="promise_in_bounds",
                           indices_are_sorted=True)
    out = out.at[widx + 1].add(hi, mode="promise_in_bounds",
                               indices_are_sorted=True)
    return out[:nwords]


def _fit(words: jnp.ndarray, m: int) -> jnp.ndarray:
    """The first ``m`` words, zero-padded if the stream is shorter."""
    if words.shape[0] >= m:
        return words[:m]
    return jnp.concatenate([words, jnp.zeros((m - words.shape[0],),
                                             jnp.uint32)])


def unpack(words: jnp.ndarray, n: int, bits: int) -> jnp.ndarray:
    """Inverse of pack: recover n unsigned symbols (int32).

    Where ``bits`` divides 32 each word holds ``32 // bits`` whole
    symbols: shift every word by each slot's offset.  Otherwise 32
    symbols fill exactly ``bits`` words: each of a group's 32 slots comes
    from a static word and offset, OR-ing in the spill from the next word
    where the slot crosses one.  Bits past symbol ``n`` are ignored.
    """
    words = words.astype(jnp.uint32)
    mask = jnp.uint32((1 << bits) - 1)
    if 32 % bits == 0:
        per = 32 // bits
        shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(bits)
        sym = _fit(words, -(-n // per))[:, None] >> shifts
    else:
        # Rows of 128 groups (4096 symbols), built word-major: word j of
        # every group is a slice of the major dimension and the slots
        # stack along it.  A (groups, bits) or (groups, 32) array would
        # have a narrow minor dimension, which the TPU pads to 128 lanes.
        rows = -(-n // 4096)
        v = _fit(words, rows * 128 * bits).reshape(rows, 128 * bits).T
        v = v.reshape(128, bits, rows)
        slots = []
        for k in range(32):
            wi, off = (k * bits) >> 5, (k * bits) & 31
            s = v[:, wi] >> off
            if off + bits > 32:
                s = s | (v[:, wi + 1] << (32 - off))
            slots.append(s)
        sym = jnp.stack(slots, axis=1).reshape(4096, rows).T
    return (sym & mask).reshape(-1)[:n].astype(jnp.int32)


def bias_codes(signed_codes: jnp.ndarray, num_levels: int) -> jnp.ndarray:
    """Signed index in [-(L-1), L-1] -> unsigned symbol in [0, 2L-2]."""
    return (signed_codes.astype(jnp.int32) + (num_levels - 1)).astype(jnp.int32)


def unbias_codes(symbols: jnp.ndarray, num_levels: int) -> jnp.ndarray:
    return symbols.astype(jnp.int32) - (num_levels - 1)


NORM_DTYPES = ("float32", "float16")


def norm_words(nb: int, norm_dtype: str = "float32") -> int:
    """uint32 words occupied by ``nb`` packed bucket norms."""
    if norm_dtype == "float32":
        return nb
    if norm_dtype == "float16":
        return -(-nb // 2)
    raise ValueError(f"unknown norm_dtype {norm_dtype!r}; known: {NORM_DTYPES}")


def pack_norms(norms: jnp.ndarray, norm_dtype: str = "float32") -> jnp.ndarray:
    """Bucket norms -> dense uint32 word stream for the wire.

    ``float32`` is a pure bitcast (1 word/norm).  ``float16`` halves the
    norm side-channel: norms are rounded to fp16 (gradient bucket norms
    sit far inside fp16's range; the ~2^-11 relative step is below
    quantization noise at every practical width) and packed two per word,
    little-end first.
    """
    norms = norms.reshape(-1)
    if norm_dtype == "float32":
        return jax.lax.bitcast_convert_type(norms.astype(jnp.float32),
                                            jnp.uint32)
    if norm_dtype == "float16":
        h = jax.lax.bitcast_convert_type(norms.astype(jnp.float16),
                                         jnp.uint16).astype(jnp.uint32)
        nb = h.shape[0]
        if nb % 2:
            h = jnp.concatenate([h, jnp.zeros((1,), jnp.uint32)])
        pair = h.reshape(-1, 2)
        return pair[:, 0] | (pair[:, 1] << jnp.uint32(16))
    raise ValueError(f"unknown norm_dtype {norm_dtype!r}; known: {NORM_DTYPES}")


def unpack_norms(words: jnp.ndarray, nb: int,
                 norm_dtype: str = "float32") -> jnp.ndarray:
    """Inverse of ``pack_norms``: recover ``nb`` fp32 bucket norms
    (fp16 norms are upcast; the fp16 rounding itself is lossy by design)."""
    if norm_dtype == "float32":
        return jax.lax.bitcast_convert_type(words, jnp.float32)[:nb]
    if norm_dtype == "float16":
        lo = (words & jnp.uint32(0xFFFF)).astype(jnp.uint16)
        hi = (words >> jnp.uint32(16)).astype(jnp.uint16)
        h = jnp.stack([lo, hi], axis=-1).reshape(-1)[:nb]
        return jax.lax.bitcast_convert_type(h, jnp.float16).astype(jnp.float32)
    raise ValueError(f"unknown norm_dtype {norm_dtype!r}; known: {NORM_DTYPES}")


# ---------------------------------------------------------------------------
# wire integrity words
# ---------------------------------------------------------------------------

# Mixing constants for the per-bucket integrity word (odd, so every
# per-position multiplier is invertible mod 2^32: any single-symbol
# change provably changes the weighted sum).
_CSUM_SYM_MULT = 0x9E3779B1   # golden-ratio odd constant
_CSUM_NORM_MULT = 0x85EBCA6B  # Murmur3 fmix constant
# Nonzero offset so the ALL-ZERO payload (a dropped/zeroed wire row:
# symbols 0, norm bits 0, stored checksum word 0) does NOT checksum to
# 0 — zero rows are detected as invalid instead of decoding as a
# "valid" zero bucket.
_CSUM_OFFSET = 0x6A09E667


def norm_bit_patterns(norms: jnp.ndarray,
                      norm_dtype: str = "float32") -> jnp.ndarray:
    """Per-bucket wire bit pattern of each norm, as uint32.

    This is what the integrity word covers for the norm side-channel:
    the exact bits that travel (fp16 norms contribute their 16-bit
    pattern), so recomputing it from DECODED norms matches iff the norm
    words arrived intact.  fp32 decoded norms round-trip to fp16
    exactly (they were produced by an exact upcast).
    """
    norms = norms.reshape(-1)
    if norm_dtype == "float32":
        return jax.lax.bitcast_convert_type(norms.astype(jnp.float32),
                                            jnp.uint32)
    if norm_dtype == "float16":
        return jax.lax.bitcast_convert_type(
            norms.astype(jnp.float16), jnp.uint16).astype(jnp.uint32)
    raise ValueError(f"unknown norm_dtype {norm_dtype!r}; known: {NORM_DTYPES}")


def bucket_checksums(symbols: jnp.ndarray,
                     norm_bits: jnp.ndarray) -> jnp.ndarray:
    """(nb, bucket_size) unsigned symbols + (nb,) norm bit patterns ->
    (nb,) uint32 integrity words.

    A position-weighted sum with distinct ODD multipliers per
    coordinate (so any single-symbol change flips the sum with
    certainty; independent multi-word corruption escapes with
    probability ~2^-32), mixed with an xorshift-multiply avalanche.
    Fully vectorized — no scan — so the integrity pass costs one
    elementwise multiply-reduce per bucket.
    """
    sym = symbols.astype(jnp.uint32)
    bs = sym.shape[-1]
    i = jnp.arange(bs, dtype=jnp.uint32)
    mult = (jnp.uint32(2) * i + jnp.uint32(1)) * jnp.uint32(_CSUM_SYM_MULT)
    h = jnp.sum(sym * mult[None, :], axis=-1, dtype=jnp.uint32)
    h = h + norm_bits.astype(jnp.uint32) * jnp.uint32(_CSUM_NORM_MULT)
    h = h + jnp.uint32(_CSUM_OFFSET)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    return h


def pack_signed(signed_codes: jnp.ndarray, num_levels: int) -> jnp.ndarray:
    bits = wire_bits_for(num_levels)
    return pack(bias_codes(signed_codes, num_levels), bits)


def unpack_signed(words: jnp.ndarray, n: int, num_levels: int) -> jnp.ndarray:
    bits = wire_bits_for(num_levels)
    return unbias_codes(unpack(words, n, bits), num_levels)
