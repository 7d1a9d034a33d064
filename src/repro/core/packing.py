"""k-bit <-> uint32 bit packing for the collective wire format.

Signed level indices in [-(L-1), +(L-1)] are biased to unsigned symbols
in [0, 2L-2] and packed ``wire_bits`` per symbol into a dense uint32
stream.  This is what actually travels over ICI in the quantized
allreduce: ``ceil(n * wire_bits / 32)`` words instead of n fp32 words.

The packer is fully vectorized (two scatter-adds per stream — one for the
low fragment of each symbol, one for the fragment spilling into the next
word), so it lowers cleanly under jit/shard_map on any backend.
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


def unpack(words: jnp.ndarray, n: int, bits: int) -> jnp.ndarray:
    """Inverse of pack: recover n unsigned symbols (int32)."""
    words = jnp.concatenate(
        [words.astype(jnp.uint32), jnp.zeros((1,), jnp.uint32)])
    i = jnp.arange(n, dtype=jnp.uint32)
    bitpos = i * jnp.uint32(bits)
    widx = (bitpos >> 5).astype(jnp.int32)
    off = bitpos & jnp.uint32(31)
    lo = words[widx] >> off
    spill_shift = jnp.where(off > 0, jnp.uint32(32) - off, jnp.uint32(31))
    hi = jnp.where(off > 0, words[widx + 1] << spill_shift, jnp.uint32(0))
    mask = jnp.uint32((1 << bits) - 1)
    return ((lo | hi) & mask).astype(jnp.int32)


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
