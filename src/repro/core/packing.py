"""Bit-packing of b-bit integer codes into uint32 words.

TPU adaptation of the usual GPU warp-shuffle packers: everything is a
vectorized shift/or over a trailing "codes-per-word" axis, which lowers to
plain VPU integer ops (and is reused inside Pallas kernels — the qattn
decode kernel calls `unpack_bits_T` on its VMEM word block and the encode
kernel calls `pack_bits` before its store).

Layout: the last axis of `codes` (length m) is treated as a flat
little-endian bitstream of m*b bits, stored in ceil(m*b/32) uint32 words.
When m*b is not a multiple of 32 the tail of the last word is zero padding
(at most 31 bits per vector — the only storage overhead of the format).

Norm codes use a coarser two-per-byte nibble scheme (`pack_nibbles`): byte j
holds code[j] in its low nibble and code[j + m/2] in its high nibble
("split-half" layout), so unpacking is a concatenation of two masked views
instead of an interleave — the cheap direction for TPU lane layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def packed_words(m: int, bits: int) -> int:
    """uint32 words needed for m b-bit codes (tail-padded to a word)."""
    if bits < 1 or bits > 32:
        raise ValueError(f"bits={bits} out of range [1, 32]")
    return -(-m * bits // 32)


def pack_bits(codes: jax.Array, bits: int) -> jax.Array:
    """Pack int codes (..., m) in [0, 2^bits) into uint32 (..., ceil(m*b/32)).

    Implementation: expand each code into its `bits` bits, reshape the flat
    bitstream into words, and recombine. O(bits) vector ops, fully shape
    static. The bitstream is little-endian: code i occupies bits
    [i*b, (i+1)*b), bit k of a word is that word's k-th stream bit.
    """
    m = codes.shape[-1]
    n_words = packed_words(m, bits)
    c = codes.astype(jnp.uint32)
    shifts = jnp.arange(bits, dtype=jnp.uint32)
    # (..., m, bits) little-endian bits of each code
    bits_arr = (c[..., None] >> shifts) & jnp.uint32(1)
    flat = bits_arr.reshape(*codes.shape[:-1], m * bits)
    pad = n_words * 32 - m * bits
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((*flat.shape[:-1], pad), flat.dtype)], axis=-1)
    flat = flat.reshape(*codes.shape[:-1], n_words, 32)
    word_shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(flat << word_shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, bits: int, m: int) -> jax.Array:
    """Inverse of pack_bits -> int32 (..., m).

    Expands every stream bit (O(bits) vector ops per code) and recombines
    each code's `bits` consecutive stream bits.
    """
    n_words = packed_words(m, bits)
    if words.shape[-1] != n_words:
        raise ValueError(f"expected {n_words} words, got {words.shape[-1]}")
    word_shifts = jnp.arange(32, dtype=jnp.uint32)
    bits_arr = (words[..., None] >> word_shifts) & jnp.uint32(1)
    flat = bits_arr.reshape(*words.shape[:-1], n_words * 32)
    flat = flat[..., : m * bits].reshape(*words.shape[:-1], m, bits)
    shifts = jnp.arange(bits, dtype=jnp.uint32)
    return jnp.sum(flat << shifts, axis=-1, dtype=jnp.uint32).astype(jnp.int32)


def unpack_bits_T(words: jax.Array, bits: int, m: int) -> jax.Array:
    """unpack_bits with a transposed contract: (bt, words) -> (m, bt).

    The code axis LEADS the output — the token-minor layout the qattn
    kernels dequant in. The word block is transposed once; code i is then
    its word row `i*bits // 32` shifted right by `i*bits % 32`, OR-ed with
    the next word row when the code straddles a word boundary. Every slice
    and shift is static, so the unpack is whole-row integer ops plus one
    row concatenate: no gather, no reshape of the lane axis and no
    unsigned reduction, which is what the TPU kernel compiler accepts at
    every width. Bitwise identical to `unpack_bits(words, bits, m).T`.
    """
    if words.ndim != 2:
        raise ValueError(f"unpack_bits_T needs 2-D words, got {words.shape}")
    n_words = packed_words(m, bits)
    if words.shape[-1] != n_words:
        raise ValueError(f"expected {n_words} words, got {words.shape[-1]}")
    w = jax.lax.bitcast_convert_type(words, jnp.int32).T  # (n_words, bt)
    rows = []
    for i in range(m):
        lo, sh = divmod(i * bits, 32)
        row = jax.lax.shift_right_logical(w[lo:lo + 1], jnp.int32(sh))
        if sh + bits > 32:  # straddles into the next word
            row = row | jax.lax.shift_left(w[lo + 1:lo + 2],
                                           jnp.int32(32 - sh))
        rows.append(row & jnp.int32((1 << bits) - 1))
    return jnp.concatenate(rows, axis=0)


def pack_nibbles(codes: jax.Array) -> jax.Array:
    """Pack codes (..., m) in [0, 16) two-per-byte -> uint8 (..., m/2).

    Split-half layout: byte j = codes[j] | codes[j + m/2] << 4, so the
    unpack is concat(lo, hi) — no interleave. m must be even.
    """
    m = codes.shape[-1]
    if m % 2:
        raise ValueError(f"nibble packing needs an even code count, got {m}")
    c = codes.astype(jnp.uint8)
    half = m // 2
    return c[..., :half] | (c[..., half:] << 4)


def unpack_nibbles(bytes_arr: jax.Array, m: int) -> jax.Array:
    """Inverse of pack_nibbles -> int32 (..., m)."""
    if bytes_arr.shape[-1] * 2 != m:
        raise ValueError(
            f"expected {m // 2} bytes for m={m}, got {bytes_arr.shape[-1]}")
    # widen first: the TPU kernel compiler has no 8-bit shifts
    b = bytes_arr.astype(jnp.int32)
    return jnp.concatenate([b & 0xF, b >> 4], axis=-1)


def storage_bits_per_code(bits: int, mode: str) -> float:
    """Physical bits per stored code under a storage mode.

    "uint8" with bits > 8 reports the uint16 container that
    `narrow_dtype` (and therefore `QuantizerConfig.index_dtype` /
    `init_quant_cache`) actually allocates — the fallback is implemented,
    not aspirational; `tests/test_bitpack.py` pins the agreement. Widths
    beyond 16 have no narrow container and raise.
    """
    if bits < 1:
        raise ValueError(f"bits={bits} must be >= 1")
    if mode == "bitpack":
        if bits > 32:
            raise ValueError(f"bits={bits} exceeds the uint32 word")
        return float(bits)
    if mode == "uint8":
        if bits > 16:
            raise ValueError(
                f"bits={bits} exceeds the uint16 fallback container; "
                "use storage='bitpack'")
        if bits > 8:
            return 16.0  # uint16 fallback (matches narrow_dtype)
        return 8.0
    if mode == "uint16":
        if bits > 16:
            raise ValueError(f"bits={bits} does not fit uint16")
        return 16.0
    raise ValueError(f"unknown storage mode {mode}")


def norm_storage_bits(bits: int, mode: str) -> float:
    """Physical bits per stored *norm* code.

    Norm codes always live in uint8 containers; bitpack mode packs them
    two-per-byte when they fit a nibble (the paper's 4-bit log-space V
    norms), i.e. nibble granularity rather than exact-bit granularity.
    """
    if bits > 8:
        raise ValueError(f"norm codes wider than 8 bits unsupported ({bits})")
    if mode == "bitpack" and bits <= 4:
        return 4.0
    return 8.0


def token_payload_bytes(n_pairs: int, index_bits: int,
                        norm_bits: int | None, mode: str = "bitpack") -> int:
    """Physical payload bytes one stored token row occupies for ONE of K or V.

    Sums the actual array widths the cache allocates: the packed uint32 word
    stream (or narrow container codes), the norm-code bytes (nibble-packed
    when they fit), and the per-vector f32 min/max pair. fp32 norms
    (norm_bits None) store n_pairs f32 values and no min/max payload is
    *added* — the cache still allocates the (…, 1) rmin/rmax arrays, counted
    here so the number matches `cache_physical_bytes` exactly. This is the
    unit the page-pool sizing math (serving/pages.py, ARCHITECTURE.md) is
    built on.
    """
    if mode == "bitpack":
        idx = 4 * packed_words(n_pairs, index_bits)
    else:
        idx = n_pairs * np.dtype(narrow_dtype(index_bits)).itemsize
    if norm_bits is None:
        nrm = 4 * n_pairs  # fp32 norms
    elif mode == "bitpack" and norm_bits <= 4 and n_pairs % 2 == 0:
        nrm = n_pairs // 2  # two-per-byte nibbles
    else:
        nrm = n_pairs  # one uint8 per code
    return idx + nrm + 8  # + f32 rmin/rmax


def narrow_dtype(bits: int) -> np.dtype:
    """Smallest unsigned container dtype for b-bit codes."""
    if bits <= 8:
        return np.dtype(np.uint8)
    if bits <= 16:
        return np.dtype(np.uint16)
    return np.dtype(np.uint32)
