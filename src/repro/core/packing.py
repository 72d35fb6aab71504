"""Bit-packing of n-bit codes into uint32 words.

Two layouts:

* Storage (``pack_codes``): k = 32 // n codes per word along the row,
  code j of a word occupying bits [j*n, (j+1)*n); rows padded to a
  multiple of k with zeros. ``ICQPacked`` keeps its codes this way, and
  the v2 gap-symbol stream is packed this way too.

* Kernel bit planes (``pack_planes``): what the Pallas kernels read. A
  (R, K) code matrix is stored transposed, (n * K / 32, R) uint32: the
  lane (minor) axis is the row R, so a block of any bit width is a
  whole number of (8, 128) uint32 tiles. Along K, every 256 columns
  form one group of 8 sublane words per bit plane:

      word row g*8n + p*8 + s, bit j  <->  bit p of code[r, g*256 + 8j + s]

  Unpacking bit j of an (8, R) word slab yields the 8 consecutive K rows
  [g*256 + 8j, g*256 + 8j + 8), so a kernel rebuilds a (K, R) tile with
  shifts, masks and 8-row-aligned stacking only. Exactly n bits per
  weight; K must be a multiple of ``PLANE_ROWS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: K positions covered by one 8-sublane word group of one bit plane
PLANE_ROWS = 256


def codes_per_word(n_bits: int) -> int:
    if not 1 <= n_bits <= 16:
        raise ValueError(f"n_bits must be in [1, 16], got {n_bits}")
    return 32 // n_bits


def packed_width(length: int, n_bits: int) -> int:
    k = codes_per_word(n_bits)
    return -(-length // k)


def pack_codes(codes: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Pack (..., L) integer codes in [0, 2^n) into (..., ceil(L/k)) uint32."""
    k = codes_per_word(n_bits)
    L = codes.shape[-1]
    pad = (-L) % k
    codes = jnp.asarray(codes, dtype=jnp.uint32)
    if pad:
        codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, pad)])
    grouped = codes.reshape(codes.shape[:-1] + (-1, k))
    shifts = jnp.arange(k, dtype=jnp.uint32) * n_bits
    # disjoint bit ranges: sum == bitwise or
    return (grouped << shifts).sum(axis=-1).astype(jnp.uint32)


def unpack_codes(words: jnp.ndarray, n_bits: int, length: int) -> jnp.ndarray:
    """Unpack uint32 words back to (..., length) uint32 codes."""
    k = codes_per_word(n_bits)
    mask = jnp.uint32((1 << n_bits) - 1)
    shifts = jnp.arange(k, dtype=jnp.uint32) * n_bits
    expanded = (words[..., None] >> shifts) & mask
    flat = expanded.reshape(words.shape[:-1] + (-1,))
    return flat[..., :length]


def pack_symbols_np(symbols: np.ndarray, b: int) -> np.ndarray:
    """Pack (rows, s_max) b-bit gap symbols into uint32 words (v2 runtime).

    Same little-endian-in-word field layout as ``pack_codes`` (the kernels
    unpack both with one shift/mask helper); symbols are stored value-1 so
    they fit exactly b bits. Rows with no symbols still get one zero word
    so downstream block shapes never collapse to width 0.
    """
    symbols = np.asarray(symbols)
    if symbols.shape[-1] == 0:
        return np.zeros(symbols.shape[:-1] + (1,), dtype=np.uint32)
    return pack_codes_np(symbols.astype(np.uint32), b)


def symbol_cols(words_width: int, b: int) -> int:
    """Unpacked column count of a packed symbol tensor of given width."""
    return words_width * codes_per_word(b)


@functools.partial(jax.jit, static_argnums=1)
def pack_planes(codes: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(..., R, K) codes in [0, 2^n), K % 256 == 0 -> (..., n*K/32, R)
    uint32 kernel bit planes (see module doc)."""
    *lead, R, K = codes.shape
    if K % PLANE_ROWS:
        raise ValueError(f"K={K} is not a multiple of {PLANE_ROWS}")
    G = K // PLANE_ROWS
    c = jnp.asarray(codes, jnp.uint32).reshape(*lead, R, G, 1, 32, 8)
    p = jnp.arange(n_bits, dtype=jnp.uint32)[:, None, None]
    j = jnp.arange(32, dtype=jnp.uint32)[:, None]
    # disjoint bits: the sum over j is a bitwise or
    words = (((c >> p) & 1) << j).sum(axis=-2, dtype=jnp.uint32)
    words = words.reshape(*lead, R, G * n_bits * 8)        # (g, p, s) order
    return jnp.swapaxes(words, -1, -2)


@functools.partial(jax.jit, static_argnums=1)
def unpack_planes(words: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(..., n*K/32, R) kernel bit planes -> (..., R, K) int32 codes."""
    *lead, W, R = words.shape
    G = W // (8 * n_bits)
    w = jnp.swapaxes(words, -1, -2).reshape(*lead, R, G, n_bits, 1, 8)
    j = jnp.arange(32, dtype=jnp.uint32)[:, None]
    p = jnp.arange(n_bits, dtype=jnp.uint32)[:, None, None]
    codes = (((w >> j) & 1) << p).sum(axis=-3, dtype=jnp.uint32)
    return codes.reshape(*lead, R, G * PLANE_ROWS).astype(jnp.int32)


def pack_codes_np(codes: np.ndarray, n_bits: int) -> np.ndarray:
    """Host-side numpy packer (pack time)."""
    k = codes_per_word(n_bits)
    L = codes.shape[-1]
    pad = (-L) % k
    codes = np.asarray(codes, dtype=np.uint32)
    if pad:
        codes = np.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, pad)])
    grouped = codes.reshape(codes.shape[:-1] + (-1, k))
    shifts = (np.arange(k, dtype=np.uint32) * n_bits)
    return np.bitwise_or.reduce(grouped << shifts, axis=-1).astype(np.uint32)
