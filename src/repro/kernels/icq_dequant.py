"""Pallas TPU kernel: ICQuant tile dequantization (+ the shared tile math).

Every prepared weight tensor is stored transposed (``core/packing.py``
bit planes): the lane axis is the weight row (d_out), the sublane axis
runs along d_in. A kernel therefore rebuilds a transposed weight tile
``W^T[k, r]`` of shape (BK, BN) in VMEM:

  * codes: n bit planes, unpacked with shifts and masks, 8 sublanes at a
    time (no minor-dim reshape);
  * selector: a 1-bit plane in the same layout, which v1 reads and v2
    builds from its tile of the checkpointed gap stream
    (``_tile_selector``);
  * codebook: a binary tree of selects over the 2^(n+1) per-row levels
    on the VPU — each weight is one of its row's stored levels, exactly.

Grid of ``dequant_padded[_v2]``: (L, pn / BN, pk / BK) over a stack of L
matrices; the output is the transposed reconstruction (L, pk, pn) f32,
still padded. ``backend.prepare`` builds the inputs; ``interpret`` comes
from kernels.platform (compiled on TPU, the interpreter elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import PLANE_ROWS


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _as_i32(words: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _srl(x: jnp.ndarray, s) -> jnp.ndarray:
    return jax.lax.shift_right_logical(x, jnp.asarray(s, x.dtype))


def _unpack_planes(words: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(n*BK/32, BN) uint32 bit-plane words -> (BK, BN) int32 codes."""
    G = words.shape[0] // (8 * n_bits)
    BN = words.shape[1]
    w = _as_i32(words).reshape(G, n_bits, 8, BN)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 8, BN), 1)
    code = None
    for p in range(n_bits):
        bit = _srl(w[:, p][:, None], j) & 1                  # (G, 32, 8, BN)
        code = bit if code is None else code | (bit << p)
    return code.reshape(G * PLANE_ROWS, BN)


def _codebook_select(idx: jnp.ndarray, cb: jnp.ndarray) -> jnp.ndarray:
    """idx (BK, BN) int32 in [0, C); cb (C, BN) per-row levels ->
    (BK, BN) f32. A binary tree of selects on the bits of idx: the
    result is a stored level bit for bit (no MXU rounding)."""
    cb = cb.astype(jnp.float32)
    levels = [cb[c:c + 1, :] for c in range(cb.shape[0])]
    bit = 0
    while len(levels) > 1:
        hi = (_srl(idx, bit) & 1) == 1
        levels = [jnp.where(hi, levels[2 * i + 1], levels[2 * i])
                  for i in range(len(levels) // 2)]
        bit += 1
    return jnp.broadcast_to(levels[0], idx.shape)


def _row_at(x: jnp.ndarray, t) -> jnp.ndarray:
    """Row t (traced) of a small (R, BN) int table as (1, BN): an
    iota-masked sum, since Mosaic has no dynamic minor-dim slice."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == t, x.astype(jnp.int32), 0), axis=0,
                   keepdims=True)


def _exclusive_prefix_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum down the rows of a non-negative (W, BN) int32
    array, exact: a strictly-lower-triangular 0/1 matmul over 8-bit
    chunks of x, each exact in bf16 with f32 accumulation (sums < 2^24)."""
    W = x.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
             > jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
             ).astype(jnp.bfloat16)
    out = jnp.zeros(x.shape, jnp.int32)
    for shift in range(0, 24, 8):
        chunk = (_srl(x, shift) & 255).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            lower, chunk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = out + (part.astype(jnp.int32) << shift)
    return out


def _tile_selector(syms: jnp.ndarray, offs: jnp.ndarray, dbase: jnp.ndarray,
                   kk, *, b: int, block_k: int) -> jnp.ndarray:
    """Checkpointed gap stream -> (BK, BN) 0/1 selector of K tile kk.

    syms:  (SW, BN) uint32 — each row's packed b-bit gap symbols down
           the sublanes (storage packing: symbol w*k_b + j in field j of
           word w; value-1 encoding, all-ones = escape flag).
    offs:  (T+1, BN) — symbol offset at every tile boundary (last row =
           the row's symbol count).
    dbase: (T, BN) — kk*block_k - dbase[kk] is the position consumed
           before the tile's first symbol.

    Only the tile's symbols [offs[kk], offs[kk+1]) contribute: their
    increments (escape = 2^b - 1 positions, no emission) are prefix
    summed exactly on top of the checkpoint base. Each emitted position
    sets one bit of a (BK/32, BN) 1-bit plane in ``_unpack_planes``'
    layout, which is unpacked once; no dense bitmap reaches HBM.
    """
    k_b = 32 // b
    m = (1 << b) - 1
    SW, BN = syms.shape
    o0, o1 = _row_at(offs, kk), _row_at(offs, kk + 1)         # (1, BN)
    d0 = _row_at(dbase, kk)
    w = _as_i32(syms)
    s0 = jax.lax.broadcasted_iota(jnp.int32, (SW, BN), 0) * k_b
    incs, emits = [], []
    for j in range(k_b):
        sym = _srl(w, j * b) & m
        inside = (s0 + j >= o0) & (s0 + j < o1)
        incs.append(jnp.where(inside, jnp.where(sym == m, m, sym + 1), 0))
        emits.append(inside & (sym != m))
    pre = []                                   # inclusive, within a word
    for inc in incs:
        pre.append(inc if not pre else pre[-1] + inc)
    base = _exclusive_prefix_rows(pre[-1]) - d0 - 1

    # Tile row k is bit (k // 8) % 32 of plane word (k // 256) * 8 + k % 8.
    # A miss (-1) or a position outside [0, BK) maps to a word outside
    # [0, BK/32), so it sets nothing.
    word, bit = [], []
    for j in range(k_b):
        rel = jnp.where(emits[j], base + pre[j], -1)          # -1: no hit
        word.append(((rel >> 8) << 3) | (rel & 7))
        bit.append(jnp.left_shift(jnp.int32(1), (rel >> 3) & 31))
    n_words = block_k // 32
    plane_rows = jax.lax.broadcasted_iota(jnp.int32, (n_words, BN), 0)
    plane = jnp.zeros((n_words, BN), jnp.int32)
    for v in range(n_words):
        # a row's emitted positions are distinct: the sum is the OR
        hits = jnp.where(word[0] == v, bit[0], 0)
        for j in range(1, k_b):
            hits = hits + jnp.where(word[j] == v, bit[j], 0)
        plane = plane | jnp.where(
            plane_rows == v, jnp.sum(hits, axis=0, keepdims=True), 0)
    return _unpack_planes(plane, 1)


def _dequant_kernel(codes_ref, bitmap_ref, cb_ref, out_ref, *, n_bits: int):
    sel = _unpack_planes(bitmap_ref[...], 1)
    idx = (sel << n_bits) + _unpack_planes(codes_ref[...], n_bits)
    out_ref[...] = _codebook_select(idx, cb_ref[...])


def _dequant_kernel_v2(codes_ref, syms_ref, offs_ref, dbase_ref, cb_ref,
                       out_ref, *, n_bits: int, b: int):
    BK = out_ref.shape[0]
    sel = _tile_selector(syms_ref[...], offs_ref[...], dbase_ref[...],
                         pl.program_id(2), b=b, block_k=BK)
    idx = (sel << n_bits) + _unpack_planes(codes_ref[...], n_bits)
    out_ref[...] = _codebook_select(idx, cb_ref[...])


def _stack_spec(rows: int, block_n: int, k_blocked: bool):
    """(L, rows_total, pn) operand blocked as (rows, block_n); its row
    block follows the K grid axis when ``k_blocked``."""
    if k_blocked:
        return pl.BlockSpec((None, rows, block_n), lambda l, j, kk: (l, kk, j))
    return pl.BlockSpec((None, rows, block_n), lambda l, j, kk: (l, 0, j))


@functools.partial(
    jax.jit, static_argnames=("n_bits", "block_n", "block_k", "interpret"))
def dequant_padded(
    codes: jnp.ndarray,      # (L, n*pk/32, pn) uint32 bit planes
    bitmap: jnp.ndarray,     # (L, pk/32, pn) uint32 selector plane
    codebooks: jnp.ndarray,  # (L, C, pn)
    *,
    n_bits: int,
    block_n: int,
    block_k: int,
    interpret: bool,
) -> jnp.ndarray:
    """v1 core over prepared inputs -> (L, pk, pn) f32 W^T (padded)."""
    L, _, pn = codes.shape
    pk = bitmap.shape[1] * 32
    C = codebooks.shape[1]
    return pl.pallas_call(
        functools.partial(_dequant_kernel, n_bits=n_bits),
        grid=(L, pn // block_n, pk // block_k),
        in_specs=[
            _stack_spec(n_bits * block_k // 32, block_n, True),
            _stack_spec(block_k // 32, block_n, True),
            _stack_spec(C, block_n, False),
        ],
        out_specs=pl.BlockSpec((None, block_k, block_n),
                               lambda l, j, kk: (l, kk, j)),
        out_shape=jax.ShapeDtypeStruct((L, pk, pn), jnp.float32),
        interpret=interpret,
        name="icq_dequant_v1",
    )(codes, bitmap, codebooks)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "b", "block_n", "interpret"))
def dequant_padded_v2(
    codes: jnp.ndarray,      # (L, n*pk/32, pn) uint32 bit planes
    syms: jnp.ndarray,       # (L, SW, pn) uint32 packed gap symbols
    offs: jnp.ndarray,       # (L, T+1, pn) uint16 tile symbol offsets
    dbase: jnp.ndarray,      # (L, T, pn) uint8/uint16 tile base deltas
    codebooks: jnp.ndarray,  # (L, C, pn)
    *,
    n_bits: int,
    b: int,
    block_n: int,
    interpret: bool,
) -> jnp.ndarray:
    """v2 core over prepared inputs -> (L, pk, pn) f32 W^T (padded).

    The K block is the checkpoint tile: pk / T, with T from the sidecar
    shape (``prepare`` guarantees pk == T * tile).
    """
    L, W, pn = codes.shape
    pk = W * 32 // n_bits
    T = offs.shape[1] - 1
    block_k = pk // T
    SW = syms.shape[1]
    return pl.pallas_call(
        functools.partial(_dequant_kernel_v2, n_bits=n_bits, b=b),
        grid=(L, pn // block_n, T),
        in_specs=[
            _stack_spec(n_bits * block_k // 32, block_n, True),
            _stack_spec(SW, block_n, False),
            _stack_spec(T + 1, block_n, False),
            _stack_spec(T, block_n, False),
            _stack_spec(codebooks.shape[1], block_n, False),
        ],
        out_specs=pl.BlockSpec((None, block_k, block_n),
                               lambda l, j, kk: (l, kk, j)),
        out_shape=jax.ShapeDtypeStruct((L, pk, pn), jnp.float32),
        interpret=interpret,
        name="icq_dequant_v2",
    )(codes, syms, offs, dbase, codebooks)
