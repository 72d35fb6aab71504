"""Pallas TPU kernel: fused ICQuant dequantize + matmul.

y = x @ W_hat.T with W stored as prepared bit planes (``core/packing.py``)
plus a per-row selector and dual codebooks. Each grid step rebuilds one
transposed weight tile W^T (BK, BN) in VMEM (icq_dequant's tile math) and
feeds it straight to the MXU — HBM never sees the dense weights.

Grid (M/BM, N/BN, K/BK), K innermost; an f32 (or bf16, ``accum``) VMEM
accumulator is flushed to the output tile at the last K step. The matmul
runs at ``Precision.HIGHEST``: the weight tile holds exact f32 codebook
levels, and a single bf16 pass would round them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.icq_dequant import (
    _codebook_select,
    _tile_selector,
    _unpack_planes,
)


def check_accum(accum: str) -> None:
    if accum not in ("f32", "bf16"):
        raise ValueError(f"accum must be 'f32' or 'bf16', got {accum!r}")


def accum_scratch_dtype(accum: str):
    """VMEM accumulator dtype for ``ICQ_ACCUM_DTYPE`` (f32 exact; bf16
    halves the scratch and rounds partial sums per K step)."""
    return jnp.float32 if accum == "f32" else jnp.bfloat16


def _accumulate(x_ref, wt, out_ref, acc_ref, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), wt,
        (((1,), (0,)), ((), ())),                              # x @ W^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(acc_ref.dtype)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _matmul_kernel(x_ref, codes_ref, bitmap_ref, cb_ref, out_ref, acc_ref,
                   *, n_bits: int, n_k: int):
    sel = _unpack_planes(bitmap_ref[...], 1)
    idx = (sel << n_bits) + _unpack_planes(codes_ref[...], n_bits)
    _accumulate(x_ref, _codebook_select(idx, cb_ref[...]), out_ref, acc_ref,
                n_k)


def _matmul_kernel_v2(x_ref, codes_ref, syms_ref, offs_ref, dbase_ref,
                      cb_ref, out_ref, acc_ref, *, n_bits: int, b: int,
                      n_k: int):
    BK = x_ref.shape[-1]
    sel = _tile_selector(syms_ref[...], offs_ref[...], dbase_ref[...],
                         pl.program_id(2), b=b, block_k=BK)
    idx = (sel << n_bits) + _unpack_planes(codes_ref[...], n_bits)
    _accumulate(x_ref, _codebook_select(idx, cb_ref[...]), out_ref, acc_ref,
                n_k)


def _fixed(rows: int, block_n: int):
    return pl.BlockSpec((rows, block_n), lambda i, j, kk: (0, j))


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "block_m", "block_n", "block_k", "interpret",
                     "accum"),
)
def matmul_padded(
    x: jnp.ndarray,          # (pm, pk) f32, pm % block_m == pk % block_k == 0
    codes: jnp.ndarray,      # (n*pk/32, pn) uint32 bit planes
    bitmap: jnp.ndarray,     # (pk/32, pn) uint32 selector plane
    codebooks: jnp.ndarray,  # (C, pn)
    *,
    n_bits: int,
    block_m: int,
    block_n: int,
    block_k: int,
    interpret: bool,
    accum: str = "f32",
) -> jnp.ndarray:
    """v1 fused core over prepared inputs -> (pm, pn) f32 (padded)."""
    check_accum(accum)
    pm, pk = x.shape
    pn = codes.shape[1]
    grid = (pm // block_m, pn // block_n, pk // block_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_bits=n_bits, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_bits * block_k // 32, block_n),
                         lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // 32, block_n), lambda i, j, kk: (kk, j)),
            _fixed(codebooks.shape[0], block_n),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n),
                                   accum_scratch_dtype(accum))],
        interpret=interpret,
        name="icq_matmul_v1",
    )(x, codes, bitmap, codebooks)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "b", "block_m", "block_n", "interpret",
                     "accum"),
)
def matmul_padded_v2(
    x: jnp.ndarray,          # (pm, pk) f32, pm % block_m == 0
    codes: jnp.ndarray,      # (n*pk/32, pn) uint32 bit planes
    syms: jnp.ndarray,       # (SW, pn) uint32 packed gap symbols
    offs: jnp.ndarray,       # (T+1, pn) uint16 tile symbol offsets
    dbase: jnp.ndarray,      # (T, pn) uint8/uint16 tile base deltas
    codebooks: jnp.ndarray,  # (C, pn)
    *,
    n_bits: int,
    b: int,
    block_m: int,
    block_n: int,
    interpret: bool,
    accum: str = "f32",
) -> jnp.ndarray:
    """v2 fused core over prepared inputs -> (pm, pn) f32 (padded).

    block_k is the checkpoint tile (pk / T from the sidecar shape); the
    selector never exists as a bitmap in HBM — each K block decodes its
    own tile of the gap stream in VMEM.
    """
    check_accum(accum)
    pm, pk = x.shape
    pn = codes.shape[1]
    T = offs.shape[0] - 1
    block_k = pk // T
    SW = syms.shape[0]
    return pl.pallas_call(
        functools.partial(_matmul_kernel_v2, n_bits=n_bits, b=b, n_k=T),
        grid=(pm // block_m, pn // block_n, T),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_bits * block_k // 32, block_n),
                         lambda i, j, kk: (kk, j)),
            _fixed(SW, block_n),
            _fixed(T + 1, block_n),
            _fixed(T, block_n),
            _fixed(codebooks.shape[0], block_n),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n),
                                   accum_scratch_dtype(accum))],
        interpret=interpret,
        name="icq_matmul_v2",
    )(x, codes, syms, offs, dbase, codebooks)
