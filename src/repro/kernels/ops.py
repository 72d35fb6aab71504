"""Public wrappers around the Pallas kernels + format conversion.

``to_runtime(packed, fmt=...)`` converts an ICQPacked (storage format:
n-bit codes + ~0.31 b/w gap stream) into a kernel runtime dict. Two
formats exist; ``runtime_bits_per_weight`` charges every tensor at its
true stored width (dtype itemsize), so the numbers below are honest HBM
residency:

  ============  =========================  =======================
  component     v1 (dense bitmap)          v2 (checkpointed stream)
  ============  =========================  =======================
  codes         n bits                     n bits
  selector      ~1.0 (1-bit bitmap)        ~0.33-0.38 (b-bit symbols,
                                           word/row padded)
  checkpoints   —                          ~24/tile (u16 offset +
                                           u8 base delta per tile)
  codebooks     2^(n+1) * 32 / d_in        same (16 with bf16 option)
  ============  =========================  =======================

i.e. v2 serves at ~0.40-0.45 b/w of outlier overhead vs ~1.0 for v1 —
the paper's index-coding saving carried through to the serving path
instead of being given back at load time. The expansion happens once at
model-load time; see kernels/backend.py for the prepared (pre-padded,
pre-blocked) layout the execution layer serves from, and
``ICQ_RUNTIME_FMT`` for the global format override.

The runtime dicts keep the row-major storage packing; ``dequant`` and
``matmul`` convert a dict into the kernel layout (``backend.prepare``)
on every call, so they suit tests and one-off calls, not a hot loop.

``interpret`` defaults to None everywhere = platform-autodetected
(compiled on TPU, interpreter off-TPU; kernels/platform.py) — callers
never pass it explicitly anymore.
"""
from __future__ import annotations

import zlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.icquant import ICQPacked
from repro.core.index_coding import decode_to_dense_mask, stream_checkpoints
from repro.kernels.backend import (
    ICQPrepared,
    WeightIntegrityError,
    _dequant_t,
    _fused,
    _round_up,
    dequantize_prepared,
    linear_apply,
    prepare,
    prepare_tree,
    snap_block_k,
    verify_runtime_integrity,
)
from repro.kernels.kmeans_assign import kmeans_assign

_CB_DTYPES = {None: jnp.float32, "f32": jnp.float32, "bf16": jnp.bfloat16}


def to_runtime(packed: ICQPacked, fmt: str = "v1", *, tile: int = 512,
               codebook_dtype: Optional[str] = None) -> Dict:
    """ICQPacked (2-D only) -> kernel runtime tensors.

    fmt='v1': dense 1-bit selector bitmap (legacy bench/test format).
    fmt='v2': packed b-bit gap symbols + per-``tile`` checkpoints
              (``tile`` is snapped to a multiple of 256 dividing the
              padded row and becomes the kernels' K block).
    """
    assert packed.codes.ndim == 2, "expand stacked weights per slice"
    if codebook_dtype not in _CB_DTYPES:
        raise ValueError(
            f"codebook_dtype must be 'f32' or 'bf16', got {codebook_dtype!r}")
    codebooks = packed.codebooks.reshape(packed.d_out, -1).astype(
        _CB_DTYPES[codebook_dtype])
    common = dict(codes=packed.codes, codebooks=codebooks,
                  n_bits=packed.n_bits, d_in=packed.d_in)
    if fmt == "v1":
        sel = decode_to_dense_mask(packed.stream).astype(jnp.uint32)
        return dict(common, fmt="v1", bitmap=packing.pack_codes(sel, 1))
    if fmt != "v2":
        raise ValueError(f"fmt must be 'v1' or 'v2', got {fmt!r}")
    tile = snap_block_k(packed.d_in, tile)
    pk = _round_up(packed.d_in, tile)
    sym_np = np.asarray(jax.device_get(packed.symbols))
    cnt_np = np.asarray(jax.device_get(packed.counts))
    offs, dbase = stream_checkpoints(sym_np, cnt_np, packed.b, tile, pk)
    words = packing.pack_symbols_np(sym_np, packed.b)
    # encode-time crc32 of each packed sidecar: verified by prepare()
    # (and verify_runtime_integrity) at every load boundary, so a
    # corrupted stream fails loudly instead of decoding outlier indices
    # into the wrong quantization groups.
    crc = {name: zlib.crc32(np.ascontiguousarray(a).tobytes()) & 0xFFFFFFFF
           for name, a in (("syms", words), ("offs", offs),
                           ("dbase", dbase))}
    return dict(
        common, fmt="v2",
        syms=jnp.asarray(words),
        offs=jnp.asarray(offs),
        dbase=jnp.asarray(dbase),
        b=packed.b,
        tile=tile,
        crc=crc,
    )


_TENSOR_KEYS = ("codes", "bitmap", "syms", "offs", "dbase", "codebooks")


def runtime_bits_per_weight(rt: Dict) -> float:
    """HBM bits per logical weight of a runtime dict.

    Every tensor is charged at its true stored width (dtype itemsize *
    8), so uint32 code/bitmap words, uint16/uint8 checkpoint sidecars
    and f32-vs-bf16 codebooks all bill honestly."""
    d_out = rt["codes"].shape[0]
    total = sum(
        rt[k].size * jnp.dtype(rt[k].dtype).itemsize * 8
        for k in _TENSOR_KEYS if rt.get(k) is not None
    )
    return total / (d_out * rt["d_in"])


def runtime_outlier_bits_per_weight(rt: Dict) -> float:
    """Bits/weight spent on outlier *selection* (bitmap, or stream +
    checkpoints) — the overhead the paper's ~0.3 b/w result bounds."""
    d_out = rt["codes"].shape[0]
    total = sum(
        rt[k].size * jnp.dtype(rt[k].dtype).itemsize * 8
        for k in ("bitmap", "syms", "offs", "dbase") if rt.get(k) is not None
    )
    return total / (d_out * rt["d_in"])


def _prepared(rt: Dict, interpret, block_n, block_k, block_m=8):
    """Kernel layout of a runtime dict with the requested blocks (the
    v2 K block is the dict's checkpoint tile)."""
    if block_k is not None and rt.get("fmt", "v1") == "v2":
        raise TypeError(
            "block_k does not apply to the v2 runtime format (its K block "
            "is the checkpoint tile)")
    blocks = (block_m, block_n, 512 if block_k is None else block_k)
    return prepare(rt, blocks=blocks, backend="pallas", interpret=interpret)


def dequant(rt: Dict, interpret: Optional[bool] = None, *,
            block_n: int = 128, block_k: Optional[int] = None) -> jnp.ndarray:
    """Dequant kernel on a runtime dict -> (d_out, d_in) f32."""
    prep = _prepared(rt, interpret, block_n, block_k)
    wt = _dequant_t(prep, prep.block_n, prep.block_k)
    return jnp.swapaxes(wt[: prep.d_in, : prep.d_out], 0, 1)


def matmul(x, rt: Dict, interpret: Optional[bool] = None, *,
           block_m: int = 128, block_n: int = 128,
           block_k: Optional[int] = None,
           accum: Optional[str] = None) -> jnp.ndarray:
    """Fused kernel on a runtime dict: (M, d_in) -> (M, d_out) f32."""
    prep = _prepared(rt, interpret, block_n, block_k, block_m)
    M = x.shape[0]
    bm = min(prep.block_m, _round_up(M, 8))
    x_p = jnp.pad(x.astype(jnp.float32),
                  ((0, _round_up(M, bm) - M), (0, prep.pk - prep.d_in)))
    y = _fused(x_p, prep, bm, prep.block_n, prep.block_k, accum)
    return y[:M, : prep.d_out]


__all__ = ["to_runtime", "runtime_bits_per_weight",
           "runtime_outlier_bits_per_weight", "dequant", "matmul",
           "kmeans_assign", "ICQPrepared", "prepare", "prepare_tree",
           "dequantize_prepared", "linear_apply",
           "WeightIntegrityError", "verify_runtime_integrity"]
