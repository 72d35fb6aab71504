"""Kernel-backed execution layer: dispatch + prepared weight layouts.

This module makes the fused Pallas ICQ kernels the *default* compute
path for every model matmul, instead of a standalone benchmark toy.
``models/linear.py`` (and through it the whole model zoo and the
serving engine) routes any ``ICQPrepared`` weight through
``linear_apply`` below.

Prepared layout
---------------
``prepare()`` converts a storage-format ``ICQPacked`` (or serving-format
``ICQRuntime`` / runtime dict) into an ``ICQPrepared`` **once at model
load time**: pre-padded, pre-blocked, and transposed so that the lane
(minor) axis of every tensor is the weight row. Any bit width then tiles
as whole (8, 128) blocks. pn = d_out padded to the N block (or to 8 when
one block covers every row); pk = d_in padded to a multiple of 256.

  codes:     (*lead, n * pk / 32, pn) uint32 — n-bit codes as n bit
             planes (``core/packing.py``: ``pack_planes``), exactly n
             bits per weight.
  codebooks: (*lead, 2^(n+1), pn) f32 (or bf16 with
             ``codebook_dtype='bf16'``) — [inlier ++ outlier] levels;
             padded rows are zero so they contribute nothing.

Two selector formats (``fmt``, default ``platform.default_runtime_fmt()``
= 'v2', env override ``ICQ_RUNTIME_FMT=v1|v2``):

  v1 — dense selector bitmap (the bitwise-parity fallback):
  bitmap:    (*lead, pk / 32, pn) uint32 — one bit plane (+1 b/w).

  v2 — checkpointed gap stream (the paper-faithful ~0.3 b/w stream,
  served directly; the kernels decode their selector tile in VMEM):
  syms:      (*lead, SW, pn) uint32 — each row's packed b-bit gap
             symbols down the sublanes (value-1 encoding, all-ones =
             escape flag).
  offs:      (*lead, T+1, pn) uint16 — symbol-stream offset at every
             block_k boundary (T = pk / block_k; last row is the
             per-row symbol count sentinel).
  dbase:     (*lead, T, pn) uint8 (uint16 if b > 8) — checkpoint base
             delta: t*block_k - dbase[t] is the absolute position
             consumed before tile t's first symbol.
             block_k IS the checkpoint tile: re-blocking K requires
             re-preparing.

  static aux: n_bits, d_out, d_in (true shapes), block_m (cap for the M
             tile), block_n, block_k (exact divisors of pn / pk),
             backend ('pallas' | 'xla'), interpret (bool), fmt
             ('v1' | 'v2'), b (gap-symbol width; 0 for v1).

Zero padding is safe end-to-end: padded K columns meet zero-padded
activations in the matmul, padded N rows are sliced off the output, and
the pure-XLA arm slices to (d_out, d_in) before the dense matmul.
Padded rows have offs = 0 (empty symbol runs), so v2 decodes them to an
all-zero selector.

Leading axes (layer-scanned stacks, expert stacks) are kept on the array
children, so ``ICQPrepared`` nodes slice transparently under
``jax.lax.scan`` exactly like ``ICQPacked`` does.

Dispatch
--------
``linear_apply(x, prep)`` picks per call, keyed on M (= batched tokens),
shape, and platform (see kernels/platform.py):

  * backend 'xla' (default off-TPU): prepared-layout XLA reconstruction,
    then a dense matmul — bitwise-identical results to the reference
    ``dequantize`` path. For v1 that is a bit-plane unpack; for v2 the
    stream is decoded in-graph (global cumsum + scatter — exact integer
    math, so v1/v2/reference agree bit-for-bit).
  * backend 'pallas', M <= ICQ_DECODE_M (decode): the fused
    ``matmul_padded[_v2]`` kernel — packed weights go HBM->VMEM, dense
    weights never touch HBM.
  * backend 'pallas', M > ICQ_DECODE_M (prefill): ``dequant_padded[_v2]``
    once, then a dense MXU matmul in the padded space.

Block sizes come from the autotune cache (kernels/autotune.py) when a
winner for this (shape, n_bits, backend, fmt) exists, else static
defaults; either way candidates are clamped so the kernel's VMEM
working set stays under ``ICQ_VMEM_BUDGET_MB`` (default 16) instead of
failing in the compiler. The prepare-time table is keyed on the decode
shape (M=1); at call time ``arm_blocks`` re-consults the cache for the
arm the call actually lands on — fused-matmul winners at the bucketed
prefill M (``autotune.PREFILL_MS``) and the M-free dequant winner — so
decode and prefill block independently when both have been tuned.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import zlib
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.icquant import ICQPacked, ICQRuntime, to_runtime_format
from repro.core.index_coding import (
    selector_from_stream_cols,
    stream_checkpoints,
)
from repro.core.packing import PLANE_ROWS
from repro.kernels import autotune
from repro.kernels.icq_dequant import (
    _round_up,
    dequant_padded,
    dequant_padded_v2,
)
from repro.kernels.icq_matmul import matmul_padded, matmul_padded_v2
from repro.kernels.platform import (
    decode_m_threshold,
    default_accum_dtype,
    default_backend,
    default_interpret,
    default_runtime_fmt,
)

DEFAULT_BLOCKS = (128, 128, 512)  # (block_m cap, block_n, block_k)
LANE = 128                        # N blocks are lane multiples (or all rows)

_CODEBOOK_DTYPES = {None: jnp.float32, "f32": jnp.float32,
                    "bf16": jnp.bfloat16}


class WeightIntegrityError(ValueError):
    """A packed v2 sidecar failed its crc32 check: the gap stream was
    corrupted between encode and load. Raised loudly at load time —
    a corrupted outlier index stream must never reach the kernels,
    where it would decode to silently-wrong weights."""


def _crc32(x) -> int:
    return zlib.crc32(np.asarray(jax.device_get(x)).tobytes()) & 0xFFFFFFFF


def _sidecar_crcs(syms, offs, dbase) -> Tuple[Tuple[str, int], ...]:
    """crc32 of each present v2 sidecar, as stored (padding included)."""
    return tuple(
        (name, _crc32(t))
        for name, t in (("syms", syms), ("offs", offs), ("dbase", dbase))
        if t is not None
    )


def verify_runtime_integrity(rt: Dict) -> None:
    """Verify a v2 runtime dict (``ops.to_runtime(fmt='v2')``) against
    the crc32 checksums it recorded at encode time. No-op for v1 dicts
    or dicts without a ``crc`` entry; raises ``WeightIntegrityError``
    naming the corrupted tensor otherwise. ``prepare()`` calls this on
    every v2 dict it loads, so checkpointed/transmitted streams fail
    loudly at load instead of serving garbage tokens."""
    crc = rt.get("crc") if isinstance(rt, dict) else None
    if not crc or rt.get("fmt", "v1") != "v2":
        return
    for name, want in crc.items():
        t = rt.get(name)
        got = _crc32(t) if t is not None else 0
        if got != want:
            raise WeightIntegrityError(
                f"v2 runtime sidecar {name!r} failed its crc32 check "
                f"(stored 0x{want:08x}, recomputed 0x{got:08x}): the "
                f"packed stream was corrupted after to_runtime() — a "
                f"flipped bit here reassigns outlier indices across "
                f"quantization groups, so the load is refused")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ICQPrepared:
    """Pre-padded, pre-blocked kernel runtime weight (see module doc).

    v1 carries ``bitmap`` (``syms``/``offs``/``dbase`` are None);
    v2 carries the checkpointed stream (``bitmap`` is None).

    ``sel_memo`` is the pure-XLA arm's decoded-selector memo: a 1-bit
    plane (the v1 bitmap layout) materialized once at prepare time when
    the weight will execute on the XLA arm, so per-call graphs unpack it
    with a shift/mask instead of re-decoding the v2 gap stream in-graph
    (a cumsum + scatter per launch, which slowed chunked-prefill TTFT).
    The memo is bit-derived from the exact decode it
    replaces, so XLA-arm outputs are unchanged bitwise. It is
    *excluded* from the bits/weight accounting: it exists only on the
    off-TPU fallback arm (where HBM residency is not the constraint the
    runtime-format numbers are about) and never ships to the Pallas
    kernels. ``ICQ_XLA_SEL_MEMO=0`` disables it.
    """

    codes: jnp.ndarray        # (*lead, n * pk / 32, pn) uint32
    bitmap: Optional[jnp.ndarray]     # v1: (*lead, pk / 32, pn) uint32
    codebooks: jnp.ndarray    # (*lead, 2^(n+1), pn) f32/bf16
    syms: Optional[jnp.ndarray]       # v2: (*lead, SW, pn) uint32
    offs: Optional[jnp.ndarray]       # v2: (*lead, T+1, pn) uint16
    dbase: Optional[jnp.ndarray]      # v2: (*lead, T, pn) uint8/uint16
    n_bits: int = dataclasses.field(metadata=dict(static=True))
    d_out: int = dataclasses.field(metadata=dict(static=True))
    d_in: int = dataclasses.field(metadata=dict(static=True))
    block_m: int = dataclasses.field(metadata=dict(static=True))
    block_n: int = dataclasses.field(metadata=dict(static=True))
    block_k: int = dataclasses.field(metadata=dict(static=True))
    backend: str = dataclasses.field(metadata=dict(static=True))
    interpret: bool = dataclasses.field(metadata=dict(static=True))
    fmt: str = dataclasses.field(default="v1", metadata=dict(static=True))
    b: int = dataclasses.field(default=0, metadata=dict(static=True))
    # v2 integrity sidecar: (('syms', crc32), ('offs', crc32), ...) over
    # the padded stored bytes — None for v1 (see verify_integrity)
    crc: Optional[Tuple[Tuple[str, int], ...]] = dataclasses.field(
        default=None, metadata=dict(static=True))
    sel_memo: Optional[jnp.ndarray] = None  # (*lead, pk / 32, pn)

    def tree_flatten(self):
        return ((self.codes, self.bitmap, self.codebooks,
                 self.syms, self.offs, self.dbase, self.sel_memo),
                (self.n_bits, self.d_out, self.d_in, self.block_m,
                 self.block_n, self.block_k, self.backend, self.interpret,
                 self.fmt, self.b, self.crc))

    @classmethod
    def tree_unflatten(cls, aux, children):
        *tensors, sel_memo = children
        return cls(*tensors, *aux, sel_memo=sel_memo)

    @property
    def pn(self) -> int:
        return self.codes.shape[-1]

    @property
    def pk(self) -> int:
        return self.codes.shape[-2] * 32 // self.n_bits

    def verify_integrity(self) -> None:
        """Recompute the v2 sidecar checksums and compare to the crc
        recorded at prepare time, raising ``WeightIntegrityError`` on the
        first mismatch.

        The failure mode this guards is specific to index-coded
        formats: a flipped bit in the packed gap stream (or its
        offset/base checkpoints) silently *reassigns an outlier index
        across quantization groups* — every weight after the corrupted
        symbol decodes against the wrong codebook half, and generation
        degrades to plausible-looking garbage instead of crashing.
        Verification costs one host pass over the sidecars; call it at
        load/restore boundaries, never per step. No-op when ``crc`` is
        None (v1, or a layout prepared before checksums existed)."""
        if self.crc is None:
            return
        have = dict(_sidecar_crcs(self.syms, self.offs, self.dbase))
        for name, want in self.crc:
            got = have.get(name, 0)
            if got != want:
                raise WeightIntegrityError(
                    f"ICQPrepared v2 sidecar {name!r} failed its crc32 "
                    f"check (stored 0x{want:08x}, recomputed "
                    f"0x{got:08x} over {self.d_out}x{self.d_in}): the "
                    f"packed gap stream was corrupted after prepare() — "
                    f"refusing to serve weights whose outlier indices "
                    f"would silently shift across groups")

    def _tensors(self):
        # sel_memo deliberately absent: XLA-fallback compute cache, not
        # part of the runtime format (see class doc)
        return [t for t in (self.codes, self.bitmap, self.codebooks,
                            self.syms, self.offs, self.dbase)
                if t is not None]

    def bits_per_weight(self) -> float:
        """HBM bits per logical weight actually resident (padding included).

        Widths derive from each array's itemsize, so uint16/uint8
        checkpoint sidecars and bf16 codebooks are charged at their true
        stored width."""
        lead = int(math.prod(self.codes.shape[:-2]))
        total = sum(t.size * jnp.dtype(t.dtype).itemsize * 8
                    for t in self._tensors())
        return total / (lead * self.d_out * self.d_in)

    def outlier_bits_per_weight(self) -> float:
        """HBM bits/weight spent on outlier *selection* only (v1 bitmap,
        or v2 stream + checkpoints) — the quantity the paper's ~0.3 b/w
        index coding result is about."""
        lead = int(math.prod(self.codes.shape[:-2]))
        sel = [t for t in (self.bitmap, self.syms, self.offs, self.dbase)
               if t is not None]
        total = sum(t.size * jnp.dtype(t.dtype).itemsize * 8 for t in sel)
        return total / (lead * self.d_out * self.d_in)


def _pad_last2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    pad = [(0, 0)] * (x.ndim - 2)
    pad += [(0, rows - x.shape[-2]), (0, cols - x.shape[-1])]
    return jnp.pad(x, pad)


def _rows_to_lanes(x: jnp.ndarray, pn: int) -> jnp.ndarray:
    """(*lead, rows, c) row-major tensor -> (*lead, c, pn) kernel layout."""
    return jnp.swapaxes(_pad_last2(jnp.asarray(x), pn, x.shape[-1]), -1, -2)


def _planes(codes2d: jnp.ndarray, n_bits: int, pn: int, pk: int):
    """(*lead, rows, d_in) codes -> (*lead, n*pk/32, pn) bit planes."""
    return packing.pack_planes(_pad_last2(codes2d, pn, pk), n_bits)


def _as_runtime(w: Union[ICQPacked, ICQRuntime, Dict]) -> ICQRuntime:
    if isinstance(w, ICQPacked):
        return to_runtime_format(w)
    if isinstance(w, dict):
        return ICQRuntime(
            codes=w["codes"], bitmap=w["bitmap"], codebooks=w["codebooks"],
            n_bits=w["n_bits"], d_out=w["codes"].shape[-2], d_in=w["d_in"],
        )
    return w


# ---------------------------------------------------------------------------
# blocks and VMEM budgeting
# ---------------------------------------------------------------------------

def snap_block_k(d_in: int, block_k: int) -> int:
    """Largest multiple of 256 <= block_k that divides round_up(d_in, 256)
    (K padding stays below one 256-row plane group)."""
    q = _round_up(d_in, PLANE_ROWS) // PLANE_ROWS
    t_req = min(max(1, block_k // PLANE_ROWS), q)
    return PLANE_ROWS * max(t for t in range(1, t_req + 1) if q % t == 0)


def snap_block_n(d_out: int, block_n: int) -> Tuple[int, int]:
    """(block_n, pn) for d_out rows: one block of all rows (padded to 8)
    when they fit in block_n, else a lane multiple dividing pn."""
    if d_out <= max(block_n, LANE):
        pn = _round_up(d_out, 8)
        return pn, pn
    bn = max(LANE, block_n // LANE * LANE)
    return bn, _round_up(d_out, bn)


def blocks_fit(bn: int, bk: int, pn: int, pk: int) -> bool:
    """Whether (bn, bk) tiles a prepared (pn, pk) weight legally."""
    return (bn >= 1 and bk >= 1 and pn % bn == 0 and pk % bk == 0
            and (bn == pn or bn % LANE == 0) and bk % PLANE_ROWS == 0)


def vmem_budget_bytes() -> int:
    """Per-kernel VMEM working-set budget (ICQ_VMEM_BUDGET_MB, default 16)."""
    env = os.environ.get("ICQ_VMEM_BUDGET_MB")
    mb = float(env) if env else 16.0
    return int(mb * 2**20)


def vmem_bytes_estimate(block_m: int, block_n: int, block_k: int, *,
                        n_bits: int, C: int, fmt: str = "v1",
                        s_cols: int = 0,
                        accum: Optional[str] = None) -> int:
    """Rough VMEM bytes for one fused-matmul grid step (dequant is a
    subset): double-buffered input and output blocks, the accumulator,
    and about six (BK, BN) int32/f32 temporaries of the tile rebuild
    (unpacked codes, selector, select tree). v2 adds its symbol block
    and the per-symbol decode temporaries (``s_cols`` unpacked symbols
    per row). Used to reject or clamp block candidates
    before the compiler does, not to bill exact bytes."""
    f32 = 4
    if accum is None:
        accum = default_accum_dtype()
    est = 2 * block_m * block_k * f32                  # x tiles
    est += 2 * block_m * block_n * f32                 # out tiles
    est += block_m * block_n * (2 if accum == "bf16" else 4)  # acc scratch
    est += 2 * n_bits * block_k // 32 * block_n * 4    # bit-plane words
    est += 2 * C * block_n * f32                       # codebooks
    est += 6 * block_k * block_n * f32                 # tile temporaries
    if fmt == "v2":
        est += 6 * s_cols * block_n * 4               # syms, decode
    else:
        est += 2 * block_k // 32 * block_n * 4         # bitmap plane
    return est


def _clamp_blocks_to_budget(bm: int, bn: int, bk: int, *, n_bits: int,
                            C: int, fmt: str, d_in: int, s_cols: int,
                            allow_bk: bool = True):
    """Shrink (bk, bn, bm) until the VMEM estimate fits the budget."""
    budget = vmem_budget_bytes()
    while vmem_bytes_estimate(bm, bn, bk, n_bits=n_bits, C=C, fmt=fmt,
                              s_cols=s_cols) > budget:
        if allow_bk and bk > PLANE_ROWS:
            bk = snap_block_k(d_in, bk // 2)
            continue
        if bn > LANE and bn % (2 * LANE) == 0:
            bn //= 2
            continue
        if bm > 8:
            bm //= 2
            continue
        break  # minimal blocks; let the compiler have the final word
    return bm, bn, bk


# ---------------------------------------------------------------------------
# prepare
# ---------------------------------------------------------------------------

def _encode_v2_sidecar(symbols, counts, b: int, d_out: int, tile: int,
                       total_len: int):
    """Pack the gap stream + build checkpoints, host-side (load time).

    symbols/counts may carry leading stack axes; returns row-major jnp
    arrays (syms uint32, offs uint16, dbase uint8/16) with those axes
    restored.
    """
    sym_np = np.asarray(jax.device_get(symbols))
    cnt_np = np.asarray(jax.device_get(counts))
    lead = sym_np.shape[:-2] if sym_np.ndim > 2 else ()
    rows = int(np.prod(lead, dtype=np.int64)) * d_out if lead else d_out
    sym2 = sym_np.reshape(rows, sym_np.shape[-1])
    cnt2 = cnt_np.reshape(rows)
    words = packing.pack_symbols_np(sym2, b)
    offs, dbase = stream_checkpoints(sym2, cnt2, b, tile, total_len)
    return (
        jnp.asarray(words.reshape(*lead, d_out, words.shape[-1])),
        jnp.asarray(offs.reshape(*lead, d_out, offs.shape[-1])),
        jnp.asarray(dbase.reshape(*lead, d_out, dbase.shape[-1])),
    )


def prepare(
    w: Union[ICQPacked, ICQRuntime, Dict],
    *,
    blocks: Optional[Tuple[int, int, int]] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    fmt: Optional[str] = None,
    codebook_dtype: Optional[str] = None,
) -> ICQPrepared:
    """Expand + pad + block a quantized weight for the execution layer.

    ``blocks`` is (block_m_cap, block_n, block_k); when None the
    autotune cache is consulted (decode-shape key, M=1) and static
    defaults are used on a miss. Either way blocks are snapped to the
    kernel layout and clamped to the VMEM budget.

    ``fmt`` is 'v1' | 'v2' | None (None = platform default, normally
    'v2'). v2 needs the gap stream, so it requires an ``ICQPacked`` (or
    a v2 runtime dict from ``ops.to_runtime(fmt='v2')``); bitmap-only
    sources (``ICQRuntime``, v1 dicts) silently fall back to v1 — they
    already paid the dense-bitmap expansion.

    ``codebook_dtype`` is 'f32' (default) or 'bf16' — bf16 halves the
    codebook HBM charge at ~3 decimal digits of level precision.
    """
    backend = default_backend() if backend is None else backend
    interpret = default_interpret() if interpret is None else interpret
    want = default_runtime_fmt() if fmt is None else fmt
    if want not in ("v1", "v2"):
        raise ValueError(f"fmt must be 'v1' or 'v2', got {want!r}")
    if codebook_dtype not in _CODEBOOK_DTYPES:
        raise ValueError(
            f"codebook_dtype must be 'f32' or 'bf16', got {codebook_dtype!r}")
    cb_dtype = _CODEBOOK_DTYPES[codebook_dtype]

    is_v2_dict = isinstance(w, dict) and w.get("fmt", "v1") == "v2"
    if is_v2_dict:
        # load boundary: a checkpointed/transmitted stream is verified
        # against its encode-time checksums before any decoding happens
        verify_runtime_integrity(w)
    if is_v2_dict and want == "v1":
        raise ValueError("cannot prepare a v2 runtime dict as fmt='v1' — "
                         "the dense bitmap was never materialized")
    has_stream = isinstance(w, ICQPacked) or is_v2_dict
    fmt = "v2" if (want == "v2" and has_stream) else "v1"

    # -- source tensors (row-major storage layout) -----------------------
    bitmap = syms = offs = dbase = None
    b = 0
    if is_v2_dict:
        codes, codebooks = w["codes"], w["codebooks"]
        n_bits, d_in, b = w["n_bits"], w["d_in"], w["b"]
        d_out = codes.shape[-2]
        syms, offs, dbase = w["syms"], w["offs"], w["dbase"]
        tile_src = w["tile"]
    elif fmt == "v2":  # ICQPacked, stream kept — never build the bitmap
        n_bits, d_out, d_in, b = w.n_bits, w.d_out, w.d_in, w.b
        codes = w.codes
        codebooks = w.codebooks.reshape(*w.codes.shape[:-2], d_out, -1)
    else:
        rt = _as_runtime(w)
        codes, bitmap, codebooks = rt.codes, rt.bitmap, rt.codebooks
        n_bits, d_out, d_in = rt.n_bits, rt.d_out, rt.d_in

    # -- block selection ----------------------------------------------
    if blocks is None:
        hit = autotune.lookup(autotune.matmul_key(
            1, d_out, d_in, n_bits, "pallas", interpret, fmt=fmt))
        blocks = tuple(hit) if hit is not None else DEFAULT_BLOCKS
    bm_cap, bn, bk = blocks
    bn, pn = snap_block_n(d_out, bn)
    bk = tile_src if is_v2_dict else snap_block_k(d_in, bk)
    C = codebooks.shape[-1]
    s_cols = 0
    if fmt == "v2":
        words = syms.shape[-1] if is_v2_dict else \
            max(packing.packed_width(w.symbols.shape[-1], b), 1)
        s_cols = packing.symbol_cols(words, b)
    bm_cap, bn, bk = _clamp_blocks_to_budget(
        bm_cap, bn, bk, n_bits=n_bits, C=C, fmt=fmt, d_in=d_in,
        s_cols=s_cols, allow_bk=not is_v2_dict)
    pk = _round_up(d_in, bk)

    # -- v2 sidecar -----------------------------------------------------
    if fmt == "v2" and not is_v2_dict:
        syms, offs, dbase = _encode_v2_sidecar(
            w.symbols, w.counts, b, d_out, tile=bk, total_len=pk)

    def lanes(x):
        return None if x is None else _rows_to_lanes(x, pn)

    dense_codes = packing.unpack_codes(codes, n_bits, d_in)
    prep = ICQPrepared(
        codes=_planes(dense_codes, n_bits, pn, pk),
        bitmap=None if fmt == "v2" else _planes(
            packing.unpack_codes(bitmap, 1, d_in), 1, pn, pk),
        codebooks=lanes(codebooks.astype(cb_dtype)),
        syms=lanes(syms),
        offs=lanes(offs),
        dbase=lanes(dbase),
        n_bits=n_bits,
        d_out=d_out,
        d_in=d_in,
        block_m=bm_cap,
        block_n=bn,
        block_k=bk,
        backend=backend,
        interpret=interpret,
        fmt=fmt,
        b=b,
    )
    if fmt == "v2":
        # record crc32 of the padded sidecars as stored: cheap (one host
        # pass at load time), and verify_integrity() can then catch any
        # later corruption of the packed stream before it reaches a
        # kernel. v1's dense bitmap degrades gracefully under bit flips
        # (one weight wrong); the v2 stream does not (every weight after
        # the flip decodes against the wrong group) — hence v2-only.
        prep = dataclasses.replace(
            prep, crc=_sidecar_crcs(prep.syms, prep.offs, prep.dbase))
    if fmt == "v2" and backend != "pallas" and xla_sel_memo_enabled():
        # memoize the decoded selector for the pure-XLA arm: the stream
        # decode below is exactly the per-call computation the memo
        # replaces, so the selector (and every downstream weight gather)
        # is bit-identical with or without it — it runs once here, at
        # load time, instead of inside every jitted launch. Keyed on the
        # *backend*, not on choose_path's per-call arm: a stacked
        # pallas-backend weight does fall to the XLA arm if applied
        # outside its layer scan, but building the memo for that case
        # would charge ~1 b/w of real TPU HBM to speed up a path the
        # scan-sliced serving hot loop never takes.
        sel = _xla_selector(prep)
        prep = dataclasses.replace(prep, sel_memo=_planes(sel, 1, pn, pk))
    return prep


def xla_sel_memo_enabled() -> bool:
    """ICQ_XLA_SEL_MEMO (default on): memoize the decoded v2 selector as a
    packed bitmap for weights prepared onto the pure-XLA arm."""
    return os.environ.get("ICQ_XLA_SEL_MEMO", "1") not in ("0", "false", "")


def prepare_tree(params: Any, **kw) -> Any:
    """Convert every ICQPacked/ICQRuntime leaf of a param tree (load time)."""
    return jax.tree.map(
        lambda w: prepare(w, **kw)
        if isinstance(w, (ICQPacked, ICQRuntime)) else w,
        params,
        is_leaf=lambda w: isinstance(w, (ICQPacked, ICQRuntime)),
    )


_FORCED_BACKEND: Optional[str] = None


@contextlib.contextmanager
def forced_backend(name: Optional[str]):
    """Per-call dispatch override: every ``choose_path`` decision made
    while the context is active lands on ``name``'s arm, regardless of
    the prepared backend or M.

    Only ``'xla'`` (and None = no-op) is accepted: the pure-XLA arm is
    the bitwise-exact fallback every prepared layout can execute, which
    is what makes it the *degraded mode* of the serving fault-recovery
    path — a step retried under ``forced_backend('xla')`` recomputes
    the same tokens the Pallas arms would have produced (exactly on
    CPU/same-arm configs; greedy-token-identical on TPU). The override
    is consulted at **trace time**: wrap the jitted call so the first
    trace bakes the XLA arm in (wrapping subsequent calls is free).
    """
    if name not in (None, "xla"):
        raise ValueError(
            f"forced_backend supports only 'xla' (the universal fallback "
            f"arm) or None, got {name!r}")
    global _FORCED_BACKEND
    prev = _FORCED_BACKEND
    _FORCED_BACKEND = name
    try:
        yield
    finally:
        _FORCED_BACKEND = prev


def choose_path(M: int, prep: ICQPrepared) -> str:
    """'fused' | 'dequant' | 'xla' for a call with M batched tokens."""
    if _FORCED_BACKEND == "xla":
        return "xla"
    if prep.backend != "pallas" or prep.codes.ndim != 2:
        return "xla"
    return "fused" if M <= decode_m_threshold() else "dequant"


def bucket_m(M: int) -> int:
    """Autotune M bucket for a call with M batched tokens: the largest
    tuned bucket (1, *PREFILL_MS) not exceeding M — small decode batches
    reuse the M=1 decode table, prefill-sized calls graduate to the
    prefill entries as M grows past each bucket."""
    best = 1
    for b in autotune.PREFILL_MS:
        if M >= b:
            best = b
    return best


def arm_blocks(M: int, prep: ICQPrepared) -> Tuple[int, int, int]:
    """Per-call (block_m, block_n, block_k) for the dispatch arm M lands on.

    ``prepare()`` bakes decode-keyed (M=1) blocks into the layout; this
    consults the autotune cache again at call time so prefill-M sweeps
    (``autotune.PREFILL_MS`` entries for the fused arm, the M-free
    ``dequant_key`` winner for the dequant arm) can re-block each arm
    independently. A winner is only adopted when it tiles the prepared
    layout legally (``blocks_fit``; v2 additionally pins block_k to the
    prepared checkpoint tile — re-tiling K would need a re-prepare);
    otherwise the prepare-time blocks stand.
    """
    base = (prep.block_m, prep.block_n, prep.block_k)
    path = choose_path(M, prep)
    if path == "fused":
        hit = autotune.lookup(autotune.matmul_key(
            bucket_m(M), prep.d_out, prep.d_in, prep.n_bits, "pallas",
            prep.interpret, fmt=prep.fmt))
        if hit is None:
            return base
        bm, bn, bk = hit
    elif path == "dequant":
        hit = autotune.lookup(autotune.dequant_key(
            prep.d_out, prep.d_in, prep.n_bits, "pallas", prep.interpret,
            fmt=prep.fmt))
        if hit is None:
            return base
        bm, (bn, bk) = prep.block_m, hit
    else:
        return base
    if prep.fmt == "v2":
        bk = prep.block_k
    if bm < 8 or bm % 8 or not blocks_fit(bn, bk, prep.pn, prep.pk):
        return base
    return bm, bn, bk


# ---------------------------------------------------------------------------
# execution arms
# ---------------------------------------------------------------------------

def _xla_selector(prep: ICQPrepared) -> jnp.ndarray:
    """(*lead, d_out, d_in) int32 selector via the prepared tensors."""
    plane = prep.sel_memo if prep.sel_memo is not None else prep.bitmap
    if plane is not None:
        # v1 bitmap, or the prepare-time memo of the v2 stream decode
        # below (bit-identical by construction): one shift/mask unpack.
        sel = packing.unpack_planes(plane, 1)
        return sel[..., : prep.d_out, : prep.d_in]
    syms = jnp.swapaxes(prep.syms[..., : prep.d_out], -1, -2)
    S = packing.symbol_cols(syms.shape[-1], prep.b)
    sym = packing.unpack_codes(syms, prep.b, S)
    lead = sym.shape[:-2]
    rows = int(math.prod(lead)) * prep.d_out
    # counts live in the checkpoint sentinel row; the global-cumsum
    # decode is bit-identical to the kernels' per-tile checkpoint decode
    # (same positions) at a fraction of the work.
    counts = prep.offs[..., -1, : prep.d_out].reshape(rows)
    sel = selector_from_stream_cols(
        sym.reshape(rows, S).astype(jnp.int32), counts,
        b=prep.b, out_len=prep.d_in,
    )
    return sel.reshape(*lead, prep.d_out, prep.d_in)


def _xla_weight(prep: ICQPrepared) -> jnp.ndarray:
    """Prepared tensors -> (*lead, d_out, d_in) weights, pure XLA.

    v1 unpacks the bitmap plane; v2 decodes the gap stream in-graph with
    the same exact integer math as the kernels' checkpoint decode, so
    the selector — and therefore the gathered weight — is bit-identical
    across formats and to the reference ``dequantize`` path. Output
    dtype follows the stored codebooks (f32, or bf16 codebook cache).
    """
    codes = packing.unpack_planes(prep.codes, prep.n_bits)
    codes = codes[..., : prep.d_out, : prep.d_in]
    idx = _xla_selector(prep) * (1 << prep.n_bits) + codes
    cb = jnp.swapaxes(prep.codebooks[..., : prep.d_out], -1, -2)
    return jnp.take_along_axis(cb, idx, axis=-1)


def _dequant_t(prep: ICQPrepared, block_n: int, block_k: int) -> jnp.ndarray:
    """Dequant kernel over a prepared weight -> (*lead, pk, pn) f32 W^T
    (still padded). Leading stack axes become the kernel's L grid axis."""
    lead = prep.codes.shape[:-2]

    def stack(x):
        return x.reshape((-1,) + x.shape[-2:])

    if prep.fmt == "v2":
        out = dequant_padded_v2(
            stack(prep.codes), stack(prep.syms), stack(prep.offs),
            stack(prep.dbase), stack(prep.codebooks),
            n_bits=prep.n_bits, b=prep.b, block_n=block_n,
            interpret=prep.interpret,
        )
    else:
        out = dequant_padded(
            stack(prep.codes), stack(prep.bitmap), stack(prep.codebooks),
            n_bits=prep.n_bits, block_n=block_n, block_k=block_k,
            interpret=prep.interpret,
        )
    return out.reshape(*lead, prep.pk, prep.pn)


def _fused(x_p: jnp.ndarray, prep: ICQPrepared, block_m: int, block_n: int,
           block_k: int, accum: Optional[str] = None) -> jnp.ndarray:
    """Fused kernel: (pm, pk) f32 activations -> (pm, pn) f32 (padded)."""
    accum = default_accum_dtype() if accum is None else accum
    if prep.fmt == "v2":
        return matmul_padded_v2(
            x_p, prep.codes, prep.syms, prep.offs, prep.dbase,
            prep.codebooks, n_bits=prep.n_bits, b=prep.b, block_m=block_m,
            block_n=block_n, interpret=prep.interpret, accum=accum)
    return matmul_padded(
        x_p, prep.codes, prep.bitmap, prep.codebooks, n_bits=prep.n_bits,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=prep.interpret, accum=accum)


def dequantize_prepared(prep: ICQPrepared) -> jnp.ndarray:
    """Materialize (*lead, d_out, d_in) weights. Pallas backend runs the
    dequant kernel (one call for a whole stack — dequantization is
    row-independent, so stacks need no vmap)."""
    if prep.backend != "pallas":
        return _xla_weight(prep)
    wt = _dequant_t(prep, prep.block_n, prep.block_k)
    return jnp.swapaxes(wt[..., : prep.d_in, : prep.d_out], -1, -2)


def linear_apply(x: jnp.ndarray, prep: ICQPrepared) -> jnp.ndarray:
    """y = x @ W_hat^T for x (..., d_in) -> (..., d_out), dispatching on M.

    Output dtype follows x (matching models/linear.py's dense contract).
    """
    M = int(math.prod(x.shape[:-1]))
    if M == 0:   # empty wave: keep the drop-in (0, d_out) contract
        return jnp.zeros(x.shape[:-1] + (prep.d_out,), x.dtype)
    path = choose_path(M, prep)

    if path == "xla":
        # exact-shape slice first: bitwise-identical to the reference
        # dequantize()-then-matmul path (token-parity guarantee).
        w = _xla_weight(prep)
        return x @ jnp.swapaxes(w, -1, -2).astype(x.dtype)

    x2 = x.reshape(M, prep.d_in).astype(jnp.float32)
    bm, bn, bk = arm_blocks(M, prep)   # per-arm autotuned block table
    if path == "fused":
        bm = min(bm, _round_up(M, 8))
        pm = _round_up(M, bm)
        x_p = jnp.pad(x2, ((0, pm - M), (0, prep.pk - prep.d_in)))
        y = _fused(x_p, prep, bm, bn, bk)[:M, : prep.d_out]
    else:  # 'dequant': reconstruct W^T once, ride the dense MXU matmul
        wt = _dequant_t(prep, bn, bk)                 # (pk, pn)
        x_p = jnp.pad(x2, ((0, 0), (0, prep.pk - prep.d_in)))
        y = jnp.dot(x_p, wt, preferred_element_type=jnp.float32
                    )[:, : prep.d_out]
    return y.reshape(*x.shape[:-1], prep.d_out).astype(x.dtype)


__all__ = [
    "ICQPrepared",
    "WeightIntegrityError",
    "prepare",
    "prepare_tree",
    "arm_blocks",
    "blocks_fit",
    "bucket_m",
    "choose_path",
    "dequantize_prepared",
    "forced_backend",
    "linear_apply",
    "snap_block_k",
    "snap_block_n",
    "verify_runtime_integrity",
    "vmem_bytes_estimate",
    "vmem_budget_bytes",
    "DEFAULT_BLOCKS",
]
