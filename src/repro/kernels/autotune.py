"""Per-(shape, n_bits, backend) block-size autotuner for the ICQ kernels.

The Pallas kernels take ``block_m/n/k`` tile sizes whose best values
depend on matrix geometry, n_bits (packing granularity) and whether the
kernel runs compiled on TPU or interpreted. ``autotune_matmul`` /
``autotune_dequant`` sweep a small candidate list on synthetic runtime
tensors of the right geometry, time each, and cache the winner:

  * in-memory (process lifetime), and
  * as JSON on disk (``ICQ_AUTOTUNE_CACHE``, default
    ``.icq_autotune.json`` at the root of the checkout, which git
    ignores) so ``benchmarks/run.py`` and the serving engine reuse
    winners across processes, and no file outside the checkout steers
    block choices.

``lookup(key)`` is cheap and is what ``backend.prepare`` consults; a
miss falls back to the static defaults, so autotuning is always
optional.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# winner blocks per key, e.g. {"matmul/m1_o4096_i4096_n2_xla": [8, 128, 512]}
_MEM: Dict[str, List[int]] = {}
_LOADED_FROM: Optional[str] = None  # cache file the in-memory view mirrors

MATMUL_CANDIDATES: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 512),
    (128, 256, 512),
    (64, 128, 1024),
    (8, 128, 512),
)
DEQUANT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (256, 512),
    (128, 1024),
    (512, 256),
)

# Batched-M buckets tuned in addition to the decode shape (M=1): winners
# at these keys let backend.arm_blocks re-block the fused arm for
# prefill-sized calls instead of reusing the decode-tuned table.
# ``register_prefill_m`` extends the table at runtime — the serving
# engine registers batch * prefill_chunk so chunked-prefill matmuls get
# their own bucket instead of rounding down to a coarser one.
PREFILL_MS: Tuple[int, ...] = (64, 256)


def register_prefill_m(m: int) -> None:
    """Add a batched-M bucket (idempotent; M <= 1 is the decode key and
    is ignored). Affects ``backend.bucket_m`` immediately and adds the
    bucket to subsequent ``autotune_arms`` sweeps."""
    global PREFILL_MS
    m = int(m)
    if m > 1 and m not in PREFILL_MS:
        PREFILL_MS = tuple(sorted((*PREFILL_MS, m)))


#: default cache file: the root of the checkout this module lives in
DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".icq_autotune.json")


def cache_path() -> str:
    return os.environ.get("ICQ_AUTOTUNE_CACHE",
                          os.path.normpath(DEFAULT_CACHE))


def _key_suffix(fmt: str, accum: Optional[str] = "f32") -> str:
    """Key qualifiers: runtime formats and accumulator dtypes tune
    independently (v1/f32 keep the un-suffixed spellings). The
    accumulator dtype must be part of the key because VMEM admission
    depends on it."""
    if accum is None:
        from repro.kernels.platform import default_accum_dtype

        accum = default_accum_dtype()
    sfx = "" if fmt == "v1" else f"_{fmt}"
    if accum != "f32":
        sfx += f"_acc-{accum}"
    return sfx


def matmul_key(M: int, d_out: int, d_in: int, n_bits: int,
               backend: str, interpret: bool, fmt: str = "v1",
               accum: Optional[str] = None) -> str:
    """Cache key (see _key_suffix for the fmt/accum qualifiers)."""
    mode = f"{backend}{'-int' if interpret else ''}"
    return (f"matmul/m{M}_o{d_out}_i{d_in}_n{n_bits}_{mode}"
            f"{_key_suffix(fmt, accum)}")


def dequant_key(d_out: int, d_in: int, n_bits: int,
                backend: str, interpret: bool, fmt: str = "v1") -> str:
    mode = f"{backend}{'-int' if interpret else ''}"
    return (f"dequant/o{d_out}_i{d_in}_n{n_bits}_{mode}"
            f"{_key_suffix(fmt)}")


def _load_disk() -> None:
    """Mirror the current cache file; reload if ICQ_AUTOTUNE_CACHE moved
    (so entries tuned against an old path never leak into the new file)."""
    global _LOADED_FROM
    path = cache_path()
    if _LOADED_FROM == path:
        return
    _MEM.clear()
    _LOADED_FROM = path
    try:
        with open(path) as f:
            _MEM.update(json.load(f))
    except (OSError, ValueError):
        pass


def lookup(key: str) -> Optional[List[int]]:
    _load_disk()
    return _MEM.get(key)


def record(key: str, blocks: Sequence[int]) -> None:
    _load_disk()
    _MEM[key] = list(blocks)
    path = cache_path()
    try:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(_MEM, f, indent=1, sort_keys=True)
    except OSError:
        pass  # read-only filesystem: in-memory cache still works


def reset(forget_disk: bool = True) -> None:
    """Drop the in-memory cache (tests). forget_disk=False keeps the
    view empty without re-reading the current file."""
    global _LOADED_FROM
    _MEM.clear()
    _LOADED_FROM = None if forget_disk else cache_path()


def _synthetic_packed(d_out: int, d_in: int, n_bits: int,
                      gamma: float = 0.05, seed: int = 0):
    """Random ICQPacked of the right geometry (timing only): random codes
    and codebooks, sorted uniform outlier positions for the gap stream."""
    import jax.numpy as jnp

    from repro.core.bounds import optimal_b
    from repro.core.icquant import ICQPacked
    from repro.core.index_coding import encode_positions
    from repro.core.packing import packed_width

    rng = np.random.default_rng(seed)
    p = max(1, int(gamma * d_in))
    pos = np.sort(
        rng.random((d_out, d_in)).argpartition(p, axis=1)[:, :p], axis=1)
    b = optimal_b(gamma)
    stream = encode_positions(pos, d_in, b)
    codes = rng.integers(0, 2**32, size=(d_out, packed_width(d_in, n_bits)),
                         dtype=np.uint32)
    return ICQPacked(
        codes=jnp.asarray(codes), symbols=stream.symbols,
        counts=stream.counts,
        codebooks=jnp.asarray(
            rng.standard_normal((d_out, 2, 1 << n_bits)), jnp.float32),
        n_bits=n_bits, b=b, gamma=gamma, d_out=d_out, d_in=d_in,
        method="rtn")


def _time_once(fn, iters: int) -> float:
    import time

    fn().block_until_ready()                       # compile + warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


def _sweep(pk, candidates, run, *, interpret: bool, fmt: str, iters: int):
    """Time ``run(prep)`` for each distinct prepared blocking of the
    candidates (snapped and clamped by ``prepare``); return the fastest
    blocking and its median microseconds."""
    from repro.kernels import backend as _backend

    best, best_us = None, float("inf")
    seen = set()
    for cand in candidates:
        prep = _backend.prepare(pk, blocks=cand, backend="pallas",
                                interpret=interpret, fmt=fmt)
        resolved = (prep.block_m, prep.block_n, prep.block_k)
        if resolved in seen:                         # snapping may collide
            continue
        seen.add(resolved)
        us = _time_once(lambda: run(prep), iters)
        if us < best_us:
            best, best_us = resolved, us
    return best, best_us


def autotune_matmul(
    M: int, d_out: int, d_in: int, n_bits: int,
    *,
    interpret: Optional[bool] = None,
    candidates: Optional[Sequence[Tuple[int, int, int]]] = None,
    iters: int = 3,
    fmt: str = "v1",
) -> Dict[str, object]:
    """Sweep fused-matmul blocks; cache and return the winner.

    ``fmt`` selects the runtime format being tuned (independent cache
    entries — v2 kernels have different VMEM/decode trade-offs). Each
    candidate is snapped to the kernel layout and clamped to the VMEM
    budget by ``backend.prepare``; candidates that collapse onto an
    already-timed blocking are skipped.

    Returns {"blocks": (bm, bn, bk), "us": median_us, "cached": bool}.
    """
    import jax.numpy as jnp

    from repro.kernels import backend as _backend
    from repro.kernels.platform import default_interpret

    if interpret is None:
        interpret = default_interpret()
    key = matmul_key(M, d_out, d_in, n_bits, "pallas", interpret, fmt=fmt)
    hit = lookup(key)
    if hit is not None:
        return dict(blocks=tuple(hit), us=None, cached=True)

    pk = _synthetic_packed(d_out, d_in, n_bits)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((M, d_in)), jnp.float32)

    def run(prep):
        bm = min(prep.block_m, _backend._round_up(M, 8))
        x_p = jnp.pad(x, ((0, _backend._round_up(M, bm) - M),
                          (0, prep.pk - d_in)))
        return _backend._fused(x_p, prep, bm, prep.block_n, prep.block_k)

    best, us = _sweep(pk, candidates or MATMUL_CANDIDATES, run,
                      interpret=interpret, fmt=fmt, iters=iters)
    record(key, best)
    return dict(blocks=best, us=us, cached=False)


def autotune_dequant(
    d_out: int, d_in: int, n_bits: int,
    *,
    interpret: Optional[bool] = None,
    candidates: Optional[Sequence[Tuple[int, int]]] = None,
    iters: int = 3,
    fmt: str = "v1",
) -> Dict[str, object]:
    """Sweep dequant (block_n, block_k); cache and return the winner."""
    from repro.kernels import backend as _backend
    from repro.kernels.platform import default_interpret

    if interpret is None:
        interpret = default_interpret()
    key = dequant_key(d_out, d_in, n_bits, "pallas", interpret, fmt=fmt)
    hit = lookup(key)
    if hit is not None:
        return dict(blocks=tuple(hit), us=None, cached=True)

    pk = _synthetic_packed(d_out, d_in, n_bits)

    def run(prep):
        return _backend._dequant_t(prep, prep.block_n, prep.block_k)

    cands = [(8, bn, bk) for bn, bk in (candidates or DEQUANT_CANDIDATES)]
    best, us = _sweep(pk, cands, run, interpret=interpret, fmt=fmt,
                      iters=iters)
    best = best[1:]
    record(key, best)
    return dict(blocks=best, us=us, cached=False)


def paged_attn_key(G: int, d: int, dv: int, bs: int, n_pt: int, *,
                   d2: int = 0, itemsize: int = 4,
                   backend: str = "pallas",
                   interpret: bool = False) -> str:
    """Cache key for the paged-attention pages-per-step sweep. Keyed on
    per-program geometry (head group G, head dims, KV block size, page
    table length, pool itemsize) — batch and kv-head count only scale
    the grid, not the per-step working set."""
    mode = f"{backend}{'-int' if interpret else ''}"
    return (f"paged_attn/g{G}_d{d}_v{dv}_r{d2}_bs{bs}_pt{n_pt}"
            f"_e{itemsize}_{mode}")


def paged_attn_pages_per_step(*, G: int, d: int, dv: int, bs: int,
                              n_pt: int, d2: int = 0,
                              itemsize: int = 4) -> int:
    """Trace-time pages-per-grid-step pick for the paged-attention
    kernel: the cached sweep winner if one exists, else the largest
    candidate fitting the VMEM budget (no timing — what
    ``models/layers.py`` consults per dispatch, mirroring
    ``backend.arm_blocks``)."""
    from repro.kernels.paged_attention import fallback_pages_per_step
    from repro.kernels.platform import default_interpret

    hit = lookup(paged_attn_key(G, d, dv, bs, n_pt, d2=d2,
                                itemsize=itemsize,
                                interpret=default_interpret()))
    if hit:
        return int(hit[0])
    return fallback_pages_per_step(G=G, d=d, dv=dv, bs=bs, n_pt=n_pt,
                                   d2=d2, itemsize=itemsize)


def autotune_paged_attn(
    B: int, Hkv: int, G: int, d: int, dv: int, bs: int, n_pt: int,
    *,
    d2: int = 0,
    interpret: Optional[bool] = None,
    candidates: Optional[Sequence[int]] = None,
    iters: int = 3,
) -> Dict[str, object]:
    """Sweep the paged-attention pages-per-grid-step knob on synthetic
    full-occupancy pools; cache and return the winner.

    Candidates whose per-step VMEM bill exceeds the budget are skipped
    before reaching the compiler; P=1 always fits as the floor.
    Returns {"pages_per_step": P, "us": median_us, "cached": bool}.
    """
    import jax.numpy as jnp

    from repro.kernels import backend as _backend
    from repro.kernels.paged_attention import (
        PAGES_PER_STEP_CANDIDATES, attn_vmem_bytes, paged_attention,
    )
    from repro.kernels.platform import default_interpret

    if interpret is None:
        interpret = default_interpret()
    key = paged_attn_key(G, d, dv, bs, n_pt, d2=d2, interpret=interpret)
    hit = lookup(key)
    if hit is not None:
        return dict(pages_per_step=int(hit[0]), us=None, cached=True)

    rng = np.random.default_rng(0)
    nb = B * n_pt + 1
    k_pool = jnp.asarray(
        rng.standard_normal((nb, Hkv, bs, d)), jnp.float32)
    v_pool = jnp.asarray(
        rng.standard_normal((nb, Hkv, bs, dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, d)), jnp.float32)
    q2 = k2_pool = None
    if d2:
        q2 = jnp.asarray(rng.standard_normal((B, Hkv, G, d2)), jnp.float32)
        k2_pool = jnp.asarray(
            rng.standard_normal((nb, Hkv, bs, d2)), jnp.float32)
    # full lanes (worst case): every page mapped, shuffled placement
    pages = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:B * n_pt].reshape(B, n_pt)
        .astype(np.int32))
    kv_len = jnp.full((B,), n_pt * bs, jnp.int32)

    best, best_us = None, float("inf")
    budget = _backend.vmem_budget_bytes()
    for P in (candidates or PAGES_PER_STEP_CANDIDATES):
        P = min(int(P), n_pt)
        if P != 1 and attn_vmem_bytes(P, G=G, d=d, dv=dv, bs=bs,
                                      d2=d2) > budget:
            continue
        fn = lambda P=P: paged_attention(
            q, k_pool, v_pool, pages, kv_len, q2=q2, k2_pool=k2_pool,
            pages_per_step=P, interpret=interpret)
        us = _time_once(fn, iters)
        if us < best_us:
            best, best_us = P, us
    record(key, [best])
    return dict(pages_per_step=best, us=best_us, cached=False)


def autotune_arms(
    d_out: int, d_in: int, n_bits: int,
    *,
    interpret: Optional[bool] = None,
    fmt: str = "v1",
    iters: int = 3,
    prefill_ms: Optional[Sequence[int]] = None,
) -> Dict[str, object]:
    """Tune every dispatch arm of one weight geometry in one shot.

    Populates the decode key (fused matmul, M=1), one fused-matmul key
    per prefill-M bucket (``PREFILL_MS`` by default), and the M-free
    dequant key — i.e. the full per-arm block table that
    ``backend.arm_blocks`` consults at call time. Returns
    {"decode": ..., "prefill": {M: ...}, "dequant": ...} with each
    leaf the corresponding autotune result dict.
    """
    out: Dict[str, object] = dict(
        decode=autotune_matmul(1, d_out, d_in, n_bits,
                               interpret=interpret, iters=iters, fmt=fmt),
        prefill={},
        dequant=autotune_dequant(d_out, d_in, n_bits,
                                 interpret=interpret, iters=iters, fmt=fmt),
    )
    for m in (PREFILL_MS if prefill_ms is None else prefill_ms):
        out["prefill"][int(m)] = autotune_matmul(
            int(m), d_out, d_in, n_bits,
            interpret=interpret, iters=iters, fmt=fmt)
    return out


def kv_block_size_key(max_len: int) -> str:
    """Cache key for the paged-KV block-size sweep. Keyed on the engine
    cache cap only: the tradeoff below is a pure function of sequence
    lengths relative to max_len, independent of model geometry (every
    layer pays the same per-row bytes) and batch (both costs scale
    linearly with lane count)."""
    return f"kv_block/maxlen{int(max_len)}"


def kv_block_size_for(max_len: int) -> Optional[int]:
    """The cached block-size winner for this cache cap, or None (the
    engine's ``kv_block_size='auto'`` consults this and falls back to
    the static default on a miss)."""
    hit = lookup(kv_block_size_key(max_len))
    return int(hit[0]) if hit else None


KV_BLOCK_CANDIDATES: Tuple[int, ...] = (4, 8, 16, 32, 64)


def autotune_kv_block_size(
    seq_lens: Sequence[int],
    max_len: int,
    *,
    row_bytes: float = 4096.0,
    table_entry_bytes: float = 8.0,
    block_touch_bytes: float = 256.0,
    candidates: Optional[Sequence[int]] = None,
) -> Dict[str, object]:
    """Pick the paged-KV block size for a traffic trace by cost model
    and record it in the shared JSON cache.

    Block size trades two overheads (both in byte-equivalents so they
    compare on one axis):

      * **fragmentation** — the last block of every sequence is on
        average half empty: larger blocks waste more pool HBM rows
        (``row_bytes`` per wasted row, i.e. KV bytes across all layers);
      * **page-table + walk overhead** — smaller blocks mean more
        page-table entries shipped per version bump
        (``table_entry_bytes`` each, host int32 + device mirror) and
        more per-block walk/DMA setup in the paged-attention kernel
        (``block_touch_bytes`` per block actually touched).

    Unlike the kernel sweeps this is a closed-form model, not a timing
    loop — allocator cost is host bookkeeping and the dominant terms
    (wasted HBM rows vs table entries) are exactly countable from the
    trace. Returns {"block_size", "cost_bytes", "cached"}.
    """
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    lens = [min(int(n), int(max_len)) for n in seq_lens]
    if not lens or min(lens) < 1:
        raise ValueError("seq_lens must be non-empty positive lengths")
    key = kv_block_size_key(max_len)
    hit = lookup(key)
    if hit is not None:
        return dict(block_size=int(hit[0]), cost_bytes=None, cached=True)

    best, best_cost = None, float("inf")
    for bs in (candidates or KV_BLOCK_CANDIDATES):
        bs = min(int(bs), int(max_len))
        n_pt = -(-max_len // bs)
        frag_rows = sum(-(-n // bs) * bs - n for n in lens)
        blocks_touched = sum(-(-n // bs) for n in lens)
        cost = (frag_rows * row_bytes
                + len(lens) * n_pt * table_entry_bytes
                + blocks_touched * block_touch_bytes)
        if cost < best_cost:
            best, best_cost = bs, cost
    record(key, [best])
    return dict(block_size=best, cost_bytes=best_cost, cached=False)
