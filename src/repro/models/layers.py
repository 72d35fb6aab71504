"""Shared neural layers: norms, RoPE, chunked attention (GQA + MLA), MLP.

Pure-functional JAX: params are nested dicts of arrays, every layer is
``init_*(key, cfg) -> params`` plus an apply function. Homogeneous layer
stacks are scanned (params carry a leading layer axis), which keeps HLO
size flat in depth — important when lowering 62-layer models for a
512-device mesh.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from repro.models.linear import as_dense, linear

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / (d_in**0.5)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def pin_bshd(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain a (B, S, H, D) activation to batch x head sharding.

    GSPMD otherwise tends to all-gather per-layer attention activations
    (measured: -64% collective bytes on SSD mixers, see EXPERIMENTS §Perf
    A4); no-op outside a mesh context.
    """
    if x.ndim != 4:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec("data", None, "model", None)
        )
    except Exception:   # no mesh (plain CPU tests)
        return x


def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype=dtype)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: (B, S, H, D) (D even), positions: (B, S)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention (flash-style, pure JAX)
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jnp.ndarray,                # (B, S, H, D)
    k: jnp.ndarray,                # (B, T, Hkv, D)
    v: jnp.ndarray,                # (B, T, Hkv, Dv)
    pos_q: jnp.ndarray,            # (B, S) absolute positions
    pos_k: jnp.ndarray,            # (B, T)
    k_valid: Optional[jnp.ndarray] = None,  # (B, T) cache validity
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax attention scanned over KV chunks: O(S*chunk) memory.

    GQA via head grouping; sliding window folded into the position mask.
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    scale = scale if scale is not None else D**-0.5

    chunk = min(chunk, T)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_k = jnp.pad(pos_k, ((0, 0), (0, pad)), constant_values=-1)
        valid_pad = jnp.pad(
            jnp.ones((B, T), bool) if k_valid is None else k_valid,
            ((0, 0), (0, pad)),
        )
    else:
        valid_pad = jnp.ones((B, T), bool) if k_valid is None else k_valid

    qg = (q * scale).reshape(B, S, Hkv, G, D)
    kc = k.reshape(B, n_chunks, chunk, Hkv, D)
    vc = v.reshape(B, n_chunks, chunk, Hkv, Dv)
    pkc = pos_k.reshape(B, n_chunks, chunk)
    vmc = valid_pad.reshape(B, n_chunks, chunk)

    neg = jnp.float32(-1e30)

    def body(carry, xs):
        m, l, acc = carry
        k_i, v_i, pk_i, vm_i = xs  # (B, chunk, Hkv, D), ..., (B, chunk)
        s = jnp.einsum(
            "bshgd,bthd->bshgt", qg, k_i, preferred_element_type=jnp.float32
        )
        mask = vm_i[:, None, None, None, :]
        if causal:
            mask = mask & (pk_i[:, None, :] <= pos_q[:, :, None])[:, :, None, None, :]
        if window:
            mask = mask & (
                pk_i[:, None, :] > pos_q[:, :, None] - window
            )[:, :, None, None, :]
        s = jnp.where(mask, s, neg)
        m_i = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_i)
        p = jnp.exp(s - m_i[..., None])
        l_i = l * alpha + p.sum(axis=-1)
        acc_i = acc * alpha[..., None] + jnp.einsum(
            "bshgt,bthd->bshgd", p, v_i.astype(jnp.float32)
        )
        return (m_i, l_i, acc_i), None

    m0 = jnp.full((B, S, Hkv, G), neg, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, G, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            jnp.moveaxis(pkc, 1, 0),
            jnp.moveaxis(vmc, 1, 0),
        ),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (with optional KV cache and cross attention)
# ---------------------------------------------------------------------------

def gqa_init(key, cfg) -> Params:
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return dict(
        wq=dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dt),
        wk=dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dt),
        wv=dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dt),
        wo=dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dt),
    )


def _chunk_write_cols(idx: jnp.ndarray, S: int, T: int,
                      seq_lens: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Per-lane cache write columns for an S-token chunk.

    ``seq_lens`` (B,) masks ragged chunk tails (lanes with fewer than S
    valid tokens this step — mid-decode lanes contribute 0): invalid
    columns are pushed past the cache edge ``T`` so the ``mode='drop'``
    scatter discards them instead of clobbering live rows.
    """
    cols = idx[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    if seq_lens is None:
        return cols
    valid = jnp.arange(S, dtype=jnp.int32)[None] < seq_lens[:, None]
    return jnp.where(valid, cols, T)


def _check_seq_lens(seq_lens, cache) -> None:
    if seq_lens is None:
        return
    if cache is None or "pos" in cache or not cache["index"].ndim:
        raise NotImplementedError(
            "seq_lens (chunked prefill validity masks) requires a per-lane "
            "slot cache (make_cache(..., per_lane=True))")


def _paged_scatter(pool: jnp.ndarray, pages: jnp.ndarray, cols: jnp.ndarray,
                   values: jnp.ndarray) -> jnp.ndarray:
    """Write ``values`` (B, S, H, d) into a block pool through a page table.

    ``pool`` is head-major (num_blocks, H, block_size, d), so the paged
    attention kernel reads one head's page as a contiguous
    (block_size, d) tile; ``pages`` (B, n_pt) maps
    each lane's logical block j to a physical block (-1 = unmapped);
    ``cols`` (B, S) holds logical positions with invalid entries already
    pushed to ``n_pt * block_size`` by ``_chunk_write_cols``. Invalid
    columns and unmapped pages resolve to physical block ``num_blocks``,
    which the ``mode='drop'`` scatter discards — mirroring the
    contiguous path's out-of-range-write semantics exactly.
    """
    nb, bs = pool.shape[0], pool.shape[2]
    n_pt = pages.shape[1]
    blk = jnp.take_along_axis(
        pages, jnp.clip(cols // bs, 0, n_pt - 1), axis=1)       # (B, S)
    ok = (cols < n_pt * bs) & (blk >= 0)
    blk = jnp.where(ok, blk, nb)                                # -> dropped
    off = jnp.where(ok, cols % bs, 0)
    return pool.at[blk, :, off].set(values.astype(pool.dtype), mode="drop")


def _paged_gather(pool: jnp.ndarray, pages: jnp.ndarray) -> jnp.ndarray:
    """Read each lane's logical KV view (B, n_pt * block_size, H, d) out of
    the head-major block pool. Unmapped (-1) page entries clamp to block 0 — the
    gathered garbage sits at logical positions beyond the lane's write
    index, which the per-lane validity mask already excludes (a lane
    maps a block before the first write into it, and position ``p`` is
    written in the same step it first becomes valid)."""
    nb, H, bs, d = pool.shape
    B, n_pt = pages.shape
    out = pool[jnp.clip(pages, 0, nb - 1)]          # (B, n_pt, H, bs, d)
    return jnp.swapaxes(out, 2, 3).reshape(B, n_pt * bs, H, d)


def _paged_attn_arm(S: int, window: int, T: int) -> str:
    """Which arm serves a paged-attention call: 'pallas' (the in-kernel
    page-table walk, kernels/paged_attention.py) or 'xla' (gather the
    logical view, the bitwise-authoritative fallback).

    Trace-time decision, mirroring the matmul dispatch: the kernel only
    serves S=1 decode without an active sliding window, and
    ``backend.forced_backend('xla')`` — the fault-tolerance degrade
    context — pins the XLA arm exactly as it does for the matmul
    kernels. Otherwise ``ICQ_PAGED_ATTN`` picks (pallas on TPU, xla
    elsewhere).
    """
    from repro.kernels import backend as _backend
    from repro.kernels.platform import default_paged_attn
    if S != 1 or (window and window < T):
        return "xla"
    if _backend._FORCED_BACKEND == "xla":
        return "xla"
    return default_paged_attn()


def _paged_pages_per_step(*, G: int, d: int, dv: int, bs: int, n_pt: int,
                          d2: int = 0, itemsize: int = 4) -> int:
    """Autotune-cache-aware pages-per-grid-step pick (trace time)."""
    from repro.kernels import autotune
    return autotune.paged_attn_pages_per_step(
        G=G, d=d, dv=dv, bs=bs, n_pt=n_pt, d2=d2, itemsize=itemsize)


def gqa_apply(
    p: Params,
    x: jnp.ndarray,               # (B, S, d_model)
    cfg,
    positions: jnp.ndarray,       # (B, S)
    cache: Optional[Params] = None,
    causal: bool = True,
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
    seq_lens: Optional[jnp.ndarray] = None,   # (B,) valid tokens this chunk
) -> Tuple[jnp.ndarray, Optional[Params]]:
    B, S, _ = x.shape
    _check_seq_lens(seq_lens, cache)
    hd = cfg.resolved_head_dim
    q = linear(x, p["wq"]).reshape(B, S, cfg.n_heads, hd)

    if cross_kv is not None:
        enc_out, enc_mask = cross_kv  # (B, Tsrc, d_model), (B, Tsrc)
        Tsrc = enc_out.shape[1]
        k = linear(enc_out, p["wk"]).reshape(B, Tsrc, cfg.n_kv_heads, hd)
        v = linear(enc_out, p["wv"]).reshape(B, Tsrc, cfg.n_kv_heads, hd)
        pos_k = jnp.broadcast_to(
            jnp.arange(Tsrc, dtype=jnp.int32)[None], (B, Tsrc)
        )
        out = chunked_attention(
            q, k, v, positions, pos_k, enc_mask,
            causal=False, chunk=cfg.attn_chunk,
        )
        return linear(out.reshape(B, S, -1), p["wo"]), cache

    k = linear(x, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = linear(x, p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # NB: pin_bshd here was measured NET-HARMFUL for attention (unlike the
    # SSD mixer): deepseek train compute 21->344 s. See §Perf B5. Attention
    # activations are left to GSPMD propagation.
    out, new_cache = _gqa_attend(q, k, v, cfg, positions, cache, causal,
                                 seq_lens)
    return linear(out.reshape(B, S, -1), p["wo"]), new_cache


@jax.named_scope("attn_core")
def _gqa_attend(q, k, v, cfg, positions, cache, causal, seq_lens):
    """``gqa_apply``'s KV write and attention, under the program scope
    ``attn_core`` (the projections around them are ``icq_linear``'s).
    Returns (out (B, S, n_heads, head_dim), new cache)."""
    B, S, _, hd = q.shape
    if cache is None:
        out = chunked_attention(
            q, k, v, positions, positions,
            causal=causal, window=cfg.sliding_window, chunk=cfg.attn_chunk,
        )
        new_cache = None
    elif "pos" in cache:
        if cache["index"].ndim:
            raise NotImplementedError(
                "per-lane cache positions are not supported for the "
                "sliding-window ring cache (its pos column is batch-global)"
            )
        # ring-buffer cache of size W (sliding-window attention):
        # attend over [history ring ++ current chunk], then fold the last
        # W tokens back into the ring.
        idx = cache["index"]
        W = cfg.sliding_window
        k_full = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], 1)
        v_full = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], 1)
        pos_full = jnp.concatenate(
            [cache["pos"], idx + jnp.arange(S, dtype=jnp.int32)]
        )
        valid = jnp.broadcast_to((pos_full >= 0)[None], (B, W + S))
        out = chunked_attention(
            q, k_full, v_full, positions,
            jnp.broadcast_to(pos_full[None], (B, W + S)), valid,
            causal=True, window=W, chunk=cfg.attn_chunk,
        )
        if S >= W:
            kw, vw = k[:, -W:], v[:, -W:]
            write_pos = idx + S - W + jnp.arange(W, dtype=jnp.int32)
        else:
            kw, vw = k, v
            write_pos = idx + jnp.arange(S, dtype=jnp.int32)
        slots = write_pos % W
        ck = cache["k"].at[:, slots].set(kw.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(vw.astype(cache["v"].dtype))
        cpos = cache["pos"].at[slots].set(write_pos)
        new_cache = dict(k=ck, v=cv, pos=cpos, index=idx + S)
    elif "pages" in cache:
        # paged per-lane cache: a global block pool + per-lane page
        # tables (serving/kv_pool.py). Logical positions are unchanged —
        # only the physical placement of cache rows differs — so the
        # attention math below is the contiguous per-lane branch verbatim
        # over the gathered logical view (bitwise-parity-pinned in
        # tests/test_kv_pool.py).
        idx = cache["index"]                        # (B,) per-lane
        pages = cache["pages"]                      # (B, n_pt), -1 unmapped
        T = pages.shape[1] * cache["k"].shape[2]    # logical capacity
        cols = _chunk_write_cols(idx, S, T, seq_lens)
        ck = _paged_scatter(cache["k"], pages, cols, k)
        cv = _paged_scatter(cache["v"], pages, cols, v)
        adv = S if seq_lens is None else seq_lens
        if _paged_attn_arm(S, cfg.sliding_window, T) == "pallas":
            # stream only live blocks through VMEM; the kernel masks
            # partial tails / unmapped pages in-kernel (same logical
            # semantics as the gather arm below, parity-pinned in
            # tests/test_paged_attention.py)
            from repro.kernels.paged_attention import paged_attention
            Hkv = cfg.n_kv_heads
            G = cfg.n_heads // Hkv
            bs = cache["k"].shape[2]
            qk = (q[:, 0].astype(jnp.float32) * hd**-0.5
                  ).reshape(B, Hkv, G, hd)
            pps = _paged_pages_per_step(
                G=G, d=hd, dv=hd, bs=bs, n_pt=pages.shape[1],
                itemsize=ck.dtype.itemsize)
            out = paged_attention(
                qk, ck, cv, pages, idx + adv, pages_per_step=pps,
            ).reshape(B, 1, cfg.n_heads, hd).astype(q.dtype)
        else:
            pos_k = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (B, T))
            k_valid = pos_k < (idx + adv)[:, None]
            out = chunked_attention(
                q, _paged_gather(ck, pages), _paged_gather(cv, pages),
                positions, pos_k, k_valid,
                causal=True, window=cfg.sliding_window, chunk=cfg.attn_chunk,
            )
        new_cache = dict(k=ck, v=cv, index=idx + adv, pages=pages)
    else:
        idx = cache["index"]  # int32 #tokens cached: scalar, or (B,) per-lane
        if idx.ndim:
            # continuous batching: each lane writes at its own position.
            # Out-of-range writes (a recycled lane clamped at max_len, or
            # a ragged chunk tail masked by seq_lens) are dropped, never
            # wrapped.
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = _chunk_write_cols(idx, S, cache["k"].shape[1], seq_lens)
            ck = cache["k"].at[rows, cols].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[rows, cols].set(
                v.astype(cache["v"].dtype), mode="drop")
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0)
            )
        T = ck.shape[1]
        adv = S if seq_lens is None else seq_lens   # per-lane tokens added
        pos_k = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        k_valid = pos_k < (
            (idx + adv)[:, None] if idx.ndim else idx + adv)
        out = chunked_attention(
            q, ck, cv, positions, pos_k, k_valid,
            causal=True, window=cfg.sliding_window, chunk=cfg.attn_chunk,
        )
        new_cache = dict(k=ck, v=cv, index=idx + adv)
    return out, new_cache


def _check_paged(paged, per_lane: bool):
    """Validate a ``paged=(num_blocks, block_size)`` cache request; returns
    (num_blocks, block_size) or None."""
    if paged is None:
        return None
    if not per_lane:
        raise NotImplementedError(
            "a paged KV cache requires per-lane positions "
            "(make_cache(..., per_lane=True))")
    num_blocks, block_size = paged
    if num_blocks < 1 or block_size < 1:
        raise ValueError(f"paged cache needs num_blocks >= 1 and "
                         f"block_size >= 1, got {paged}")
    return int(num_blocks), int(block_size)


def gqa_cache_init(cfg, batch: int, max_len: int,
                   per_lane: bool = False, paged=None) -> Params:
    """KV cache. ``per_lane=True`` gives the write index a (B,) batch axis
    (continuous-batching slot cache: every lane tracks its own position).
    ``paged=(num_blocks, block_size)`` replaces the contiguous (B, max_len)
    rows with a global block pool plus per-lane page tables (-1 =
    unmapped); cache HBM becomes num_blocks * block_size rows, decoupled
    from batch * max_len."""
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg)
    paged = _check_paged(paged, per_lane)
    if cfg.sliding_window and cfg.sliding_window < max_len:
        if per_lane:
            raise NotImplementedError(
                "per-lane positions are not supported with a sliding-window "
                "ring cache; serve with max_len <= sliding_window or use "
                "the wave engine")
        W = cfg.sliding_window
        return dict(  # ring buffer
            k=jnp.zeros((batch, W, cfg.n_kv_heads, hd), dt),
            v=jnp.zeros((batch, W, cfg.n_kv_heads, hd), dt),
            pos=jnp.full((W,), -1, jnp.int32),
            index=jnp.zeros((), jnp.int32),
        )
    if paged is not None:
        nb, bs = paged
        n_pt = -(-max_len // bs)
        return dict(
            k=jnp.zeros((nb, cfg.n_kv_heads, bs, hd), dt),
            v=jnp.zeros((nb, cfg.n_kv_heads, bs, hd), dt),
            index=jnp.zeros((batch,), jnp.int32),
            pages=jnp.full((batch, n_pt), -1, jnp.int32),
        )
    return dict(
        k=jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt),
        v=jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt),
        index=jnp.zeros((batch,) if per_lane else (), jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V3 / MiniCPM3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg) -> Params:
    dt = _dtype(cfg)
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p: Params = {}
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dt)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dt)
        p["w_uq"] = dense_init(ks[1], cfg.q_lora_rank, H * (nd + rd), dt)
    else:
        p["w_q"] = dense_init(ks[1], cfg.d_model, H * (nd + rd), dt)
    p["w_dkv"] = dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank, dt)
    p["kv_norm"] = rmsnorm_init(cfg.kv_lora_rank, dt)
    p["w_kr"] = dense_init(ks[3], cfg.d_model, rd, dt)
    p["w_uk"] = dense_init(ks[4], cfg.kv_lora_rank, H * nd, dt)
    p["w_uv"] = dense_init(ks[5], cfg.kv_lora_rank, H * vd, dt)
    p["wo"] = dense_init(ks[6], H * vd, cfg.d_model, dt)
    return p


def _mla_q(p, x, cfg, positions):
    B, S, _ = x.shape
    H, nd, rd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        q = linear(rmsnorm(linear(x, p["w_dq"]), p["q_norm"], cfg.norm_eps), p["w_uq"])
    else:
        q = linear(x, p["w_q"])
    q = q.reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(
    p: Params,
    x: jnp.ndarray,
    cfg,
    positions: jnp.ndarray,
    cache: Optional[Params] = None,
    seq_lens: Optional[jnp.ndarray] = None,   # (B,) valid tokens this chunk
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Standard form for train/prefill; latent-absorbed form for decode.

    Cache holds the *compressed* latent (c_kv, k_rope): the MLA memory win.
    """
    B, S, _ = x.shape
    _check_seq_lens(seq_lens, cache)
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    scale = (nd + rd) ** -0.5

    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv = rmsnorm(linear(x, p["w_dkv"]), p["kv_norm"], cfg.norm_eps)   # (B,S,r)
    k_rope = rope(linear(x, p["w_kr"])[:, :, None, :], positions, cfg.rope_theta)

    if cache is None:
        # standard (un-absorbed) attention
        k_nope = linear(c_kv, p["w_uk"]).reshape(B, S, H, nd)
        vv = linear(c_kv, p["w_uv"]).reshape(B, S, H, vd)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1
        )
        with jax.named_scope("attn_core"):
            out = chunked_attention(
                q, k, vv, positions, positions,
                causal=True, chunk=cfg.attn_chunk, scale=scale,
            )
        return linear(out.reshape(B, S, -1), p["wo"]), None

    # decode: absorb W_uk into q, attend directly over the latent cache
    with jax.named_scope("attn_core"):   # the latent cache write
        idx = cache["index"]  # #tokens cached: scalar, or (B,) per-lane
        pages = cache.get("pages")      # paged latent cache (see gqa_apply)
        if pages is not None:
            T = pages.shape[1] * cache["c_kv"].shape[2]
            cols = _chunk_write_cols(idx, S, T, seq_lens)
            cc = _paged_scatter(cache["c_kv"], pages, cols, c_kv[:, :, None])
            cr = _paged_scatter(cache["k_rope"], pages, cols, k_rope)
            if _paged_attn_arm(S, 0, T) == "pallas":
                cc_log = cr_log = None      # in-kernel page walk, no gather
            else:
                cc_log = _paged_gather(cc, pages)[:, :, 0]
                cr_log = _paged_gather(cr, pages)[:, :, 0]
        elif idx.ndim:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = _chunk_write_cols(idx, S, cache["c_kv"].shape[1], seq_lens)
            cc = cache["c_kv"].at[rows, cols].set(
                c_kv.astype(cache["c_kv"].dtype), mode="drop")
            cr = cache["k_rope"].at[rows, cols].set(
                k_rope[:, :, 0, :].astype(cache["k_rope"].dtype), mode="drop")
            cc_log, cr_log = cc, cr
        else:
            cc = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, idx, 0)
            )
            cr = jax.lax.dynamic_update_slice(
                cache["k_rope"],
                k_rope[:, :, 0, :].astype(cache["k_rope"].dtype),
                (0, idx, 0),
            )
            cc_log, cr_log = cc, cr
    if pages is None:
        T = cc_log.shape[1]
    adv = S if seq_lens is None else seq_lens       # per-lane tokens added
    w_uk = as_dense(p["w_uk"]).reshape(r, H, nd)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)           # absorbed q
    with jax.named_scope("attn_core"):
        if pages is not None and cc_log is None:
            # Pallas paged-attention arm over the latent cache: the c_kv
            # pool doubles as K (latent half) and V; the rope side-channel
            # rides the kernel's q2/k2 score pair (Hkv=1, G=H).
            from repro.kernels.paged_attention import paged_attention
            bs_ = cc.shape[2]
            qm = (q_lat[:, 0].astype(jnp.float32) * scale).reshape(B, 1, H, r)
            q2 = (q_rope[:, 0].astype(jnp.float32) * scale
                  ).reshape(B, 1, H, rd)
            pps = _paged_pages_per_step(
                G=H, d=r, dv=r, bs=bs_, n_pt=pages.shape[1], d2=rd,
                itemsize=cc.dtype.itemsize)
            ctx = paged_attention(
                qm, cc, cc, pages, idx + adv, q2=q2, k2_pool=cr,
                pages_per_step=pps,
            ).reshape(B, 1, H, r).astype(q_lat.dtype)            # (B,1,H,r)
        else:
            pos_k = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (B, T))
            k_valid = pos_k < ((idx + adv)[:, None] if idx.ndim else idx + adv)
            # treat latent dims + rope dims as one concatenated "head dim"
            q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,S,H,r+rd)
            k_cat = jnp.concatenate(
                [cc_log, cr_log], axis=-1)[:, :, None, :]      # (B,T,1,r+rd)
            ctx = chunked_attention(
                q_cat, k_cat, cc_log[:, :, None, :], positions, pos_k, k_valid,
                causal=True, chunk=cfg.attn_chunk, scale=scale,
            )                                                    # (B,S,H,r)
    w_uv = as_dense(p["w_uv"]).reshape(r, H, vd)
    out = jnp.einsum("bshr,rhv->bshv", ctx, w_uv)
    new_cache = dict(c_kv=cc, k_rope=cr, index=idx + adv)
    if pages is not None:
        new_cache["pages"] = pages
    return linear(out.reshape(B, S, -1), p["wo"]), new_cache


def mla_cache_init(cfg, batch: int, max_len: int,
                   per_lane: bool = False, paged=None) -> Params:
    dt = _dtype(cfg)
    paged = _check_paged(paged, per_lane)
    if paged is not None:
        nb, bs = paged
        n_pt = -(-max_len // bs)
        return dict(
            c_kv=jnp.zeros((nb, 1, bs, cfg.kv_lora_rank), dt),
            k_rope=jnp.zeros((nb, 1, bs, cfg.qk_rope_head_dim), dt),
            index=jnp.zeros((batch,), jnp.int32),
            pages=jnp.full((batch, n_pt), -1, jnp.int32),
        )
    return dict(
        c_kv=jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        k_rope=jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dt),
        index=jnp.zeros((batch,) if per_lane else (), jnp.int32),
    )


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff: Optional[int] = None) -> Params:
    dt = _dtype(cfg)
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return dict(
        w_gate=dense_init(ks[0], cfg.d_model, d_ff, dt),
        w_up=dense_init(ks[1], cfg.d_model, d_ff, dt),
        w_down=dense_init(ks[2], d_ff, cfg.d_model, dt),
    )


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return linear(jax.nn.silu(linear(x, p["w_gate"])) * linear(x, p["w_up"]), p["w_down"])
