"""Pallas TPU kernel: paged-attention for S=1 decode over a block-pool KV.

The paged KV layout (serving/kv_pool.py) stores cache rows in a global
head-major block pool ``(num_blocks, n_heads, block_size, head_dim)``
with per-lane page tables
``(B, n_pt)`` int32 (-1 = unmapped). The XLA serving arm
(``models/layers.py::_paged_gather``) materializes the full logical
``(B, n_pt * block_size)`` view every step — reading every
mapped-or-clamped block from HBM regardless of how many rows a lane
actually holds. This kernel walks the page table *inside* the kernel
instead and streams only live blocks through VMEM (vLLM PagedAttention
semantics, Kwon et al.):

  * grid ``(B, n_kv_heads, ceil(n_pt / P))`` with ``P`` pages fetched
    per grid step (the autotuned "pages-per-program" knob);
  * the page table and per-lane lengths ride scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``) so every K/V BlockSpec index map
    can look up the physical block id before the DMA is issued. Steps
    past a lane's live-block count ``ceil(kv_len / block_size)`` clamp
    to the lane's *last live page*: consecutive grid steps then request
    the same block and Mosaic elides the copy — dead pages cost neither
    HBM reads nor compute (the arithmetic is `pl.when`-gated off);
  * online-softmax accumulation in f32 VMEM scratch (running max m,
    running sum l, f32 acc), so partially-filled tail blocks and
    unmapped (-1) entries are masked in-kernel (score ``-1e30``) rather
    than through a post-hoc validity mask over the logical view.

One kernel serves both paged attention flavors:

  * **GQA** — q ``(B, Hkv, G, hd)`` (pre-scaled by the caller), K/V
    pools ``(nb, Hkv, bs, hd)``: one grid step's page of one head is a
    contiguous ``(bs, hd)`` tile, read once;
  * **MLA latent cache** — the absorbed decode attends over the latent
    ``c_kv`` stream with a rope side-channel: pass the rope halves as
    ``q2``/``k2_pool`` (scores add) and the ``c_kv`` pool as *both* K
    and V (``Hkv=1``, ``G=H``).

The XLA gather arm stays bitwise-authoritative: it is the CPU/GPU
default, the fault-tolerance degrade target, and the parity oracle the
property tests pin this kernel against (``ICQ_PAGED_ATTN=pallas|xla``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import default_interpret

_NEG = -1e30  # python float: a jnp constant would be captured by the kernel
# f32 pools stay f32 on the MXU (the default precision rounds operands to
# bf16: ~1e-2 absolute on unit-scale values); free for bf16 pools
_HI = jax.lax.Precision.HIGHEST

#: pages-per-grid-step candidates for the autotune sweep, largest first
PAGES_PER_STEP_CANDIDATES = (8, 4, 2, 1)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _pool_index_map(i: int, P: int):
    """Index map for the i-th of P per-step pool fetches.

    Steps past the lane's live-block count repeat the last live page
    (clamped, never negative) so the DMA is elided on TPU; the matching
    compute is `pl.when`-gated off, so interpret-mode correctness does
    not depend on what the repeated fetch holds.
    """
    def index_map(b, h, j, pages_ref, nblk_ref, len_ref):
        last = jnp.maximum(nblk_ref[b] - 1, 0)
        blk = jnp.minimum(j * P + i, last)
        page = jnp.maximum(pages_ref[b, blk], 0)   # -1 unmapped -> block 0
        return (page, h, 0, 0)
    return index_map


def _paged_attn_kernel(pages_ref, nblk_ref, len_ref, *refs,
                       P: int, bs: int, n_steps: int, has_q2: bool):
    q_ref = refs[0]
    pos_ = 1
    if has_q2:
        q2_ref = refs[pos_]
        pos_ += 1
    k_refs = refs[pos_:pos_ + P]
    pos_ += P
    if has_q2:
        k2_refs = refs[pos_:pos_ + P]
        pos_ += P
    v_refs = refs[pos_:pos_ + P]
    out_ref = refs[pos_ + P]
    m_ref, l_ref, acc_ref = refs[pos_ + P + 1:pos_ + P + 4]

    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32)                       # (G, d)
    q2 = q2_ref[...].astype(jnp.float32) if has_q2 else None

    for i in range(P):
        blk = j * P + i

        @pl.when(blk < nblk_ref[b])
        def _live(i=i, blk=blk):
            k = k_refs[i][...].astype(jnp.float32)           # (bs, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),              # q @ k.T
                precision=_HI, preferred_element_type=jnp.float32)
            if has_q2:
                k2 = k2_refs[i][...].astype(jnp.float32)
                s = s + jax.lax.dot_general(
                    q2, k2, (((1,), (1,)), ((), ())),
                    precision=_HI, preferred_element_type=jnp.float32)
            # mask rows past the lane's live length (partial tail block)
            pos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < len_ref[b], s, _NEG)
            m_prev = m_ref[:, 0:1]                           # (G, 1)
            l_prev = l_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                           # (G, bs)
            v = v_refs[i][...].astype(jnp.float32)           # (bs, dv)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == n_steps - 1)
    def _flush():
        out_ref[...] = (acc_ref[...]
                        / jnp.maximum(l_ref[:, 0:1], 1e-30)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret"))
def _paged_attention_call(q, k_pool, v_pool, q2, k2_pool, pages, nblk,
                          kv_len, *, pages_per_step: int, interpret: bool):
    B, Hkv, G, d = q.shape
    bs = k_pool.shape[2]
    dv = v_pool.shape[-1]
    n_pt = pages.shape[1]
    P = max(1, min(pages_per_step, n_pt))
    n_steps = _cdiv(n_pt, P)
    has_q2 = q2 is not None

    def _fixed(shape_map):
        return pl.BlockSpec(shape_map, lambda b, h, j, *_refs: (b, h, 0, 0))

    in_specs = [_fixed((None, None, G, d))]                  # q
    operands = [q]
    if has_q2:
        in_specs.append(_fixed((None, None, G, q2.shape[-1])))
        operands.append(q2)
    for i in range(P):                                       # K pages
        in_specs.append(pl.BlockSpec((None, None, bs, d),
                                     _pool_index_map(i, P)))
        operands.append(k_pool)
    if has_q2:
        for i in range(P):                                   # rope K pages
            in_specs.append(pl.BlockSpec((None, None, bs, k2_pool.shape[-1]),
                                         _pool_index_map(i, P)))
            operands.append(k2_pool)
    for i in range(P):                                       # V pages
        in_specs.append(pl.BlockSpec((None, None, bs, dv),
                                     _pool_index_map(i, P)))
        operands.append(v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, G, dv),
                               lambda b, h, j, *_refs: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),               # running max
            pltpu.VMEM((G, 128), jnp.float32),               # running sum
            pltpu.VMEM((G, dv), jnp.float32),                # f32 acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, P=P, bs=bs, n_steps=n_steps,
                          has_q2=has_q2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dv), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(pages, nblk, kv_len, *operands)


def attn_vmem_bytes(pages_per_step: int, *, G: int, d: int, dv: int,
                    bs: int, d2: int = 0, itemsize: int = 4) -> int:
    """VMEM bill for one grid step: double-buffered page fetches plus
    the resident q/out tiles and the online-softmax scratch."""
    paged = pages_per_step * bs * (d + dv + d2) * itemsize
    fixed = G * (d + d2) * itemsize + G * dv * 4
    scratch = (2 * G * 128 + G * dv) * 4
    return 2 * paged + fixed + scratch


def fallback_pages_per_step(*, G: int, d: int, dv: int, bs: int, n_pt: int,
                            d2: int = 0, itemsize: int = 4,
                            budget: Optional[int] = None) -> int:
    """Largest sweep candidate that fits the VMEM budget (no timing)."""
    if budget is None:
        from repro.kernels import backend as _backend
        budget = _backend.vmem_budget_bytes()
    for cand in PAGES_PER_STEP_CANDIDATES:
        if cand <= max(1, n_pt) and attn_vmem_bytes(
                cand, G=G, d=d, dv=dv, bs=bs, d2=d2,
                itemsize=itemsize) <= budget:
            return cand
    return 1


def paged_attention(
    q: jnp.ndarray,                     # (B, Hkv, G, d), pre-scaled
    k_pool: jnp.ndarray,                # (nb, Hkv, bs, d)
    v_pool: jnp.ndarray,                # (nb, Hkv, bs, dv)
    pages: jnp.ndarray,                 # (B, n_pt) int32, -1 = unmapped
    kv_len: jnp.ndarray,                # (B,) int32 live rows per lane
    *,
    q2: Optional[jnp.ndarray] = None,       # (B, Hkv, G, d2) rope half
    k2_pool: Optional[jnp.ndarray] = None,  # (nb, Hkv, bs, d2)
    pages_per_step: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode-step paged attention -> (B, Hkv, G, dv) f32.

    ``q`` must already carry the softmax scale (``q * d**-0.5`` — or the
    model's scale of choice); scores are ``q @ k.T (+ q2 @ k2.T)``.
    Lanes with ``kv_len == 0`` produce zeros. Unmapped (-1) pages inside
    a lane's live range clamp to block 0 with positions ``< kv_len``
    still attended — the same contract as the XLA gather arm, so the two
    arms agree even on garbage lanes.
    """
    if interpret is None:
        interpret = default_interpret()
    if (q2 is None) != (k2_pool is None):
        raise ValueError("q2 and k2_pool must be passed together")
    B, Hkv, G, d = q.shape
    bs = k_pool.shape[2]
    n_pt = pages.shape[1]
    if pages_per_step is None:
        pages_per_step = fallback_pages_per_step(
            G=G, d=d, dv=v_pool.shape[-1], bs=bs, n_pt=n_pt,
            d2=0 if q2 is None else q2.shape[-1],
            itemsize=k_pool.dtype.itemsize)
    pages = pages.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    nblk = (kv_len + bs - 1) // bs
    return _paged_attention_call(
        q, k_pool, v_pool, q2, k2_pool, pages, nblk, kv_len,
        pages_per_step=int(pages_per_step), interpret=bool(interpret))


__all__ = [
    "PAGES_PER_STEP_CANDIDATES",
    "attn_vmem_bytes",
    "fallback_pages_per_step",
    "paged_attention",
]
