"""Jit-able train / prefill / decode step functions for every family.

These are the functions the dry-run lowers on the production mesh and the
drivers (launch/train.py, launch/serve.py) run on real hardware. All of
them are pure: (params, [opt_state | cache], batch) -> outputs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import (
    encdec_apply,
    encdec_cache_init,
    lm_apply,
    lm_cache_init,
    lm_hidden_and_logits,
    mtp_logits,
)
from repro.optim import adamw_update
from repro.optim.compression import error_feedback_update

AUX_COEF = 0.01
MTP_COEF = 0.3


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _ce(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def loss_fn(params, cfg, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    if cfg.is_encdec:
        logits, _, _, aux = encdec_apply(
            params, cfg, batch["frames"], batch["frame_mask"], batch["tokens"]
        )
        ce = _ce(logits[:, :-1], batch["tokens"][:, 1:])
        loss = ce + AUX_COEF * aux
        return loss, dict(loss=loss, ce=ce, aux=aux)

    prefix = batch.get("prefix_embeds")
    if cfg.mtp:
        hidden, logits, aux = lm_hidden_and_logits(
            params, cfg, batch["tokens"], prefix_embeds=prefix
        )
        P = 0 if prefix is None else prefix.shape[1]
        text_logits = logits[:, P:]
        ce = _ce(text_logits, batch["labels"])
        mtp = mtp_logits(params, cfg, hidden[:, P:], batch["tokens"])
        # mtp predicts token t+2 from hidden t  ->  labels shifted by one
        ce_mtp = _ce(mtp[:, :-1], batch["labels"][:, 2:])
        loss = ce + MTP_COEF * ce_mtp + AUX_COEF * aux
        return loss, dict(loss=loss, ce=ce, ce_mtp=ce_mtp, aux=aux)

    logits, _, aux = lm_apply(params, cfg, batch["tokens"], prefix_embeds=prefix)
    P = 0 if prefix is None else prefix.shape[1]
    ce = _ce(logits[:, P:], batch["labels"])
    loss = ce + AUX_COEF * aux
    return loss, dict(loss=loss, ce=ce, aux=aux)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt_cfg, compress_grads: bool = False):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). If compress_grads, opt_state carries 'residuals' and the
    gradient passes through int8 + error feedback before the update
    (modeling the cross-pod reduction; see optim.compression)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch), has_aux=True
        )(params)
        if compress_grads:
            grads, new_res = error_feedback_update(
                grads, opt_state["residuals"]
            )
        new_params, new_adam = adamw_update(
            params, grads, opt_state["adam"], opt_cfg
        )
        new_state = dict(adam=new_adam)
        if compress_grads:
            new_state["residuals"] = new_res
        metrics = dict(metrics, grad_norm=_safe_norm(grads))
        return new_params, new_state, metrics

    return train_step


def _safe_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree.leaves(tree))
    )


def init_opt_state(params, opt_cfg, compress_grads: bool = False):
    from repro.optim import adamw_init
    from repro.optim.compression import init_residuals

    st = dict(adam=adamw_init(params, opt_cfg))
    if compress_grads:
        st["residuals"] = init_residuals(params)
    return st


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg):
    def prefill_step(params, cache, batch):
        if cfg.is_encdec:
            logits, cache, enc_out, _ = encdec_apply(
                params, cfg, batch["frames"], batch["frame_mask"],
                batch["tokens"], cache=cache,
                start_pos=jnp.zeros((), jnp.int32),
            )
            return logits[:, -1], cache, enc_out
        logits, cache, _ = lm_apply(
            params, cfg, batch["tokens"], cache=cache,
            start_pos=jnp.zeros((), jnp.int32),
            prefix_embeds=batch.get("prefix_embeds"),
        )
        return logits[:, -1], cache

    return prefill_step


def sync_cache_positions(cache, start_pos):
    """Overwrite every ``index`` leaf of a (stacked) cache with ``start_pos``.

    With per-lane positions (``start_pos`` of shape (B,)) the serving
    engine owns the position vector: recycling a slot is a host-side
    ``pos[slot] = 0`` and the next step's cache writes land at the new
    lane origin — no device-side per-slot cache surgery. ``index`` leaves
    carry a leading layer axis ((L,) scalar caches, (L, B) per-lane
    caches); ``start_pos`` broadcasts across it.
    """
    if isinstance(cache, dict):
        return {
            k: (jnp.broadcast_to(start_pos, v.shape).astype(v.dtype)
                if k == "index" else sync_cache_positions(v, start_pos))
            for k, v in cache.items()
        }
    return cache


def sync_cache_pages(cache, pages):
    """Overwrite every ``pages`` leaf of a (stacked) paged cache.

    The serving engine's host-side block allocator
    (serving/kv_pool.py) owns the page tables; like the position
    vector, the device copy is just a mirror shipped in with each
    launch. ``pages`` is (B, max_blocks); stacked leaves carry a
    leading layer axis it broadcasts across (every layer maps logical
    positions through the same table — blocks are allocated per
    logical position, and each layer has its own physical pool).
    """
    if isinstance(cache, dict):
        return {
            k: (jnp.broadcast_to(pages, v.shape).astype(v.dtype)
                if k == "pages" else sync_cache_pages(v, pages))
            for k, v in cache.items()
        }
    return cache


def fork_cache_block(cache, src, dst):
    """Copy one physical KV block's rows, pool block ``src`` -> ``dst``
    (copy-on-write fork; see serving/prefix_cache.py).

    A paged cache subtree is recognized by its ``pages`` leaf; its
    sibling pool leaves — ``k``/``v`` (GQA) or ``c_kv``/``k_rope``
    (MLA), with any leading layer-stack axes — get row ``dst`` on the
    block axis overwritten with row ``src``. ``index``/``pages`` leaves
    are per-lane bookkeeping, not pool storage, and pass through
    untouched. ``src``/``dst`` may be traced scalars, so the engine can
    jit this once and fork arbitrary block pairs without retracing.
    """
    if isinstance(cache, dict):
        if "pages" in cache:
            # pages is (..., B, n_pt); the pool leaves share its leading
            # layer-stack axes, then (num_blocks, heads, block_size, d)
            lead = (slice(None),) * (cache["pages"].ndim - 2)
            return {
                k: (v if k in ("index", "pages")
                    else v.at[lead + (dst,)].set(v[lead + (src,)]))
                for k, v in cache.items()
            }
        return {k: fork_cache_block(v, src, dst) for k, v in cache.items()}
    return cache


def make_prefill_chunk_step(cfg):
    """S-token prompt-chunk admission step for the continuous engine.

    ``(params, cache, tokens (B, S), start_pos (B,), seq_lens (B,)) ->
    cache``: writes each lane's first ``seq_lens[i]`` chunk tokens into
    the per-lane cache at positions ``start_pos[i] + j`` and returns the
    updated cache. Lanes with ragged tails (fewer than S prompt tokens
    left) or lanes currently decoding pass ``seq_lens[i] < S`` and are
    write-masked — one traced program serves every chunk shape. No
    logits come back: chunk matmuls carry M = B*S tokens, which routes
    them through the large-M dequant+MXU dispatch arm, and the final
    norm + lm_head are skipped entirely (the first *generated* token's
    logits always come from the decode step consuming the last prompt
    token, so chunking never changes what that token sees).

    ``pages`` (B, max_blocks) is the paged-KV page table (None for the
    contiguous layout): chunk rows then land at physical block offsets
    via the same table the decode step reads through.
    """

    def prefill_chunk_step(params, cache, tokens, start_pos, seq_lens,
                           pages=None):
        if pages is not None:
            cache = sync_cache_pages(cache, pages)
        cache = sync_cache_positions(cache, start_pos)
        _, cache, _ = lm_apply(
            params, cfg, tokens, cache=cache, start_pos=start_pos,
            seq_lens=seq_lens, compute_logits=False,
        )
        return cache

    return prefill_chunk_step


def make_fused_step(cfg):
    """One launch for a mixed prefill+decode continuous-batching iteration.

    ``(params, cache, tokens (B, S), start_pos (B,), seq_lens (B,),
    pages) -> (logits (B, vocab), cache)``: each lane consumes its first
    ``seq_lens[i]`` tokens of the (B, S) chunk — ``seq_lens[i] > 1`` for
    lanes still admitting prompt, ``seq_lens[i] == 1`` for decoding
    lanes whose next token sits in column 0, ``seq_lens[i] == 0`` for
    idle lanes (fully write-masked). This folds what used to be two
    device launches per mixed iteration (an S-token chunk pass plus a
    1-token decode pass) into ONE program: the decode token rides the
    chunk program's token axis, and the chunk matmuls keep their large
    M = B*S dispatch arm.

    Logits come back for every lane at its own last valid column
    (``max(seq_lens - 1, 0)``) via ``lm_apply(logits_cols=...)``, so the
    vocab projection bills B rows, not B*S. For a decode lane that is
    exactly the new token's logits; for a prompt lane it is the logits
    after its last admitted token — meaningful (and consumed by the
    engine) only on the chunk that admits the final prompt token.
    Lanes with ``seq_lens[i] == 0`` return garbage logits the engine
    ignores. ``pages`` mirrors the paged-KV page table exactly as in
    the chunk/decode steps (None for contiguous per-lane caches).
    """

    def fused_step(params, cache, tokens, start_pos, seq_lens, pages=None):
        if pages is not None:
            cache = sync_cache_pages(cache, pages)
        cache = sync_cache_positions(cache, start_pos)
        cols = jnp.maximum(seq_lens - 1, 0).astype(jnp.int32)
        logits, cache, _ = lm_apply(
            params, cfg, tokens, cache=cache, start_pos=start_pos,
            seq_lens=seq_lens, logits_cols=cols,
        )
        return logits[:, 0], cache

    return fused_step


def make_verify_step(cfg):
    """Speculative-decode verifier: score every draft position at once.

    ``(params, cache, tokens (B, S), start_pos (B,), seq_lens (B,),
    pages) -> (logits (B, S, vocab), cache)``: lane ``i`` consumes its
    current feed token in column 0 followed by ``seq_lens[i] - 1`` draft
    tokens, all written into the cache at ``start_pos[i] + j``. The
    full per-column logits come back: column ``j`` is the greedy
    verdict after consuming token ``j``, so acceptance is a host-side
    longest-matching-prefix scan (serving/spec_decode.py). ``S`` is
    traced-static (``spec_k + 1``); ragged lanes ride the chunked-
    prefill per-lane validity masks (``seq_lens``), exactly like
    ``make_fused_step`` — but unlike the fused step the lm_head bills
    all B*S rows, since every column's argmax is consulted. M = B*S
    routes the matmuls down the large-M dequant+MXU arm.

    Cache rows written past the accepted prefix are *stale, not wrong*:
    the engine rewinds its host ``pos`` vector (and trims paged tail
    blocks) and the write-discipline invariant — a lane writes position
    ``p`` the step ``p`` re-enters its valid range — guarantees they
    are overwritten before any gather can see them as valid.
    """

    def verify_step(params, cache, tokens, start_pos, seq_lens, pages=None):
        if pages is not None:
            cache = sync_cache_pages(cache, pages)
        cache = sync_cache_positions(cache, start_pos)
        logits, cache, _ = lm_apply(
            params, cfg, tokens, cache=cache, start_pos=start_pos,
            seq_lens=seq_lens,
        )
        return logits, cache

    return verify_step


def make_decode_step(cfg):
    """One new token against an existing cache (the ``decode_*`` shapes).

    ``start_pos`` is a scalar (wave decoding) or a (B,) per-lane position
    vector (continuous batching). In the per-lane case the cache's own
    ``index`` leaves are overridden from ``start_pos`` before the forward
    pass, so the caller's position vector is the single source of truth
    (admitting a request into a recycled slot resets only host state).

    ``pages`` (B, max_blocks) mirrors the host block allocator's page
    tables into a paged cache's ``pages`` leaves (kv_layout='paged');
    ``reset`` (B,) zeroes recycled lanes' recurrent SSM state before the
    token is consumed (continuous serving of ssm/hybrid mixers). Both
    default to None and change nothing for contiguous attention caches.
    """

    def decode_step(params, cache, tokens, start_pos, enc_out=None,
                    frame_mask=None, pages=None, reset=None):
        if pages is not None:
            cache = sync_cache_pages(cache, pages)
        if jnp.ndim(start_pos):
            cache = sync_cache_positions(cache, start_pos)
        if cfg.is_encdec:
            logits, cache, _, _ = encdec_apply(
                params, cfg, None, frame_mask, tokens, cache=cache,
                enc_out=enc_out, start_pos=start_pos,
            )
            return logits[:, -1], cache
        logits, cache, _ = lm_apply(
            params, cfg, tokens, cache=cache, start_pos=start_pos,
            reset=reset,
        )
        return logits[:, -1], cache

    return decode_step


def make_cache(params, cfg, batch: int, max_len: int,
               per_lane: bool = False, paged=None):
    """``paged=(num_blocks, block_size)`` builds the block-pool KV layout
    (requires ``per_lane=True``; see serving/kv_pool.py)."""
    if cfg.is_encdec:
        if paged is not None:
            raise NotImplementedError(
                "paged KV caches are not supported for enc-dec models")
        return encdec_cache_init(params, cfg, batch, max_len,
                                 per_lane=per_lane)
    return lm_cache_init(params, cfg, batch, max_len, per_lane=per_lane,
                         paged=paged)


def prepare_serving_params(params, mode: str = "prepared", **prepare_kw):
    """One-time load-step weight conversion for the serving hot path.

    mode:
      'prepared' — ICQPacked/ICQRuntime leaves -> ICQPrepared (kernel
                   execution layer; padding + checkpoint/bitmap build
                   happen exactly once, never inside the jitted step).
                   Extra ``prepare_kw`` reach ``backend.prepare`` —
                   notably ``fmt='v1'|'v2'`` (runtime format; default is
                   the platform's, normally the v2 checkpointed gap
                   stream at ~0.3-0.45 b/w outlier overhead) and
                   ``codebook_dtype='f32'|'bf16'``.
      'dense'    — dequantize-once weight cache: leaves materialize to
                   dense (d_in, d_out) arrays at load time, so
                   prefill-heavy waves never redecode per step (costs
                   full bf16 HBM; right call only when HBM is plentiful).
      'none'     — leave params untouched (reference path).
    """
    from repro.core.icquant import ICQPacked, ICQRuntime
    from repro.kernels import backend as _backend

    if mode in (None, "none"):
        return params
    if mode == "prepared":
        return _backend.prepare_tree(params, **prepare_kw)
    if mode == "dense":
        from repro.models.linear import as_dense

        return jax.tree.map(
            lambda w: as_dense(w)
            if isinstance(w, (ICQPacked, ICQRuntime, _backend.ICQPrepared))
            else w,
            params,
            is_leaf=lambda w: isinstance(
                w, (ICQPacked, ICQRuntime, _backend.ICQPrepared)),
        )
    raise ValueError(f"unknown serving weight mode {mode!r}")
