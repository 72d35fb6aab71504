"""Scalar quantizers used by ICQuant and the baselines.

Everything produces *codebooks*: a quantizer maps a masked subset of a row
to (codes, codebook) with reconstruction w_hat = codebook[code]. This
unifies RTN (uniform codebook), signed-tail RTN for outliers (paper
Appendix E.1), and Fisher-weighted K-means (SqueezeLLM / ICQuant^SK).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_EPS = 1e-12


# ---------------------------------------------------------------------------
# codebook application
# ---------------------------------------------------------------------------

@jax.jit
def assign_codes(w: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    """Nearest-centroid code for each element. w: (..., L), codebook:
    (..., C) broadcastable over leading dims. Jitted so the (..., L, C)
    distances fuse into the argmin instead of being materialized (at a
    92544-row vocabulary head they would take gigabytes)."""
    d = jnp.abs(w[..., :, None] - codebook[..., None, :])
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def lookup(codes: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    return jnp.take_along_axis(codebook, codes, axis=-1)


# ---------------------------------------------------------------------------
# RTN (uniform) codebooks
# ---------------------------------------------------------------------------

def uniform_codebook(lo: jnp.ndarray, hi: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Uniform levels covering [lo, hi]; lo/hi: (...,) -> (..., 2^n)."""
    levels = 1 << n_bits
    t = jnp.linspace(0.0, 1.0, levels, dtype=jnp.float32)
    return lo[..., None] + (hi - lo)[..., None] * t


def rtn_inlier_codebook(w: jnp.ndarray, mask: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Per-row uniform codebook over the masked (inlier) min/max range."""
    big = jnp.finfo(jnp.float32).max
    lo = jnp.where(mask, w, big).min(axis=-1)
    hi = jnp.where(mask, w, -big).max(axis=-1)
    return uniform_codebook(lo, hi, n_bits)


def rtn_outlier_codebook(
    w: jnp.ndarray, mask: jnp.ndarray, n_bits: int
) -> jnp.ndarray:
    """Signed-tail RTN (Appendix E.1): 1 sign bit + (n-1)-bit RTN per tail.

    The returned 2^n codebook is the concatenation of 2^(n-1) uniform
    levels on the negative tail and 2^(n-1) on the positive tail. Empty
    tails collapse to the available tail so every code stays usable.
    """
    half = 1 << (n_bits - 1)
    big = jnp.finfo(jnp.float32).max
    wneg = jnp.where(mask & (w < 0), w, big)
    wpos = jnp.where(mask & (w >= 0), w, -big)
    neg_lo = wneg.min(axis=-1)
    neg_hi = jnp.where(mask & (w < 0), w, -big).max(axis=-1)
    pos_lo = jnp.where(mask & (w >= 0), w, big).min(axis=-1)
    pos_hi = wpos.max(axis=-1)
    has_neg = (neg_hi > -big) & (neg_lo < big)
    has_pos = (pos_hi > -big) & (pos_lo < big)
    # fall back to the other tail (or zero) when a tail is empty
    neg_lo = jnp.where(has_neg, neg_lo, jnp.where(has_pos, pos_lo, 0.0))
    neg_hi = jnp.where(has_neg, neg_hi, jnp.where(has_pos, pos_hi, 0.0))
    pos_lo = jnp.where(has_pos, pos_lo, neg_lo)
    pos_hi = jnp.where(has_pos, pos_hi, neg_hi)
    t = jnp.linspace(0.0, 1.0, half, dtype=jnp.float32)
    neg = neg_lo[..., None] + (neg_hi - neg_lo)[..., None] * t
    pos = pos_lo[..., None] + (pos_hi - pos_lo)[..., None] * t
    return jnp.concatenate([neg, pos], axis=-1)


# ---------------------------------------------------------------------------
# Fisher-weighted K-means (SqueezeLLM quantizer; ICQuant^SK)
# ---------------------------------------------------------------------------

def _quantile_init(w, weight, n_clusters):
    """Initialize centroids at weighted quantiles of the masked values."""
    order = jnp.argsort(w)
    w_sorted = jnp.take(w, order)
    m_sorted = jnp.take(weight, order)
    cum = jnp.cumsum(m_sorted)
    total = jnp.maximum(cum[-1], _EPS)
    targets = (jnp.arange(n_clusters, dtype=jnp.float32) + 0.5) / n_clusters
    idx = jnp.searchsorted(cum / total, targets)
    idx = jnp.clip(idx, 0, w.shape[0] - 1)
    init = jnp.take(w_sorted, idx)
    # nudge duplicates apart so empty clusters are rare at init
    span = jnp.maximum(w_sorted[-1] - w_sorted[0], _EPS)
    jitter = jnp.linspace(-1e-6, 1e-6, n_clusters) * span
    return init + jitter


@partial(jax.jit, static_argnames=("n_clusters", "iters"))
def weighted_kmeans_1d(
    w: jnp.ndarray,
    weight: jnp.ndarray,
    n_clusters: int,
    iters: int = 25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted 1-D Lloyd's algorithm on a single row subset.

    w, weight: (L,). weight of 0 excludes a point (mask folded in).
    Returns (codebook (n_clusters,) sorted, codes (L,)).
    """
    centroids = _quantile_init(w, weight, n_clusters)

    def step(c, _):
        d = jnp.abs(w[:, None] - c[None, :])
        a = jnp.argmin(d, axis=-1)
        onehot = jax.nn.one_hot(a, n_clusters, dtype=jnp.float32)
        wsum = (onehot * weight[:, None]).sum(axis=0)
        vsum = (onehot * (weight * w)[:, None]).sum(axis=0)
        new = jnp.where(wsum > _EPS, vsum / jnp.maximum(wsum, _EPS), c)
        return new, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=iters)
    centroids = jnp.sort(centroids)
    codes = jnp.argmin(jnp.abs(w[:, None] - centroids[None, :]), axis=-1)
    return centroids, codes.astype(jnp.int32)


def weighted_kmeans_rows(
    W: jnp.ndarray,
    weight: jnp.ndarray,
    n_clusters: int,
    iters: int = 25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """vmap of weighted_kmeans_1d over rows. W, weight: (R, L)."""
    f = jax.vmap(lambda w, m: weighted_kmeans_1d(w, m, n_clusters, iters))
    return f(W, weight)


# ---------------------------------------------------------------------------
# plain helpers
# ---------------------------------------------------------------------------

def quantization_mse(
    W: jnp.ndarray, W_hat: jnp.ndarray, fisher: Optional[jnp.ndarray] = None
) -> float:
    err = (W - W_hat) ** 2
    if fisher is not None:
        err = err * fisher
    return float(err.sum())
