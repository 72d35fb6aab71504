"""Plain float32 reference of the served model, and of its quantizer.

Imports nothing of the serving program. It draws the same float32
weights from the configuration's seed (``bench/weights.py``), quantizes
each matrix with its own ICQuant RTN (the paper's per-output-channel
partition: the top ``gamma`` share of |w| in each row are outliers; the
inliers take a uniform n-bit grid over their range, the outliers a signed
split: n-1 bits on each tail), and runs the model one layer at a time over
whole sequences: the embedding, each layer as the architecture's module
(``bench/archs/``) computes it, then the final norm and the LM head. No
kernels, no cache, no batching. Every matrix product runs at
``Precision.HIGHEST``; the unquantized leaves (embedding, norm gains) are
rounded to the configuration's dtype first, since that is the model the
program serves.

``lower`` switches on the control: every matrix product's operands are
rounded to float8 e4m3 (a scale per tensor), the precision one step below
the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.model import Model

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0   # largest finite float8 e4m3 value


def _lower(a):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, lower: bool):
    if lower:
        a, b = _lower(a), _lower(b)
    return jnp.matmul(a, b, precision=HI)


def _top(mag, p: int):
    """Mask of each row's p largest values (ties at the p-th all kept):
    the p-th largest found exactly by bisection on the float bits, which
    order like the values for non-negative floats; 31 passes over the
    matrix instead of a sort."""
    b = jax.lax.bitcast_convert_type(mag, jnp.int32)
    rows = b.shape[0]

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = (b >= mid[:, None]).sum(-1) >= p
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo = jnp.zeros((rows,), jnp.int32)
    hi = jnp.full((rows,), 0x7F800001, jnp.int32)   # above +inf
    lo, _ = jax.lax.fori_loop(0, 31, step, (lo, hi))
    return b >= lo[:, None]


@functools.partial(jax.jit, static_argnums=(1, 2))
def icq_rtn(w, bits: int, gamma: float):
    """ICQuant RTN of a (d_in, d_out) matrix, per output channel; returns
    the dequantized (d_in, d_out) float32 matrix."""
    wt = w.T                                      # rows = output channels
    d_in = wt.shape[1]
    p = int(math.floor(gamma * d_in))
    big = jnp.finfo(jnp.float32).max
    out = _top(jnp.abs(wt), p)                    # p per row
    t = jnp.linspace(0.0, 1.0, 1 << bits, dtype=jnp.float32)
    lo = jnp.where(out, big, wt).min(-1)
    hi = jnp.where(out, -big, wt).max(-1)
    cb_in = lo[:, None] + (hi - lo)[:, None] * t
    half = 1 << (bits - 1)
    th = jnp.linspace(0.0, 1.0, half, dtype=jnp.float32)
    neg, pos = out & (wt < 0), out & (wt >= 0)
    n_lo = jnp.where(neg, wt, big).min(-1)
    n_hi = jnp.where(neg, wt, -big).max(-1)
    p_lo = jnp.where(pos, wt, big).min(-1)
    p_hi = jnp.where(pos, wt, -big).max(-1)
    has_n = neg.any(-1)
    has_p = pos.any(-1)
    n_lo2 = jnp.where(has_n, n_lo, jnp.where(has_p, p_lo, 0.0))
    n_hi2 = jnp.where(has_n, n_hi, jnp.where(has_p, p_hi, 0.0))
    p_lo2 = jnp.where(has_p, p_lo, n_lo2)
    p_hi2 = jnp.where(has_p, p_hi, n_hi2)
    cb_out = jnp.concatenate(
        [n_lo2[:, None] + (n_hi2 - n_lo2)[:, None] * th,
         p_lo2[:, None] + (p_hi2 - p_lo2)[:, None] * th], axis=-1)

    def nearest(cb):
        # the first of the nearest entries, as an argmin over the entries
        # would pick, walked one entry at a time: no trailing axis of
        # 2**bits, which the chip would pad to a whole lane tile
        best, val = jnp.abs(wt - cb[:, :1]), jnp.broadcast_to(cb[:, :1],
                                                              wt.shape)
        for i in range(1, cb.shape[1]):
            d = jnp.abs(wt - cb[:, i:i + 1])
            take = d < best
            best = jnp.where(take, d, best)
            val = jnp.where(take, cb[:, i:i + 1], val)
        return val

    w_hat = jnp.where(out, nearest(cb_out), nearest(cb_in))
    return w_hat.T


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def head(x, start, g, w, m: Model, rows: int, lower: bool):
    """Logits of ``rows`` positions of x (T, d) from ``start`` on."""
    x = jnp.pad(x, ((0, rows), (0, 0)))
    x = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return _mm(_rms(x, g, m.norm_eps), w, lower)


def served(m: Model, name: str, layer_i: int = 0):
    """An unquantized leaf as served: rounded to the model's dtype."""
    a = weights.leaf(m, name, layer_i)
    return a.astype(jnp.dtype(m.dtype)).astype(jnp.float32)


def quantized(m: Model, name: str, layer_i: int = 0):
    """A matrix through the reference's ICQuant RTN; an expert stack
    (E, d_in, d_out) one 2-D slice at a time."""
    w = weights.leaf(m, name, layer_i)
    if w.ndim == 2:
        return icq_rtn(w, m.bits, m.gamma)
    return jnp.stack([icq_rtn(s, m.bits, m.gamma) for s in w])


def logits(m: Model, seqs: Sequence[Tuple[np.ndarray, int]], pad_to: int,
           rows: int, lower: bool = False,
           times: Optional[Dict[str, float]] = None) -> List[np.ndarray]:
    """For each (tokens, n_prompt): float32 logits (n_new, vocab) at the
    positions that predicted tokens[n_prompt:], where n_new =
    len(tokens) - n_prompt <= rows. Sequences are padded at the end to
    ``pad_to`` (one compiled shape); causal attention keeps the padding
    out of every position that is read. ``times`` collects the seconds
    spent quantizing the weights again and running the layers."""
    times = {} if times is None else times
    for k in ("quantize", "layers"):
        times.setdefault(k, 0.0)
    embed = served(m, "embed")
    xs = []
    for toks, _ in seqs:
        ids = np.zeros((pad_to,), np.int32)
        ids[: len(toks)] = toks
        xs.append(embed[jnp.asarray(ids)])
    del embed
    for li in range(m.n_layers):
        t = time.perf_counter()
        w = jax.block_until_ready(m.arch.layer_weights(m, li))
        times["quantize"] += time.perf_counter() - t
        t = time.perf_counter()
        xs = jax.block_until_ready([m.arch.layer(x, w, m, lower)
                                    for x in xs])
        times["layers"] += time.perf_counter() - t
        del w
    t = time.perf_counter()
    g = served(m, "final_norm")
    w = jax.block_until_ready(quantized(m, "lm_head"))
    times["quantize"] += time.perf_counter() - t
    out = []
    for x, (toks, n_prompt) in zip(xs, seqs):
        z = head(x, n_prompt - 1, g, w, m, rows, lower)
        out.append(np.asarray(z[: len(toks) - n_prompt]))
    return out
