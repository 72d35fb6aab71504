"""Linear application that dispatches on the weight representation.

Model params hold a dense (d_in, d_out) array, an ``ICQPacked`` weight
(the paper's codec; packed per *output channel*, i.e. over the
transposed matrix), an ``ICQRuntime`` (decode-free bitmap overlay), or
an ``ICQPrepared`` (pre-padded kernel layout — see kernels/backend.py).
Every matmul in the model zoo routes through ``linear`` so ICQuant is a
first-class, drop-in weight format everywhere; prepared weights flow
through the kernel-backed execution layer instead of a full in-graph
``dequantize()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.icquant import (
    ICQPacked,
    ICQRuntime,
    dequantize,
    dequantize_runtime,
)
from repro.kernels.backend import (
    ICQPrepared,
    dequantize_prepared,
    linear_apply,
)


def linear(x: jnp.ndarray, w) -> jnp.ndarray:
    """y = x @ w for dense w of shape (d_in, d_out), ICQPacked (storage
    format: gap-stream decode in-graph), ICQRuntime (serving format:
    decode-free bitmap overlay) or ICQPrepared (kernel execution layer:
    fused Pallas / prepared-XLA dispatch) — all stored per output
    channel. Its operations carry the program scope ``icq_linear``, which
    a device trace reads to attribute time to the model's linears."""
    with jax.named_scope("icq_linear"):
        if isinstance(w, ICQPrepared):
            return linear_apply(x, w)
        if isinstance(w, ICQPacked):
            w_hat = dequantize(w)            # (d_out, d_in)
            return x @ w_hat.T.astype(x.dtype)
        if isinstance(w, ICQRuntime):
            w_hat = dequantize_runtime(w)
            return x @ w_hat.T.astype(x.dtype)
        return x @ w


def as_dense(w, dtype=None) -> jnp.ndarray:
    """Materialize a weight as a dense (d_in, d_out) array."""
    if isinstance(w, (ICQPacked, ICQRuntime, ICQPrepared)):
        if isinstance(w, ICQPacked):
            w_hat = dequantize(w)
        elif isinstance(w, ICQRuntime):
            w_hat = dequantize_runtime(w)
        else:
            w_hat = dequantize_prepared(w)
        w_hat = jnp.swapaxes(w_hat, -1, -2)          # (..., d_in, d_out)
        return w_hat.astype(dtype) if dtype is not None else w_hat
    return w


def weight_shape(w):
    """Logical (d_in, d_out) of any weight representation."""
    if isinstance(w, (ICQPacked, ICQRuntime, ICQPrepared)):
        return (w.d_in, w.d_out)
    return w.shape
