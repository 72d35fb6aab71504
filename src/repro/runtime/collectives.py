"""Cross-pod gradient reduction with int8 compression.

The inter-pod hop is the thin link of the multi-pod mesh (DCN / long ICI),
so the gradient all-reduce is split hierarchically:

  intra-pod: native reduce-scatter/all-reduce (GSPMD-inserted, full bw)
  inter-pod: int8-quantized all-gather + local dequant-sum  (4x fewer
             bytes on the thin link than f32, 2x fewer than bf16)

Exposed as ``compressed_cross_pod_mean`` — a shard_map over the ``pod``
axis only (other mesh axes stay under automatic sharding propagation).
Error feedback (optim.compression.error_feedback_update) runs *before*
this reduction in the train step, keeping the quantization unbiased over
time.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.optim.compression import BLOCK


def _compress(x):
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


def _leaf_mean(x, axis_name: str, n_pods: int):
    shape = x.shape
    q, s = _compress(x)
    qg = jax.lax.all_gather(q, axis_name)          # int8 on the wire
    sg = jax.lax.all_gather(s, axis_name)          # f32 scales (tiny)
    summed = (qg.astype(jnp.float32) * sg).sum(axis=0)
    flat = summed.reshape(-1)
    size = 1
    for d in shape:
        size *= d
    return (flat[:size].reshape(shape) / n_pods).astype(x.dtype)


def compressed_cross_pod_mean(tree: Any, mesh, axis_name: str = "pod") -> Any:
    """Mean-reduce a pytree across the pod axis with int8 on the wire.

    Must be called inside a computation already running under `mesh`;
    tensors keep their data/model shardings (auto axes)."""
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    P = jax.sharding.PartitionSpec
    # the gathered+summed result is replicated over `pod` by
    # construction; the static VMA checker can't prove it
    smap = functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
        axis_names={axis_name}, check_vma=False,
    )

    @smap
    def reduce_tree(t):
        return jax.tree.map(
            lambda x: _leaf_mean(x, axis_name, n_pods), t
        )

    return reduce_tree(tree)
