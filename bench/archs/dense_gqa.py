"""The dense GQA decoder: everything the benchmark knows of its block.

RMSNorm, rotary embedding in the rotate-half layout, causal softmax
attention with ``num_key_value_heads`` shared KV heads (MHA where they
equal the query heads; an optional sliding window), and a SwiGLU MLP,
as InternLM2 and Phi-3 publish them. The serving program runs it as its
``dense`` family with GQA attention; its fused ``wqkv`` / ``qkv_proj``
and ``gate_up_proj`` are served as separate matrices, the same products.

This module gives, for a ``bench.model.Model`` whose ``block`` it read:
the sizes (``block``); the program's ``ModelConfig``
(``program_config``); the weight leaves in the fixed order whose index
seeds each draw, their shapes, and the tree the program takes
(``LEAVES``, ``NORMS``, ``leaf_shape``, ``tree``); the prepared weight
bytes of one layer (``layer_bytes``); the plain reference layer
(``layer_weights``, ``layer``); the work counts of a launch
(``kv_row_bytes``, ``step_flops``, ``step_bytes``); and the calls each
kernel makes in a launch (``matmul_calls``, ``dequant_calls``,
``paged_attention_calls``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench import reference, weights

ARCHITECTURES = ("InternLM2ForCausalLM", "Phi3ForCausalLM")


@dataclasses.dataclass(frozen=True)
class Block:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    sliding_window: int        # 0: full attention


def block(p: dict) -> Block:
    """The block's sizes from the published ``config.json`` keys."""
    heads = p["num_attention_heads"]
    return Block(
        n_heads=heads,
        n_kv_heads=p["num_key_value_heads"],
        head_dim=p.get("head_dim", p["hidden_size"] // heads),
        d_ff=p["intermediate_size"],
        rope_theta=float(p["rope_theta"]),
        sliding_window=int(p.get("sliding_window") or 0))


def q_dim(m) -> int:
    return m.block.n_heads * m.block.head_dim


def kv_dim(m) -> int:
    return m.block.n_kv_heads * m.block.head_dim


def program_config(m):
    """The serving program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig

    b = m.block
    return ModelConfig(
        name=m.name, family="dense", n_layers=m.n_layers,
        d_model=m.d_model, n_heads=b.n_heads, n_kv_heads=b.n_kv_heads,
        head_dim=b.head_dim, d_ff=b.d_ff, vocab_size=m.vocab,
        rope_theta=b.rope_theta, norm_eps=m.norm_eps,
        sliding_window=b.sliding_window, param_dtype=m.dtype,
        tie_embeddings=False)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

# (name, per layer?) in a fixed order: the index seeds the leaf
LEAVES = (("wq", True), ("wk", True), ("wv", True), ("wo", True),
          ("w_gate", True), ("w_up", True), ("w_down", True),
          ("ln1", True), ("ln2", True),
          ("embed", False), ("final_norm", False), ("lm_head", False))
NORMS = ("ln1", "ln2", "final_norm")
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


def layer_linears(m):
    """(name, d_in, d_out) of each quantized matrix of one layer."""
    d, F = m.d_model, m.block.d_ff
    return (("wq", d, q_dim(m)), ("wk", d, kv_dim(m)),
            ("wv", d, kv_dim(m)), ("wo", q_dim(m), d),
            ("w_gate", d, F), ("w_up", d, F), ("w_down", F, d))


def leaf_shape(m, name: str):
    mats = {n: (a, b) for n, a, b in layer_linears(m)}
    if name in mats:
        return mats[name]
    if name in NORMS:
        return (m.d_model,)
    if name == "embed":
        return (m.vocab, m.d_model)
    if name == "lm_head":
        return (m.d_model, m.vocab)
    raise KeyError(name)


def tree(out):
    """The program's parameter tree from its leaves by name (layer leaves
    stacked on a leading axis)."""
    return dict(
        embed=out["embed"], final_norm=out["final_norm"],
        lm_head=out["lm_head"],
        stack=dict(ln1=out["ln1"], ln2=out["ln2"],
                   attn={n: out[n] for n in ATTN},
                   mlp={n: out[n] for n in MLP}))


def layer_bytes(m, params):
    """Prepared bytes of each quantized matrix of one layer, by name."""
    st = params["stack"]
    out = {n: weights.prepared_bytes(st["attn"][n]) // m.n_layers
           for n in ATTN}
    out.update({n: weights.prepared_bytes(st["mlp"][n]) // m.n_layers
                for n in MLP})
    return out


# ---------------------------------------------------------------------------
# the plain reference (bench/reference.py runs it layer by layer)
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """x (T, H, D); rotate-half rotary embedding at positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, w, g, m, lower: bool):
    """One decoder layer over a whole sequence x (T, d)."""
    _mm, _lower, HI = reference._mm, reference._lower, reference.HI
    T = x.shape[0]
    H, Hk, D = m.block.n_heads, m.block.n_kv_heads, m.block.head_dim
    theta = m.block.rope_theta
    h = reference._rms(x, g[0], m.norm_eps)
    q = _rope(_mm(h, w["wq"], lower).reshape(T, H, D), theta)
    k = _rope(_mm(h, w["wk"], lower).reshape(T, Hk, D), theta)
    v = _mm(h, w["wv"], lower).reshape(T, Hk, D)
    rep = H // Hk
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if lower:
        q, k, v = _lower(q), _lower(k), _lower(v)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(D)
    i = jnp.arange(T)
    mask = i[None, :] <= i[:, None]
    if m.block.sliding_window:
        mask &= i[None, :] > i[:, None] - m.block.sliding_window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if lower:
        p = _lower(p)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, H * D)
    x = x + _mm(o, w["wo"], lower)
    h = reference._rms(x, g[1], m.norm_eps)
    a = _mm(h, w["w_gate"], lower)
    b = _mm(h, w["w_up"], lower)
    return x + _mm(jax.nn.silu(a) * b, w["w_down"], lower)


def layer_weights(m, li: int):
    """Layer ``li``'s matrices through the reference's own quantizer, and
    its two norm gains as served."""
    w = {n: reference.quantized(m, n, li) for n, _, _ in layer_linears(m)}
    g = jnp.stack([reference.served(m, "ln1", li),
                   reference.served(m, "ln2", li)])
    return w, g


def layer(x, lw, m, lower: bool):
    w, g = lw
    return _layer(x, w, g, m, lower)


# ---------------------------------------------------------------------------
# work of a launch (bench/work.py) and the calls of each kernel
# ---------------------------------------------------------------------------

def kv_row_bytes(m) -> int:
    """Bytes of K and V one token adds to the cache, all layers."""
    return 2 * m.n_layers * kv_dim(m) * m.itemsize


def linear_params(m) -> int:
    """Quantized weights of every layer, without the LM head."""
    return m.n_layers * sum(a * b for _, a, b in layer_linears(m))


def attn_flops(m, ctx: int, n: int) -> int:
    """QK^T and PV over every layer for n new tokens after ctx rows."""
    keys = n * ctx + n * (n + 1) // 2
    return 4 * m.n_layers * m.block.n_heads * m.block.head_dim * keys


def step_flops(m, s) -> int:
    toks = sum(n for _, n in s.lanes)
    lin = 2 * linear_params(m) * toks
    head = 2 * m.d_model * m.vocab * len(s.lanes)
    return lin + head + sum(attn_flops(m, c, n) for c, n in s.lanes)


def step_bytes(m, w, s) -> int:
    wts = m.n_layers * sum(w.layer_bytes.values()) + w.lm_head_bytes
    kv = sum((c + n) * kv_row_bytes(m) for c, n in s.lanes)
    embed = sum(n for _, n in s.lanes) * m.d_model * m.itemsize
    return wts + kv + embed


def matmul_calls(m, w, step, batch: int):
    """(M, K, N, weight bytes) of the fused-arm calls of one launch: a
    decode launch takes every layer matrix and the LM head at M = batch;
    a fused launch only the LM head (one logits row per lane), its layer
    matrices going through the dequant arm."""
    head = [(batch, m.d_model, m.vocab, w.lm_head_bytes)]
    if step.kind == "fused":
        return head
    per = [(batch, a, b, w.layer_bytes[n]) for n, a, b in layer_linears(m)]
    return per * m.n_layers + head


def dequant_calls(m, w, step):
    """(K, N, weight bytes) of the dequant-arm calls of one launch: every
    layer matrix of a fused launch (M = batch x chunk); none in a decode
    launch."""
    if step.kind != "fused":
        return []
    per = [(a, b, w.layer_bytes[n]) for n, a, b in layer_linears(m)]
    return per * m.n_layers


def paged_attention_calls(m, step, batch: int):
    """(FLOPs, bytes) of each paged-attention call of one launch: one a
    layer in a decode launch, none in a fused one. For each live lane,
    QK^T and PV over its live KV rows (4 x heads x head_dim x rows
    operations) and those rows of K and V read once, plus the float32
    queries in and outputs out."""
    if step.kind != "decode":
        return []
    rows = sum(c + k for c, k in step.lanes)
    b = m.block
    flops = 4 * b.n_heads * b.head_dim * rows
    nbytes = 2 * rows * kv_dim(m) * m.itemsize
    nbytes += 2 * batch * b.n_heads * b.head_dim * 4
    return [(flops, nbytes)] * m.n_layers
