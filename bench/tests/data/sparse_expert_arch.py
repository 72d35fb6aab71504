"""A test-only sparse-expert decoder, to show that an architecture whose
leaves cover only some layers and carry an expert axis is added as a
module alone. Not a model the benchmark serves.

Layer 0 is dense (GQA attention, SwiGLU MLP); every later layer holds
``n_routed_experts`` SwiGLU experts of width ``moe_intermediate_size``,
softmax top-``num_experts_per_tok`` routing with the chosen gates
renormalised to sum to 1, and ``n_shared_experts`` shared experts that
every token takes: the serving program's ``moe`` family as it stands,
run dropless (``capacity_factor`` = the number of experts). The router
stays float32, as the program keeps it. Its ``STEP_COUNTERS`` name an
engine counter the engine keeps today (live rows of a fused launch), in
the place where a sparse-expert model would name the experts a launch
routed to.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench import reference, weights

ARCHITECTURES = ("SparseExpertForTest",)
STEP_COUNTERS = ("fused_rows_live",)


@dataclasses.dataclass(frozen=True)
class Block:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                  # the dense layer's MLP
    d_expert: int
    n_experts: int
    n_shared: int
    top_k: int
    rope_theta: float
    experts: range             # the experts held, by id in the whole model


def block(p: dict) -> Block:
    heads = p["num_attention_heads"]
    return Block(
        n_heads=heads, n_kv_heads=p["num_key_value_heads"],
        head_dim=p["hidden_size"] // heads, d_ff=p["intermediate_size"],
        d_expert=p["moe_intermediate_size"],
        n_experts=p["n_routed_experts"], n_shared=p["n_shared_experts"],
        top_k=p["num_experts_per_tok"], rope_theta=float(p["rope_theta"]),
        experts=range(p["n_routed_experts"]))


def program_config(m):
    from repro.configs.base import ModelConfig

    b = m.block
    return ModelConfig(
        name=m.name, family="moe", n_layers=m.n_layers, d_model=m.d_model,
        n_heads=b.n_heads, n_kv_heads=b.n_kv_heads, head_dim=b.head_dim,
        d_ff=b.d_ff, vocab_size=m.vocab, rope_theta=b.rope_theta,
        norm_eps=m.norm_eps, param_dtype=m.dtype, tie_embeddings=False,
        n_experts=b.n_experts, n_shared_experts=b.n_shared,
        experts_per_token=b.top_k, moe_d_ff=b.d_expert,
        first_dense_layers=1, capacity_factor=float(b.n_experts))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

DENSE, SPARSE = slice(0, 1), slice(1, None)
LEAVES = (("wq", True), ("wk", True), ("wv", True), ("wo", True),
          ("w_gate", DENSE), ("w_up", DENSE), ("w_down", DENSE),
          ("router", SPARSE), ("expert_gate", SPARSE),
          ("expert_up", SPARSE), ("expert_down", SPARSE),
          ("shared_gate", SPARSE), ("shared_up", SPARSE),
          ("shared_down", SPARSE),
          ("ln1", True), ("ln2", True),
          ("embed", False), ("final_norm", False), ("lm_head", False))
NORMS = ("ln1", "ln2", "final_norm")
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
EXPERT = ("expert_gate", "expert_up", "expert_down")
SHARED = ("shared_gate", "shared_up", "shared_down")


def _mats(m):
    d, b = m.d_model, m.block
    q, kv, fs = b.n_heads * b.head_dim, b.n_kv_heads * b.head_dim, \
        b.d_expert * b.n_shared
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, b.d_ff), "w_up": (d, b.d_ff), "w_down": (b.d_ff, d),
            "router": (d, b.n_experts),
            "expert_gate": (d, b.d_expert), "expert_up": (d, b.d_expert),
            "expert_down": (b.d_expert, d),
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d)}


def leaf_shape(m, name: str):
    if name in EXPERT:
        return (len(m.block.experts),) + _mats(m)[name]
    if name in NORMS:
        return (m.d_model,)
    if name == "embed":
        return (m.vocab, m.d_model)
    if name == "lm_head":
        return (m.d_model, m.vocab)
    return _mats(m)[name]


def leaf_experts(m, name: str):
    return m.block.experts


def tree(out):
    def part(lo, hi):
        return dict(ln1=weights.layer_slice(out["ln1"], lo, hi),
                    ln2=weights.layer_slice(out["ln2"], lo, hi),
                    attn={n: weights.layer_slice(out[n], lo, hi)
                          for n in ATTN})

    dense, sparse = part(0, 1), part(1, None)
    dense["mlp"] = {n: out[n] for n in MLP}
    sparse["moe"] = dict(
        router=out["router"],
        w_gate=out["expert_gate"], w_up=out["expert_up"],
        w_down=out["expert_down"],
        shared=dict(w_gate=out["shared_gate"], w_up=out["shared_up"],
                    w_down=out["shared_down"]))
    return dict(embed=out["embed"], final_norm=out["final_norm"],
                lm_head=out["lm_head"], dense_stack=dense, stack=sparse)


def layer_bytes(m, params):
    """Prepared bytes of each quantized leaf in one of its layers."""
    d, s = params["dense_stack"], params["stack"]
    pb, n_sparse = weights.prepared_bytes, m.n_layers - 1
    out = {n: (pb(d["attn"][n]) + pb(s["attn"][n])) // m.n_layers
           for n in ATTN}
    out.update({n: pb(d["mlp"][n]) for n in MLP})
    moe = s["moe"]
    for n, k in zip(EXPERT + SHARED, ("w_gate", "w_up", "w_down") * 2):
        x = moe[k] if n in EXPERT else moe["shared"][k]
        out[n] = pb(x) // n_sparse
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rope(x, theta):
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, g, u, d, lower):
    mm = reference._mm
    return mm(jax.nn.silu(mm(h, g, lower)) * mm(h, u, lower), d, lower)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, w, g, m, lower: bool):
    """One layer over a whole sequence x (T, d): attention, then the
    dense MLP or the routed and shared experts."""
    mm, HI = reference._mm, reference.HI
    T = x.shape[0]
    b = m.block
    H, Hk, D = b.n_heads, b.n_kv_heads, b.head_dim
    h = reference._rms(x, g[0], m.norm_eps)
    q = _rope(mm(h, w["wq"], lower).reshape(T, H, D), b.rope_theta)
    k = _rope(mm(h, w["wk"], lower).reshape(T, Hk, D), b.rope_theta)
    v = mm(h, w["wv"], lower).reshape(T, Hk, D)
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(D)
    i = jnp.arange(T)
    s = jnp.where((i[None, :] <= i[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + mm(o.reshape(T, H * D), w["wo"], lower)
    h = reference._rms(x, g[1], m.norm_eps)
    if "router" not in w:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], lower)
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=HI), -1)
    gate, idx = jax.lax.top_k(probs, b.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    mix = jnp.zeros_like(probs).at[i[:, None], idx].set(gate)     # (T, E)
    ys = jax.vmap(lambda a, c, e: _swiglu(h, a, c, e, lower))(
        w["expert_gate"], w["expert_up"], w["expert_down"])        # (E, T, d)
    y = jnp.einsum("te,etd->td", mix, ys, precision=HI)
    return x + y + _swiglu(h, w["shared_gate"], w["shared_up"],
                           w["shared_down"], lower)


def layer_weights(m, li: int):
    names = ATTN + (MLP if li == 0 else EXPERT + SHARED)
    w = {n: reference.quantized(m, n, li) for n in names}
    if li > 0:
        w["router"] = weights.leaf(m, "router", li)     # served float32
    g = jnp.stack([reference.served(m, "ln1", li),
                   reference.served(m, "ln2", li)])
    return w, g


def layer(x, lw, m, lower: bool):
    w, g = lw
    return _layer(x, w, g, m, lower)


# ---------------------------------------------------------------------------
# work of a launch
# ---------------------------------------------------------------------------

def kv_row_bytes(m) -> int:
    return 2 * m.n_layers * m.block.n_kv_heads * m.block.head_dim \
        * m.itemsize


def step_flops(m, s) -> int:
    b, mats = m.block, _mats(m)
    per_tok = sum(2 * a * c for n, (a, c) in mats.items()
                  if n in ATTN) * m.n_layers
    per_tok += sum(2 * a * c for n, (a, c) in mats.items() if n in MLP)
    per_tok += (m.n_layers - 1) * sum(
        2 * a * c * (b.top_k if n in EXPERT else 1)
        for n, (a, c) in mats.items() if n in EXPERT + SHARED + ("router",))
    toks = sum(n for _, n in s.lanes)
    return per_tok * toks + 2 * m.d_model * m.vocab * len(s.lanes)


def step_bytes(m, w, s) -> int:
    """Weights read, with only the experts a launch can have routed to:
    at most ``top_k`` a live row (the launch's counter), at most all."""
    rows = s.counters["fused_rows_live"] or len(s.lanes)
    routed = min(m.block.n_experts, m.block.top_k * rows)
    lb = w.layer_bytes
    dense = m.n_layers * sum(lb[n] for n in ATTN) + sum(lb[n] for n in MLP)
    sparse = (m.n_layers - 1) * (sum(lb[n] for n in SHARED) + sum(
        lb[n] for n in EXPERT) * routed // m.block.n_experts)
    kv = sum((c + n) * kv_row_bytes(m) for c, n in s.lanes)
    return dense + sparse + w.lm_head_bytes + kv
