#!/usr/bin/env python3
"""Chip smoke test: serve InternLM2-1.8B at its published widths on one TPU.

    python3 chip_smoke.py            # from the root of a checkout

Drives the default quantized serving path once, end to end, in one
process: seeded full-width weights (``launch/serve.py``'s loader),
ICQuant RTN at 3 bits (gamma 0.05), the v2 prepared format on the Pallas
arms, then a paged-KV ``GenerationEngine`` with chunked prefill, so the
dequant arm, the fused decode arm and the paged-attention kernel all run.
Before serving it checks each arm at real width against a plain
reference. Any failed check exits non-zero with no result line.

Not a benchmark: the tokens/s it prints is one cold, short run.

Exits non-zero, printing no result, when JAX finds no TPU. The last line
of standard output is the result:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

ARCH = "internlm2-1.8b"
BITS, GAMMA = 3, 0.05
BATCH, MAX_LEN, CHUNK = 4, 256, 32
N_REQUESTS, PROMPT_LENS, MAX_NEW = 8, (64, 128), 16
SEED = 0

# Fused arm vs x @ W.T at Precision.HIGHEST, per output element, in units
# of the products' root-sum-square sqrt(sum_k (x_k w_k)^2). Both sides
# multiply the same exact f32 weights; summing in another order moves the
# result by about eps_f32 * sqrt(K) ~ 5e-6 of that scale at K = 8192,
# while rounding either operand to bf16 would move it by ~2^-9 ~ 2e-3.
FUSED_TOL = 1e-4
# Paged attention vs the gather arm's math in f32 at HIGHEST, absolute, on
# unit-scale values: the kernel's online softmax only reorders f32 sums
# (~1e-6); any bf16 rounding of q, k, p or v would show as ~1e-3 to 1e-2
# (at the MXU's default precision this check read 7.6e-3 on a v5e).
PAGED_TOL = 1e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _prepared_leaves(params):
    import jax

    from repro.kernels.backend import ICQPrepared

    is_prep = lambda x: isinstance(x, ICQPrepared)  # noqa: E731
    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_prep)[0]
    return [(path, leaf) for path, leaf in flat if is_prep(leaf)]


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def check_arms(prepared) -> None:
    """Dequant arm bitwise, fused arm within FUSED_TOL, on layer 0's
    weights and the LM head."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import backend

    hi = jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(SEED)
    xla_weight = jax.jit(backend._xla_weight)
    dequant = jax.jit(backend.dequantize_prepared)
    fused = jax.jit(backend.linear_apply)
    for path, prep in _prepared_leaves(prepared):
        if prep.codes.ndim == 3:
            prep = jax.tree.map(lambda a: a[0], prep)     # layer 0
        name = _name(path)
        w_ref = xla_weight(prep)
        w_k = dequant(prep)
        if not np.array_equal(np.asarray(w_k), np.asarray(w_ref)):
            fail(f"dequant arm != reference weights for {name}")
        x = jnp.asarray(rng.standard_normal((8, prep.d_in)), jnp.float32)
        if backend.choose_path(8, prep) != "fused":
            fail(f"{name}: an 8-token call did not take the fused arm")
        y = fused(x, prep)
        y_ref = jnp.dot(x, w_ref.T, precision=hi)
        scale = jnp.sqrt(jnp.dot(x * x, (w_ref * w_ref).T, precision=hi))
        err = float(jnp.max(jnp.abs(y - y_ref) / (scale + 1e-30)))
        log(f"arm check {name} {prep.d_out}x{prep.d_in}: dequant bitwise "
            f"equal, fused err {err:.3e} (tol {FUSED_TOL:g})")
        if not err <= FUSED_TOL:
            fail(f"fused arm error {err:.3e} > {FUSED_TOL:g} for {name}")


def check_paged(cfg) -> None:
    """Paged-attention kernel vs the gather arm at the model's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention
    from repro.models.layers import _paged_gather

    hi = jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(SEED + 1)
    Hkv, hd, bs = cfg.n_kv_heads, cfg.resolved_head_dim, 16
    G = cfg.n_heads // Hkv
    n_pt = MAX_LEN // bs
    nb = BATCH * n_pt + 1
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((nb, Hkv, bs, hd)), jnp.float32)
    k_pool, v_pool = pool(), pool()
    q = jnp.asarray(rng.standard_normal((BATCH, Hkv, G, hd)) * hd**-0.5,
                    jnp.float32)
    pages = jnp.asarray(rng.permutation(np.arange(1, nb))
                        .reshape(BATCH, n_pt).astype(np.int32))
    # a full lane, a one-row lane, ragged tails
    kv_len = jnp.asarray(np.resize([MAX_LEN, 1, 77, 200], BATCH)
                         .clip(1, MAX_LEN), jnp.int32)
    out = paged_attention(q, k_pool, v_pool, pages, kv_len)
    k, v = _paged_gather(k_pool, pages), _paged_gather(v_pool, pages)
    s = jnp.einsum("bhgd,bthd->bhgt", q, k, precision=hi)
    valid = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -jnp.inf), -1)
    ref = jnp.einsum("bhgt,bthd->bhgd", p, v, precision=hi)
    err = float(jnp.max(jnp.abs(out - ref)))
    log(f"arm check paged attention B{BATCH} Hkv{Hkv} G{G} hd{hd} bs{bs}: "
        f"max err {err:.3e} (tol {PAGED_TOL:g})")
    if not err <= PAGED_TOL:
        fail(f"paged attention error {err:.3e} > {PAGED_TOL:g}")


class Capture:
    """Wraps a jitted program; keeps the argument shapes of its first call
    so the compiled program can be inspected afterwards."""

    def __init__(self, fn):
        self.fn, self.spec = fn, None

    def __call__(self, *args, **kw):
        import jax

        if self.spec is None:
            self.spec = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None))
                if hasattr(a, "shape") else a, (args, kw))
        return self.fn(*args, **kw)

    def compiled_text(self) -> str:
        args, kw = self.spec
        return self.fn.lower(*args, **kw).compile().as_text()


def main() -> int:
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax

    devices = jax.devices()
    log(f"devices: {devices}")
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform}; this smoke test "
             f"only runs on the chip")

    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.quantize import quantize_tree
    from repro.launch.serve import load_params
    from repro.launch.steps import prepare_serving_params
    from repro.serving import GenerationEngine, Request

    log(f"compile cache: {use_compile_cache(root)}")

    # -- weights -------------------------------------------------------------
    t = time.perf_counter()
    cfg, params = load_params(ARCH, full=True, train_steps=0, seed=SEED)
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t
    t = time.perf_counter()
    qparams, acct = quantize_tree(params, BITS, gamma=GAMMA)
    jax.block_until_ready(qparams)
    t_quant = time.perf_counter() - t
    del params
    t = time.perf_counter()
    prepared = prepare_serving_params(qparams, mode="prepared", fmt="v2")
    jax.block_until_ready(prepared)
    t_prep = time.perf_counter() - t
    del qparams
    leaves = _prepared_leaves(prepared)
    bad = [_name(p) for p, w in leaves
           if w.backend != "pallas" or w.interpret or w.fmt != "v2"]
    if not leaves or bad:
        fail(f"prepared leaves off the compiled v2 Pallas path: {bad}")
    n_w = [math.prod(w.codes.shape[:-2]) * w.d_out * w.d_in
           for _, w in leaves]
    bits = sum(w.bits_per_weight() * n for (_, w), n in zip(leaves, n_w))
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype}")
    log(f"set-up: init {t_init:.1f} s, quantize {t_quant:.1f} s, prepare "
        f"{t_prep:.1f} s")
    log(f"bits/weight: storage {acct['mean_bits']:.4f}, prepared v2 "
        f"{bits / sum(n_w):.4f} over {sum(n_w) / 1e6:.1f}M quantized "
        f"weights in {len(leaves)} leaves (all pallas, compiled)")

    # -- arm checks at real width ---------------------------------------------
    check_arms(prepared)
    check_paged(cfg)

    # -- serving --------------------------------------------------------------
    eng = GenerationEngine(prepared, cfg, batch_size=BATCH, max_len=MAX_LEN,
                           weight_cache="prepared", runtime_fmt="v2",
                           kv_layout="paged", prefill_chunk=CHUNK)
    eng._step_greedy = Capture(eng._step_greedy)
    eng._fused_greedy = Capture(eng._fused_greedy)
    rng = np.random.default_rng(SEED)

    def prompt():
        n = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        return rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    t = time.perf_counter()
    eng.submit(Request(-1, prompt(), max_new_tokens=2))
    eng.run()
    t_compile = time.perf_counter() - t
    log(f"set-up: first compile (warm-up request) {t_compile:.1f} s")

    reqs = [Request(i, prompt(), max_new_tokens=MAX_NEW)
            for i in range(N_REQUESTS)]
    t = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    wall = time.perf_counter() - t
    for r in reqs:
        got = done.get(r.rid)
        status = None if got is None else got.status
        n = 0 if got is None else len(got.generated)
        log(f"request {r.rid}: prompt {len(r.prompt)} tokens, status "
            f"{status}, {n} tokens")
        if status != "ok" or n != MAX_NEW:
            fail(f"request {r.rid} ended {status} with {n}/{MAX_NEW} tokens")
    m = eng.metrics.summary()
    counters = {k: m[k] for k in ("faults", "degraded_steps", "replays",
                                  "paged_attn_window_fallbacks")}
    log(f"engine counters: {counters}; launches {m['launches']:.0f} "
        f"(fused {m['fused_steps']:.0f}, decode {m['decode_steps']:.0f})")
    if any(v != 0 for v in counters.values()):
        fail(f"engine recovered from faults or fell back: {counters}")
    for name, prog in (("decode", eng._step_greedy),
                       ("fused", eng._fused_greedy)):
        if prog.spec is None:
            fail(f"the {name} program never ran")
        n_calls = prog.compiled_text().count("tpu_custom_call")
        log(f"compiled {name} program: {n_calls} tpu_custom_call sites")
        if not n_calls:
            fail(f"compiled {name} program has no Pallas kernel")

    toks = N_REQUESTS * MAX_NEW
    log(f"smoke, not a benchmark: {toks} tokens in {wall:.2f} s = "
        f"{toks / wall:.1f} tokens/s (cold engine, batch {BATCH})")
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
