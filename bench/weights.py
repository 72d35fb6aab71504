"""Seeded weights, and the cache of the serving program's prepared tree.

``leaf(model, name, layer)`` draws one weight matrix (or norm vector) in
float32 from the configuration's ``weight_seed``; the plain reference
(``bench/reference.py``) calls it to draw the same numbers again. The
serving program gets them quantized and prepared by its own code
(``quantize_tree``, ``prepare_serving_params``), once per checkout: the
prepared tree is written to ``bench/.cache/`` under a key that covers the
configuration file, this file, the architecture's module and every source
file of the program, so a changed quantizer, layout or tree never reads a
stale entry. Later runs load it. Which leaves there are, their order
(whose index seeds each draw), their shapes and the tree they make are
the architecture module's (``bench/archs/``).
"""
from __future__ import annotations

import gc
import glob
import hashlib
import os
import pickle
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Model

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")

EMBED_STD = 0.02     # initializer_range of both published configs
NORM_STD = 0.05      # norm gains 1 + 0.05 N(0, 1), so a dropped gain shows


def _std(name: str, shape) -> float:
    if name == "embed":
        return EMBED_STD
    return 1.0 / float(np.sqrt(shape[0]))   # linears: 1/sqrt(d_in)


def _draw(seed: int, index: int, layer: int, shape, std: float, norm: bool):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index), layer)
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + NORM_STD * z if norm else z * std


_draw_jit = jax.jit(_draw, static_argnums=(3, 4, 5))


def leaf(m: Model, name: str, layer: int = 0) -> jax.Array:
    """One float32 leaf (layer ``layer`` of a per-layer leaf), seeded by
    its index in the architecture's ``LEAVES``."""
    index = [n for n, _ in m.arch.LEAVES].index(name)
    shape = tuple(m.arch.leaf_shape(m, name))
    return _draw_jit(m.weight_seed, index, layer, shape,
                     _std(name, shape), name in m.arch.NORMS)


def stacked(m: Model, name: str) -> jax.Array:
    return jnp.stack([leaf(m, name, layer) for layer in range(m.n_layers)])


# ---------------------------------------------------------------------------
# the serving program's weights
# ---------------------------------------------------------------------------

def build(m: Model, log: Callable[[str], None] = print):
    """The program's prepared parameter tree, quantized leaf by leaf (a
    whole float32 model may not fit the chip at once). Returns the tree
    and the seconds spent drawing, quantizing and preparing."""
    from repro.launch.quantize import quantize_tree
    from repro.launch.steps import prepare_serving_params

    dt = jnp.dtype(m.dtype)
    times = dict(draw=0.0, quantize=0.0, prepare=0.0)
    out: Dict[str, object] = {}
    bits = n_w = 0.0
    for name, per_layer in m.arch.LEAVES:
        t = time.perf_counter()
        w = stacked(m, name) if per_layer else leaf(m, name)
        jax.block_until_ready(w)
        times["draw"] += time.perf_counter() - t
        if name in m.arch.NORMS or name == "embed":
            out[name] = w.astype(dt)
            continue
        t = time.perf_counter()
        q, acct = quantize_tree({name: w}, m.bits, gamma=m.gamma,
                                method=m.method)
        jax.block_until_ready(q)
        del w
        times["quantize"] += time.perf_counter() - t
        bits += acct["mean_bits"] * acct["quantized_weights"]
        n_w += acct["quantized_weights"]
        t = time.perf_counter()
        p = prepare_serving_params(q, mode="prepared", fmt=m.fmt)
        jax.block_until_ready(p)
        times["prepare"] += time.perf_counter() - t
        out[name] = p[name]
        del q
    tree = m.arch.tree(out)
    log(f"weights built: storage {bits / max(n_w, 1):.4f} bits/weight over "
        f"{n_w / 1e6:.1f}M quantized weights; draw {times['draw']:.1f} s, "
        f"quantize {times['quantize']:.1f} s, prepare "
        f"{times['prepare']:.1f} s")
    return tree, times


def prepared_bytes(leaf) -> int:
    """Bytes of a prepared leaf as it sits in HBM, without the selector
    memo that only the XLA arm reads."""
    total = sum(int(a.nbytes) for a in jax.tree.leaves(leaf))
    memo = getattr(leaf, "sel_memo", None)
    return total - (int(memo.nbytes) if memo is not None else 0)


def source_key(m: Model, config_file: str, root: str = ROOT) -> str:
    """Hash of what the prepared tree depends on: the configuration file,
    this file, the architecture's module, the program's sources, JAX's
    version and the platform."""
    h = hashlib.sha256()
    files = [config_file, os.path.abspath(__file__), m.arch.__file__]
    files += sorted(glob.glob(os.path.join(root, "src", "repro", "**",
                                           "*.py"), recursive=True))
    for f in files:
        h.update(os.path.relpath(f, root).encode())
        with open(f, "rb") as fh:
            h.update(fh.read())
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    return h.hexdigest()[:20]


def cache_file(m: Model, key: str, cache_dir: str = CACHE_DIR) -> str:
    return os.path.join(cache_dir, f"{m.name}-{key}.pkl")


def load_or_build(m: Model, config_file: str, cache_dir: str = CACHE_DIR,
                  log: Callable[[str], None] = print,
                  root: str = ROOT) -> Tuple[object, Optional[dict]]:
    """(prepared tree on the device, build seconds or None on a hit)."""
    path = cache_file(m, source_key(m, config_file, root), cache_dir)
    if os.path.exists(path):
        with open(path, "rb") as f:
            host = pickle.load(f)
        return jax.device_put(host), None
    tree, times = build(m, log)
    host = jax.tree.map(np.asarray, tree)
    # the first run then serves from the state a later run starts in: the
    # build's buffers and programs gone, the weights put on the device
    # from the host copy
    del tree
    jax.clear_caches()
    gc.collect()
    os.makedirs(cache_dir, exist_ok=True)
    for stale in glob.glob(os.path.join(cache_dir, f"{m.name}-*.pkl")):
        os.remove(stale)
    t = time.perf_counter()
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(host, f, protocol=4)
        # on disk now, in set-up, and not flushed later inside the window
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    times["save"] = time.perf_counter() - t
    return jax.device_put(host), times
