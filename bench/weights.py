"""Seeded weights, and the cache of the serving program's prepared tree.

``leaf(model, name, layer)`` draws one layer of one weight leaf in float32
from the configuration's ``weight_seed``; the plain reference
(``bench/reference.py``) calls it to draw the same numbers again. The
serving program gets them quantized and prepared by its own code
(``quantize_tree``, ``prepare_serving_params``), once per checkout: the
prepared tree is written to ``bench/.cache/`` under a key that covers the
configuration file, this file, the architecture's module and every source
file of the program, so a changed quantizer, layout or tree never reads a
stale entry. Later runs load it.

Which leaves there are, their order (whose index seeds each draw), their
shapes and the tree they make are the architecture module's
(``bench/archs/``). An entry of its ``LEAVES`` is ``(name, layers)``:
``True`` for a leaf in every layer, ``False`` for a global leaf, or the
layers that hold it (``range(1, 27)``, or ``slice(1, None)`` for every
layer after the first); a leaf is stacked over its own layers. A leaf
of three axes, ``(E, d_in, d_out)``, carries a leading expert axis: the
module's optional ``leaf_experts(model, name)`` names the experts held
(all of them by default), and expert ``e`` of layer ``l`` is drawn from
``fold_in(key(seed, index, l), e)``, so a share of the experts draws the
numbers the whole model has there. ``build`` draws, quantizes and
prepares one layer of one leaf at a time.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import hashlib
import os
import pickle
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

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
    # linears: 1/sqrt(d_in), the second-to-last axis of an expert stack
    return 1.0 / float(np.sqrt(shape[-2] if len(shape) >= 2 else shape[0]))


def _key(seed: int, index: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index), layer)


def _draw(seed: int, index: int, layer: int, shape, std: float, norm: bool):
    z = jax.random.normal(_key(seed, index, layer), shape, jnp.float32)
    return 1.0 + NORM_STD * z if norm else z * std


def _draw_experts(seed: int, index: int, layer: int, ids, shape,
                  std: float):
    """(len(ids), *shape): expert ``e`` from its own key."""
    key = _key(seed, index, layer)
    z = jax.vmap(lambda e: jax.random.normal(
        jax.random.fold_in(key, e), shape, jnp.float32))(ids)
    return z * std


_draw_jit = jax.jit(_draw, static_argnums=(3, 4, 5))
_draw_experts_jit = jax.jit(_draw_experts, static_argnums=(4, 5))


def layers(m: Model, name: str) -> Optional[Sequence[int]]:
    """The layers that hold a leaf, or None for a global leaf."""
    spec = dict(m.arch.LEAVES)[name]
    if spec is True:
        return range(m.n_layers)
    if spec is False:
        return None
    if isinstance(spec, slice):
        return range(m.n_layers)[spec]
    return spec


def experts(m: Model, name: str) -> Sequence[int]:
    """The experts an expert leaf holds, by their ids in the whole model."""
    held = getattr(m.arch, "leaf_experts", None)
    n = tuple(m.arch.leaf_shape(m, name))[0]
    ids = range(n) if held is None else held(m, name)
    if len(ids) != n:
        raise ValueError(f"{name}: {len(ids)} experts held, leaf_shape "
                         f"has {n}")
    return ids


def leaf(m: Model, name: str, layer: int = 0) -> jax.Array:
    """One float32 leaf (layer ``layer`` of a per-layer leaf), seeded by
    its index in the architecture's ``LEAVES``."""
    index = [n for n, _ in m.arch.LEAVES].index(name)
    shape = tuple(m.arch.leaf_shape(m, name))
    std = _std(name, shape)
    if len(shape) == 3:
        ids = jnp.asarray(list(experts(m, name)), jnp.uint32)
        return _draw_experts_jit(m.weight_seed, index, layer, ids,
                                 shape[1:], std)
    return _draw_jit(m.weight_seed, index, layer, shape, std,
                     name in m.arch.NORMS)


def stacked(m: Model, name: str) -> jax.Array:
    """A per-layer leaf over its own layers, stacked on a leading axis."""
    return jnp.stack([leaf(m, name, li) for li in layers(m, name)])


# ---------------------------------------------------------------------------
# the serving program's weights
# ---------------------------------------------------------------------------

def _with_crc(x):
    """A prepared leaf with the checksums of its own sidecars, as the
    program records them when it prepares a whole stack at once."""
    from repro.kernels import backend

    if isinstance(x, backend.ICQPrepared) and x.crc is not None:
        x = dataclasses.replace(x, crc=backend._sidecar_crcs(
            x.syms, x.offs, x.dbase))
    return x


def stack(parts: List[object]):
    """Per-layer host leaves (arrays or prepared weights) stacked on a
    leading layer axis: the leaf the program's whole-stack call gives."""
    from repro.kernels import backend

    def shape_of(p):
        if isinstance(p, backend.ICQPrepared):
            p = dataclasses.replace(p, crc=None)
        return jax.tree.structure(p)

    if any(shape_of(p) != shape_of(parts[0]) for p in parts):
        raise ValueError(f"the layers of one leaf were prepared in "
                         f"different layouts; the first: "
                         f"{shape_of(parts[0])}")
    return _with_crc(jax.tree.unflatten(
        jax.tree.structure(parts[0]),
        [np.stack(xs) for xs in zip(*map(jax.tree.leaves, parts))]))


def layer_slice(x, lo: int, hi: int):
    """Layers ``lo:hi`` of a stacked host leaf, for a tree that keeps some
    layers apart (a leading dense stack)."""
    return _with_crc(jax.tree.map(lambda a: a[lo:hi], x))


def _pad_streams(packs: List[object]) -> List[object]:
    """Every layer's gap streams padded to the widest with the flag
    symbol, as ``quantize_tree`` pads the slices of one stack."""
    from repro.core.icquant import ICQPacked

    if not isinstance(packs[0], ICQPacked):
        return packs
    width = max(p.symbols.shape[-1] for p in packs)
    flag = (1 << packs[0].b) - 1
    return [dataclasses.replace(p, symbols=np.pad(
        p.symbols, [(0, 0)] * (p.symbols.ndim - 1)
        + [(0, width - p.symbols.shape[-1])], constant_values=flag))
        for p in packs]


def built_leaf(m: Model, name: str, times: Dict[str, float]):
    """One leaf as the program serves it, on the host, built one layer at
    a time: each layer drawn and quantized, the layers' gap streams
    padded to one width, each layer prepared, the prepared layers
    stacked. That is what the program's calls on the whole stack give,
    with no float32 array on the device larger than one layer. A leaf the
    program leaves unquantized (a router) stays as drawn. Returns the
    leaf, its quantized weights and their storage bits; ``times`` gathers
    the seconds spent drawing, quantizing and preparing."""
    from repro.launch.quantize import quantize_tree
    from repro.launch.steps import prepare_serving_params

    ls = layers(m, name)
    served = name in m.arch.NORMS or name == "embed"
    parts = []
    bits = n_w = 0.0
    for li in (ls if ls is not None else [0]):
        t = time.perf_counter()
        w = jax.block_until_ready(leaf(m, name, li))
        times["draw"] += time.perf_counter() - t
        if served:
            parts.append(np.asarray(w.astype(jnp.dtype(m.dtype))))
            continue
        t = time.perf_counter()
        q, acct = quantize_tree({name: w}, m.bits, gamma=m.gamma,
                                method=m.method)
        del w
        parts.append(jax.device_get(q[name]))
        del q
        times["quantize"] += time.perf_counter() - t
        bits += acct["mean_bits"] * acct["quantized_weights"]
        n_w += acct["quantized_weights"]
    if not served:
        parts = _pad_streams(parts)
        t = time.perf_counter()
        for i, q in enumerate(parts):
            p = prepare_serving_params({name: q}, mode="prepared", fmt=m.fmt)
            parts[i] = jax.device_get(p[name])
            del p
        times["prepare"] += time.perf_counter() - t
    return (parts[0] if ls is None else stack(parts)), n_w, bits


def build(m: Model, log: Callable[[str], None] = print):
    """The program's prepared parameter tree on the host, one leaf at a
    time (``built_leaf``). Returns the tree and the seconds spent
    drawing, quantizing and preparing."""
    times = dict(draw=0.0, quantize=0.0, prepare=0.0)
    out: Dict[str, object] = {}
    bits = n_w = 0.0
    for name, _ in m.arch.LEAVES:
        out[name], n, b = built_leaf(m, name, times)
        n_w += n
        bits += b
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
