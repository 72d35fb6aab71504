"""JAX's persistent compile cache, switched on by an entry point.

Library modules never set a cache path; ``launch/serve.py``,
``benchmarks/run.py`` and ``chip_smoke.py`` call ``use_compile_cache``
first thing in ``main``.
"""
from __future__ import annotations

import os

import jax

#: root of the checkout this package runs from
CHECKOUT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir))


def use_compile_cache(root: str = CHECKOUT) -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set. Otherwise the cache lives at the
    fixed path ``<root>/.jax_cache``, which git ignores: the path is part
    of what a later run must find again, so it never depends on a
    temporary name, a process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
