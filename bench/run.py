#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload internlm2-2b.chat --seed 7 \\
        --seconds 30 --trace 0

From the root of a checkout that holds ``BENCHMARK.json``. One process,
one chip: it loads (or, in a fresh checkout, builds and caches) the
cell's weights, warms the step programs up, pre-rolls the traffic,
measures for ``--seconds``, checks what was served against the plain
reference, and prints one JSON line as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. The numbers
compared for ``correct`` go to the last lines of standard error and to
the result line's last key, ``check``.

Exits non-zero without a result line where JAX finds no TPU, or fewer
chips than the cell asks for, or where ``BENCHMARK.json`` or a file of
the cell is missing or malformed.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program reads ICQ_* variables for its defaults; a run takes
    # every setting from the cell's files instead
    for k in [k for k in os.environ if k.startswith("ICQ_")]:
        del os.environ[k]
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))

    from bench import harness, manifest

    try:
        m = manifest.load(ROOT)
        c = manifest.cell(m, args.workload)
    except (OSError, ValueError, KeyError) as e:
        print(f"[bench] {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    w = c["workload"]

    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, "bench", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        res = harness.run(
            manifest.config_path(w["config"], m, ROOT),
            manifest.traffic_path(w["traffic"], ROOT),
            {x["name"]: x["unit"] for x in c["end_to_end"]},
            {x["name"]: x["unit"] for x in c["per_layer"]},
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            chips=w["chips"], root=ROOT, t_start=T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    print(json.dumps(res, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
