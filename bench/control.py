#!/usr/bin/env python3
"""The correctness check's control, on the chip, at a cell's own size.

    python3 bench/control.py --workload internlm2-2b.chat \\
        --seeds 11,12,13 --seconds 20

For each seed: one run of the cell with a short window at the cell's own
load, then, on the same sample of served requests, the program's verdict
and the control's: the reference computed in float8, one step below the
configuration's bfloat16 (``bench/reference.py``), put in the program's
place and judged by the same ``check.verdict``. Prints one JSON line per
seed. The benchmark's own runs never run the control; its
readings set ``check.logit_gap_limit`` in the configuration files (see
``PERF.md``).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    for k in [k for k in os.environ if k.startswith("ICQ_")]:
        del os.environ[k]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness, manifest

    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, "bench",
                                                     ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    m = manifest.load(ROOT)
    w = manifest.cell(m, args.workload)["workload"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run(manifest.config_path(w["config"], m, ROOT),
                        manifest.traffic_path(w["traffic"], ROOT), {}, {},
                        seed=seed, seconds=args.seconds, trace=False,
                        chips=w["chips"], root=ROOT, control=True)
        print(json.dumps(dict(
            workload=args.workload, seed=seed, correct=r["correct"],
            logit_gap=r["check"]["logit_gap"]["value"],
            control_correct=r["control"]["correct"],
            control_logit_gap=r["control"]["logit_gap"],
            limit=r["check"]["logit_gap"]["limit"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
