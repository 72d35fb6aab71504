"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``

Output contract: ``name,us_per_call,derived`` CSV lines. The kernels and
serving modules additionally dump structured results to
``BENCH_kernels.json`` (tokens/s + bits/weight, reference vs fused
dispatch path) and ``BENCH_serving.json`` (continuous-batching vs legacy
wave engine throughput) so the perf trajectory is tracked across PRs;
block-autotuner winners land in the shared JSON cache
(``ICQ_AUTOTUNE_CACHE``) and are reused on re-runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

# modules whose run() result is archived as BENCH_<name>.json
JSON_MODULES = {"kernels", "serving"}

MODULES = [
    ("outlier_range", "benchmarks.bench_outlier_range"),    # Fig 1/6
    ("uniformity", "benchmarks.bench_uniformity"),          # Tab 1/5
    ("index_overhead", "benchmarks.bench_index_overhead"),  # Fig 4/8, Lemma 1
    ("suppression", "benchmarks.bench_suppression"),        # Fig 5
    ("e2e_quality", "benchmarks.bench_e2e_quality"),        # Tab 2-4 proxy
    ("kernels", "benchmarks.bench_kernels"),                # deployment
    ("serving", "benchmarks.bench_serving"),                # continuous vs wave
    ("roofline", "benchmarks.bench_roofline"),              # §Roofline
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    failed = []
    for name, module in MODULES:
        if args.only and args.only != name:
            continue
        print(f"# === {name} ({module}) ===", flush=True)
        try:
            mod = __import__(module, fromlist=["run"])
            result = mod.run()
            if name in JSON_MODULES and isinstance(result, dict):
                path = f"BENCH_{name}.json"
                with open(path, "w") as f:
                    json.dump(result, f, indent=1)
                print(f"# wrote {path}", flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
