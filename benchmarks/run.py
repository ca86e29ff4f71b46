"""Benchmark suite — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract), writes markdown
reports under experiments/bench/, and optionally a machine-readable JSON
trajectory (``--json``) for CI smoke runs and BENCH_*.json comparisons.
All RNGs are seeded up front so runs are deterministic.

  PYTHONPATH=src python -m benchmarks.run [--only power,perf,...]
                                          [--json experiments/bench/run.json]
                                          [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import random
import sys
import traceback

from repro.launch.cache import enable_compile_cache

MODULES = [
    ("power", "benchmarks.power_prediction"),     # paper Fig. 2
    ("perf", "benchmarks.perf_prediction"),       # paper Fig. 3
    ("hxa", "benchmarks.hxa_accuracy"),           # HyPA table
    ("dse", "benchmarks.dse_speedup"),            # DSE motivation
    ("campaign", "benchmarks.dse_campaign"),      # streaming mega-space sweep
    ("serving", "benchmarks.serving"),            # selection query layer
    ("chaos", "benchmarks.chaos"),                # fault-recovery identity
    ("offload", "benchmarks.offload_analysis"),   # paper §IV
    ("roofline", "benchmarks.roofline_table"),    # §Roofline generator
    ("kernels", "benchmarks.kernel_bench"),       # Pallas kernels
]


def seed_everything(seed: int) -> None:
    """Deterministic CI smoke runs: seed the python and numpy global RNGs.
    (Hash randomization is fixed at interpreter startup; set PYTHONHASHSEED
    in the environment if a benchmark ever depends on hash order.)"""
    random.seed(seed)
    try:
        import numpy as np
        np.random.seed(seed)
    except ImportError:
        pass


def parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="comma-separated subset of: "
                    + ",".join(k for k, _ in MODULES))
    ap.add_argument("--json", dest="json_path", metavar="PATH",
                    help="also write results as JSON to PATH")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for python/numpy RNGs (default 0)")
    args = ap.parse_args()
    enable_compile_cache()
    seed_everything(args.seed)
    want = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed, skipped, results = [], {}, []
    for key, modname in MODULES:
        if want and key not in want:
            continue
        try:
            import importlib
            mod = importlib.import_module(modname)
            for row in mod.run():
                print(row)
                results.append({"suite": key, **parse_row(row)})
        except SystemExit as e:
            print(f"{key},0,SKIPPED:{e}")
            skipped[key] = str(e)
        except Exception:
            failed.append(key)
            traceback.print_exc()
    if args.json_path:
        payload = {
            "seed": args.seed,
            "python": platform.python_version(),
            "results": results,
            "skipped": skipped,
            "failed": failed,
        }
        d = os.path.dirname(args.json_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {args.json_path}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark failures: {failed}")


if __name__ == "__main__":
    main()
