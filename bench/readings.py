"""Readings for setting the limits of ``correct``, and checks that the
benchmark's own runs do not make.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>
    python3 bench/readings.py --workload <cell> --census-check
    python3 bench/readings.py --workload <cell> --record-trace <dir>

One process sets the cell up once.  For each seed it runs a window of
``--seconds`` at the cell's own load and prints one JSON line with the
comparison numbers of the program's answers and of the control: the
reference, in bfloat16, put in the program's place over the same questions.

``--census-check`` lowers the cell's census afresh into a temporary
directory and compares it with the checkout cache's.  ``--record-trace``
traces a short window (``--seconds``, default 0.5) and writes the
``.xplane.pb`` with what the run's reduction made of it, for the tests of
the reduction.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import allocator  # noqa: E402

allocator.fix()
os.environ.setdefault("REPRO_SAVE_HLO", "0")
# the TPU runtime logs under TMPDIR, not a fixed path shared between runs
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "tpu_logs"))

from repro.launch import dryrun  # noqa: E402,F401  (host device count first)

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

from bench import census as census_mod  # noqa: E402
from bench import harness, trace, traffic  # noqa: E402


def census_check(cells) -> bool:
    cached = census_mod.load(cells)
    with tempfile.TemporaryDirectory(prefix="bench_census_") as d:
        fresh = []
        for arch, shape in cells:
            census_mod._lower(arch, shape, d)
            with open(os.path.join(d, census_mod.artifact_name(arch, shape))) as f:
                fresh.append(census_mod.census_record(arch, shape, json.load(f)))
    same = fresh == cached
    print(json.dumps({"census_check": same, "cells": len(cells),
                      "fresh": fresh, "cached": cached}), flush=True)
    return same


def record_trace(cell, seconds: float, out: str) -> None:
    import jax
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        with jax.profiler.trace(d, profiler_options=trace.options()):
            with jax.profiler.TraceAnnotation(trace.ANCHOR):
                t_anchor = time.perf_counter()
            t0 = time.perf_counter()
            obs = cell.window(t0, seconds)
        os.makedirs(out, exist_ok=True)
        shutil.copy(trace.xplane_path(d), os.path.join(out, "small.xplane.pb"))
        device = {}
        breakdown = harness.reduce_trace(d, t_anchor, obs, device)
    with open(os.path.join(out, "small.expect.json"), "w") as f:
        json.dump({"t_anchor": t_anchor, "window_start": obs["window_start"],
                   "window_end": obs["window_end"],
                   "host_spans": obs["host_spans"], "device": device,
                   "breakdown": breakdown}, f, indent=1)
    print(json.dumps({"recorded": out, "device": device,
                      "breakdown": breakdown}), flush=True)


def _summary(latencies):
    if not latencies:
        return None
    q = np.percentile(latencies, [50, 95])
    return {"n": len(latencies), "p50": float(q[0]), "p95": float(q[1]),
            "max": float(max(latencies))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--census-check", action="store_true")
    ap.add_argument("--record-trace")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the controls on the first N seeds only")
    args = ap.parse_args()
    spec = harness.load_spec()
    entry = harness.find(spec["workloads"], args.workload, "workload")
    conf = harness.find(spec["configs"], entry["config"], "config")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    cells = [tuple(c) for c in cfg["census"]["cells"]]
    dev = harness.device_info(int(entry["chips"]))
    harness.enable_compile_cache_for_bench()
    if args.census_check:
        harness.census_from_cache(cells)
        if not census_check(cells):
            sys.exit(1)
        return
    seeds = [int(s) for s in args.seeds.split(",") if s] or [0]
    driver = harness.load_module("drivers", cfg["driver"])
    cell = driver.setup(cfg, traffic.load(entry["traffic"]), seeds[0],
                        traced=bool(args.record_trace),
                        census=harness.census_from_cache)
    print(json.dumps({"device": dev, "setup_parts": cell.setup_parts,
                      "setup_s": time.perf_counter() - T_START}), flush=True)
    if args.record_trace:
        record_trace(cell, args.seconds, args.record_trace)
        return
    for i, seed in enumerate(seeds):
        cell.reset(seed)
        obs = cell.window(time.perf_counter(), args.seconds)
        t = time.perf_counter()
        program = cell.check("program")
        check_s = time.perf_counter() - t
        n = cell.n_checked
        line = {"seed": seed, "attempted": obs["attempted"],
                "failed": obs["failed"], "checked": n,
                "latency_s": _summary(obs.get("latencies_s", [])),
                "program": program, "check_s": check_s}
        if args.control_seeds is None or i < args.control_seeds:
            t = time.perf_counter()
            line["control"] = cell.check("control")
            line["control_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    try:
        main()
    except harness.NoDevice as e:
        sys.exit(e.code)
