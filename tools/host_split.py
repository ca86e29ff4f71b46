"""What tracing costs when it is on, and where the host's time goes
between launches, in the benchmark's own cells on one TPU chip.

    python tools/host_split.py [--cells campaign-default,select-novel]
                               [--seconds 51] [--seeds 2147483749,...]
                               [--out experiments/host_split]

For each cell, one driver state set up as ``bench/run.py`` sets it up
(``driver.setup(..., traced=True)``), then per seed two windows of
``--seconds`` on that one object, in alternating order: tracing on (the
cell driver's traced window, without the profiler: a tracing ``Telemetry``,
every span collected) and tracing off (no ``Telemetry`` for a campaign;
the query engine's telemetry swapped for a ``NullTelemetry``).  Every
number is read by the benchmark's own readers (``bench/metrics``): the
end-to-end metrics of both kinds of window, the program-span metrics of
the traced ones.  From the traced windows' span totals also: ``launch``'s
and ``merge``'s self time (their children run one after another on one
thread), the share of ``mini_campaign`` that its stages cover, and in
select-novel the span totals of the queries above each window's 95th
percentile against those between its 45th and 55th.

Writes ``<out>/host_split.json``.  JAX's first device must be a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Callable, Dict, List

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import allocator  # noqa: E402

allocator.fix()
os.environ.setdefault("REPRO_SAVE_HLO", "0")
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "tpu_logs"))

# sets the census's host placeholder device count before JAX starts
from repro.launch import dryrun  # noqa: E402,F401

from bench import harness, traffic  # noqa: E402

SEEDS = (2147483749, 2600000017, 3100000039)
CELLS = ("campaign-default", "select-novel")
# a span's children, which run one after another on its thread
CHILDREN = {"launch": ("pack", "dispatch", "device_wait", "fetch",
                       "host_compact"),
            "merge": ("materialize", "fold", "snapshot"),
            "mini_campaign": ("pad", "launch", "compact", "merge")}


def set_tracing(cell, on: bool) -> None:
    """Tracing on or off in a driver state built with ``traced=True``."""
    cell.traced = on
    engine = getattr(cell, "engine", None)
    if engine is not None:                  # a query engine: swap its sink
        from repro.telemetry import NullTelemetry
        if not hasattr(cell, "_traced_tel"):
            cell._traced_tel = cell.tel
        cell.tel = cell._traced_tel if on else None
        engine.telemetry = cell.tel if on else NullTelemetry()


def read_all(spec: Dict, name: str, obs: Dict, traced: bool) -> Dict:
    """The cell's metrics of one kind that ``obs`` holds, by their readers
    (``setup_s`` excepted: a window is not a run)."""
    out = {}
    for m in harness.cell_metrics(spec, name, traced):
        if m["name"] == "setup_s" or (traced
                                      and m["source"] != "program_span"):
            continue
        value = harness.load_module("metrics", m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = value
    return out


def spread(values: List[float]) -> float:
    """Distance between the quartiles as a share of the median."""
    if len(values) < 2:
        return float("nan")
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def self_and_cover(spans: Dict) -> Dict:
    """For each parent of ``CHILDREN`` in the window: the share of its
    total that its children cover, and the rest, its self share."""
    total = {n: t for n, (t, _) in spans.items()}
    out = {}
    for parent, kids in CHILDREN.items():
        if total.get(parent):
            covered = sum(total.get(k, 0.0) for k in kids)
            out[parent] = {"self_share": 1.0 - covered / total[parent],
                           "cover_share": covered / total[parent]}
    return out


def tail_against_median(obs: Dict) -> Dict:
    """Mean total ms per span name in the queries above the window's p95
    and in those between its p45 and p55 (a span belongs to the query
    whose client-side interval holds its start)."""
    queries = [(t0, t1) for n, t0, t1 in obs["host_spans"]
               if n.startswith("bench.query.")]
    per_query = [dict() for _ in queries]
    starts = np.asarray([t0 for t0, _ in queries])
    for n, t0, t1 in obs["host_spans"]:
        if n.startswith("bench."):
            continue
        i = int(np.searchsorted(starts, t0, side="right")) - 1
        if i >= 0 and t0 <= queries[i][1]:
            per_query[i][n] = per_query[i].get(n, 0.0) + (t1 - t0) * 1e3
    lat = np.asarray([t1 - t0 for t0, t1 in queries]) * 1e3
    p45, p55, p95 = np.percentile(lat, [45, 55, 95])
    out = {}
    for group, mask in (("above_p95", lat > p95),
                        ("p45_to_p55", (lat >= p45) & (lat <= p55))):
        idx = np.flatnonzero(mask)
        names = sorted(set().union(*(per_query[i] for i in idx)))
        out[group] = {"queries": int(idx.size),
                      "latency_ms": float(lat[idx].mean()),
                      "ms": {n: float(np.mean([per_query[i].get(n, 0.0)
                                               for i in idx]))
                             for n in names}}
    return out


def measure(name: str, spec: Dict, cell, seeds: List[int], seconds: float,
            log: Callable[[str], None] = print) -> Dict:
    """The alternating on/off windows of one set-up cell, and what their
    readings say."""
    runs, pooled = [], {}
    for i, seed in enumerate(seeds):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            set_tracing(cell, on)
            cell.reset(seed)
            obs = cell.window(time.perf_counter(), seconds)
            row = {"seed": seed, "tracing": on,
                   "failed": obs["failed"], "attempted": obs["attempted"],
                   **read_all(spec, name, obs, False)}
            if on:
                row["per_layer"] = read_all(spec, name, obs, True)
                row["self"] = self_and_cover(obs["spans"])
                if obs["kind"] == "selection":
                    row["tail"] = tail_against_median(obs)
                for n, (t, c) in obs["spans"].items():
                    p = pooled.setdefault(n, [0.0, 0])
                    p[0] += t
                    p[1] += c
            runs.append(row)
            log(json.dumps(row))
    set_tracing(cell, True)
    e2e = sorted({k for r in runs for k in r
                  if k not in ("seed", "tracing", "failed", "attempted",
                               "per_layer", "self", "tail")})
    cost = {}
    for k in e2e:
        off = [r[k] for r in runs if not r["tracing"] and k in r]
        on = [r[k] for r in runs if r["tracing"] and k in r]
        cost[k] = {"off": off, "on": on,
                   "median_change": statistics.median(on)
                   / statistics.median(off) - 1.0,
                   "off_spread": spread(off), "on_spread": spread(on)}
    return {"runs": runs, "tracing_cost": cost,
            "pooled_self": self_and_cover(pooled)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", default=",".join(map(str, SEEDS)))
    ap.add_argument("--out", default=os.path.join(REPO, "experiments",
                                                  "host_split"))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    dev = harness.device_info(1)
    cache = harness.enable_compile_cache_for_bench()
    print(f"device {dev}; compile cache {cache}", flush=True)
    spec = harness.load_spec()
    result = {"device": dev, "seconds": args.seconds, "seeds": seeds}
    for name in args.cells.split(","):
        entry = harness.find(spec["workloads"], name, "workload")
        conf = harness.find(spec["configs"], entry["config"], "config")
        with open(os.path.join(REPO, conf["file"])) as f:
            cfg = json.load(f)
        driver = harness.load_module("drivers", cfg["driver"])
        cell = driver.setup(cfg, traffic.load(entry["traffic"]), seeds[0],
                            traced=True, census=harness.census_from_cache)
        result[name] = measure(name, spec, cell, seeds, args.seconds,
                               log=lambda s: print(s, flush=True))
        cell.release()
        print(json.dumps({name: {k: result[name][k] for k in
                                 ("tracing_cost", "pooled_self")}}),
              flush=True)
    with open(os.path.join(args.out, "host_split.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": True, "out": args.out}))


if __name__ == "__main__":
    try:
        main()
    except harness.NoDevice as e:
        sys.exit(e.code)
