"""One run of one benchmark cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell's configuration file (``configs``' ``file``), its traffic mix
(``bench/traffic/<traffic>.json``), the driver that the configuration names
(``bench/drivers/<driver>.py``) and one reader per metric
(``bench/metrics/<metric>.py``).  Adding a cell, a mix or a metric adds files
and entries; no file here changes.

A run: refuse anything but a TPU with enough chips; set up (census from the
checkout cache, program state, warm-up of every shape the traffic uses);
measure for ``--seconds``, with the profiler on when ``--trace 1``; read the
device's peak memory; free the program's state; compare what the window
served with the plain reference; print the numbers compared beside their
limits, and last the result line.  With ``--trace 0`` the metrics are the
cell's end-to-end ones, with ``--trace 1`` its per-layer ones.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

from bench import census as census_cache  # noqa: E402
from bench import check, trace, traffic  # noqa: E402


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_spec(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    mod_name = "bench_" + kind + "_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"bench: no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str) -> Dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"bench: device kind {kind!r} is not in "
                       f"bench/peaks.json; add its published peaks")
    return peaks[kind]


def cell_metrics(spec: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics this cell reports: end-to-end untraced, per-layer
    traced; a metric with a ``workloads`` key only in the cells it lists."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_tpu and dev["platform"] != "tpu":
        sys.stderr.write(f"bench: no TPU found (JAX device: {dev}); this "
                         "benchmark measures a TPU\n")
        raise NoDevice(2)
    if require_tpu and dev["count"] < chips:
        sys.stderr.write(f"bench: the cell asks for {chips} chips, JAX "
                         f"sees {dev['count']}\n")
        raise NoDevice(2)
    return dev


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs lowered (new jit cache entries) while active."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


def reduce_trace(trace_dir: str, t_anchor: float, obs: Dict,
                 device: Dict) -> Dict:
    """Device readings of the traced window into ``device`` and ``obs``;
    returns the breakdown.  The program's spans (host clock) move onto the
    trace's clock through the anchor annotation read at ``t_anchor``."""
    events = trace.read(trace.xplane_path(trace_dir))
    a = trace.anchor_ns(events)
    if a is None:
        raise RuntimeError("the trace holds no bench.anchor annotation")

    def to_ns(t: float) -> int:
        return int(a + (t - t_anchor) * 1e9)

    spans = [(n, to_ns(s), to_ns(e)) for n, s, e in obs["host_spans"]]
    window_ns = (to_ns(obs["window_start"]), to_ns(obs["window_end"]))
    red = trace.reduce(events, window_ns, spans)
    device["busy_s"] = red["busy_s"]
    device["window_s"] = obs["window_end"] - obs["window_start"]
    obs["busy_s"], obs["window_s"] = device["busy_s"], device["window_s"]
    return {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}


def enable_compile_cache_for_bench() -> str:
    """The program's persistent compile cache (its fixed ``.jax_cache/``
    in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise),
    holding every program however quick to compile, so that a later run
    of the cell finds all of them."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def run_cell(cell: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, spec: Optional[Dict] = None,
             require_tpu: bool = True,
             census: Optional[Callable] = None) -> Dict:
    """Run one cell once; returns the result line's object.  ``census``
    replaces the census cache's loader (tests); ``require_tpu=False``
    lets a test drive the rest of a run on the CPU."""
    import jax

    spec = spec or load_spec()
    entry = find(spec["workloads"], cell, "workload")
    conf = find(spec["configs"], entry["config"], "config")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load(entry["traffic"])
    dev = device_info(int(entry["chips"]), require_tpu)
    cache_dir = enable_compile_cache_for_bench()
    log(f"[bench] {cell}: device {dev}; compile cache {cache_dir}")

    driver = load_module("drivers", cfg["driver"])
    counter = CompileCounter()
    t0 = time.perf_counter()
    state = driver.setup(cfg, mix, seed, traced=traced,
                         census=census or census_from_cache)
    log(f"[bench] setup parts (s): {json.dumps(state.setup_parts)}; "
        f"before the driver {t0 - t_start:.3f}")

    counter.active = True
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        with (jax.profiler.trace(trace_dir, profiler_options=trace.options())
              if traced
              else contextlib.nullcontext()):
            if traced:
                with jax.profiler.TraceAnnotation(trace.ANCHOR):
                    t_anchor = time.perf_counter()
            window_start = time.perf_counter()
            setup_s = window_start - t_start
            obs = state.window(window_start, seconds)
        counter.active = False
        obs["setup_s"] = setup_s
        log(f"[bench] programs lowered inside the window: {counter.count}")
        device = dict(dev)
        device["memory_peak_bytes"] = memory_peak_bytes()
        breakdown = None
        if traced:
            breakdown = reduce_trace(trace_dir, t_anchor, obs, device)
            obs["peaks"] = load_peaks(dev["kind"]) if require_tpu else None

    state.release()
    t_check = time.perf_counter()
    numbers = state.check("program")
    log(f"[bench] reference check of {state.n_checked} answers "
        f"{time.perf_counter() - t_check:.3f} s")

    metrics = {}
    for m in cell_metrics(spec, cell, traced):
        value = load_module("metrics", m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limits = cfg["limits"]
    correct = check.verdict(numbers, limits) and obs["failed"] == 0
    # JSON has no infinity: a number that could not be read is null
    read = {n: numbers.get(n, float("inf")) for n in limits}
    checks = {n: {"value": v if math.isfinite(v) else None,
                  "limit": limits[n]} for n, v in read.items()}
    result = {"correct": correct, "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for n, v in read.items():
        log(f"check {n} {v!r} limit {limits[n]!r}")
    return result


def census_from_cache(cells) -> List[Dict]:
    """The census of ``cells``, lowered on a cache miss."""
    got = census_cache.ensure(cells)
    log(f"[bench] census: {got['hit']} from the cache, {got['lowered']} "
        f"lowered ({got['dir']})")
    return census_cache.load(cells)


def main(argv=None, t_start: Optional[float] = None) -> None:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
