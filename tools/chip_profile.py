"""Where a steady campaign's time goes on one TPU chip.

    python tools/chip_profile.py [--out experiments/profile]

Runs the campaign of ``chip_smoke.py`` (the default 125,440-candidate space
at chunk 32768 over five census cells at published widths) with the
compiled Pallas sweep and the fused jit sweep.  For each evaluator, after
two warm runs:

* three untraced runs give the steady wall time;
* one run with ``repro.telemetry`` on gives the host spans, summed over
  the tiles, and the time outside ``tile_eval``;
* one run under ``jax.profiler`` gives the device's busy time (the union of
  the events on the ``XLA Modules`` line of ``/device:TPU:0``), its idle
  share (1 - busy / traced wall) and the device ops that took longest.

Prints one line per reading and writes them all to ``<out>/profile.json``.
JAX's first device must be a TPU, as for ``chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)
os.environ.setdefault("REPRO_SAVE_HLO", "0")

import chip_smoke as smoke  # noqa: E402  (sets the host device count first)
import jax  # noqa: E402

from repro.dse_campaign import (Campaign, CampaignConfig,  # noqa: E402
                                default_campaign_space)
from repro.launch import dryrun  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.telemetry import Telemetry  # noqa: E402

WARM_RUNS = 2
TIMED_RUNS = 3
DEVICE_PLANE = "/device:TPU:0"
MODULE_LINE = "XLA Modules"


def busy_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def reduce_trace(trace_dir: str) -> dict:
    """Per line of the device plane: event count, busy ns and the ops that
    took longest, summed by name."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != DEVICE_PLANE:
            continue
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by_name = collections.Counter()
            for e in events:
                by_name[e.name] += e.duration_ns
            lines[line.name] = {
                "n": len(events),
                "busy_ns": busy_ns((e.start_ns, e.start_ns + e.duration_ns)
                                   for e in events),
                "top": by_name.most_common(8)}
    return lines


def profile(art_dir: str, cfg: CampaignConfig, trace_dir: str) -> dict:
    def run(telemetry=None) -> float:
        camp = Campaign.from_artifacts(art_dir, cfg, telemetry=telemetry)
        t0 = time.perf_counter()
        result = camp.run()
        wall = time.perf_counter() - t0
        smoke.check(result.complete, f"{cfg.evaluator} campaign incomplete")
        return wall

    for _ in range(WARM_RUNS):
        run()
    walls = [run() for _ in range(TIMED_RUNS)]

    tel = Telemetry()
    tel_wall = run(tel)
    spans = collections.defaultdict(float)
    for r in tel.tracer.records:
        spans[r.name] += r.dur
    spans["outside tile_eval"] = tel_wall - spans.get("tile_eval", 0.0)

    with jax.profiler.trace(trace_dir):
        traced_wall = run()
    lines = reduce_trace(trace_dir)
    smoke.check(MODULE_LINE in lines,
                f"no {MODULE_LINE!r} line on {DEVICE_PLANE} in the trace")
    busy = lines[MODULE_LINE]["busy_ns"]
    return {"untraced_wall_s": walls, "telemetry_wall_s": tel_wall,
            "host_spans_s": dict(spans), "traced_wall_s": traced_wall,
            "device_busy_ns": busy,
            "device_idle_share": 1.0 - busy * 1e-9 / traced_wall,
            "device_lines": lines}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(REPO, "experiments",
                                                  "profile"))
    args = ap.parse_args()
    enable_compile_cache()
    report = {"device": smoke.require_tpu()}
    with tempfile.TemporaryDirectory(prefix="chip_profile_") as tmp:
        for arch, shape in smoke.CELLS:
            if (arch, shape) != smoke.HELD_OUT:
                dryrun.run_cell(arch, shape, multi_pod=False, out_dir=tmp)
        base = CampaignConfig(
            space=default_campaign_space(chunk_size=smoke.CHUNK),
            constraint=smoke.CONSTRAINT)
        for ev in ("pallas", "jit"):
            r = profile(tmp, base.replace(evaluator=ev),
                        os.path.join(tmp, "trace_" + ev))
            report[ev] = r
            smoke.log(f"[profile] {ev}: untraced walls {r['untraced_wall_s']}"
                      f" s; traced wall {r['traced_wall_s']} s; device busy "
                      f"{r['device_busy_ns']} ns; device idle share "
                      f"{r['device_idle_share']}")
            smoke.log(f"[profile] {ev}: host spans (telemetry-on wall "
                      f"{r['telemetry_wall_s']} s) {r['host_spans_s']}")
            for name, line in r["device_lines"].items():
                smoke.log(f"[profile] {ev}: device line {name!r}: "
                          f"{line['n']} events, busy {line['busy_ns']} ns, "
                          f"top {line['top'][:4]}")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "profile.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    smoke.log(f"[profile] wrote {path}")


if __name__ == "__main__":
    main()
