"""From a profiler trace to the device readings of a traced window.

The run records the window under ``jax.profiler.trace``.  The reduction
reads the ``.xplane.pb`` file with JAX alone:

* device busy time: the union of the events on the ``XLA Modules`` line of
  each ``/device:TPU:<n>`` plane, averaged over the chips;
* device ops: the ``XLA Ops`` line's events summed by name;
* idle gaps: the stretches between busy intervals, each put down to the
  innermost host span around its midpoint.  Host spans are the benchmark's
  own ``jax.profiler.TraceAnnotation`` spans from the trace and the
  program's telemetry spans, moved onto the trace's clock by the
  ``bench.anchor`` annotation, whose start is read on both clocks.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANCHOR = "bench.anchor"
ANNOTATION_PREFIX = "bench."
TOP = 10


def merged(intervals) -> List[Tuple[int, int]]:
    """The union of ``(start, end)`` intervals as disjoint sorted ones."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def options():
    """Profiler options of a traced run: device and host annotations, but
    no Python function tracer, which would record every call of the
    program's host code and slow it many times over."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return sorted(paths)[-1]


def read(path: str) -> Dict:
    """The events the reduction needs, from one ``.xplane.pb``: per device
    plane its module and op events, and every host event whose name starts
    with ``bench.``; each event as (name, start_ns, end_ns)."""
    import jax
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            d = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                d[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith(ANNOTATION_PREFIX)]
    return {"devices": devices, "host": host}


def attribute(gaps: Sequence[Tuple[int, int]],
              spans: Sequence[Tuple[str, int, int]]) -> Dict[str, float]:
    """Seconds of ``gaps`` by the innermost span around each gap's
    midpoint (the shortest span that holds it); ``host, no span`` where
    none does."""
    if not gaps:
        return {}
    mids = np.asarray([(s + e) / 2 for s, e in gaps], np.float64)
    order = np.argsort(mids)
    mids_sorted = mids[order]
    label = np.full(len(gaps), -1, np.int64)
    names = [s[0] for s in spans]
    # longest first, so a shorter span inside it overwrites the label
    for i in sorted(range(len(spans)), key=lambda i: spans[i][1] - spans[i][2]):
        _, s, e = spans[i]
        lo = np.searchsorted(mids_sorted, s, side="left")
        hi = np.searchsorted(mids_sorted, e, side="right")
        label[order[lo:hi]] = i
    out: Dict[str, float] = collections.defaultdict(float)
    for (s, e), lab in zip(gaps, label):
        out[names[lab] if lab >= 0 else "host, no span"] += (e - s) * 1e-9
    return dict(out)


def reduce(events: Dict, window: Optional[Tuple[int, int]] = None,
           extra_spans: Sequence[Tuple[str, int, int]] = ()) -> Dict:
    """Device readings of a trace: ``busy_s`` (mean over the device
    planes), ``device_ops`` and ``idle_gaps`` (top ``TOP`` as
    ``[name, seconds]``).  ``window`` (trace-clock ns) bounds the gaps;
    ``extra_spans`` are further host spans on the trace's clock."""
    if not events["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    busy, ops = [], collections.Counter()
    gaps_by: Dict[str, float] = collections.defaultdict(float)
    spans = [s for s in events["host"] if s[0] != ANCHOR] + list(extra_spans)
    n_dev = len(events["devices"])
    for d in events["devices"].values():
        m = merged((s, e) for _, s, e in d["modules"])
        busy.append(sum(e - s for s, e in m) * 1e-9)
        for name, s, e in d["ops"]:
            ops[name] += (e - s) * 1e-9
        lo, hi = window if window else (m[0][0] if m else 0,
                                        m[-1][1] if m else 0)
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        gaps = [(max(a, lo), min(b, hi)) for a, b in
                zip(edges[0::2], edges[1::2]) if min(b, hi) > max(a, lo)]
        for name, sec in attribute(gaps, spans).items():
            gaps_by[name] += sec / n_dev
    top_ops = [[n, s / n_dev] for n, s in ops.most_common(TOP)]
    top_gaps = sorted(([n, s] for n, s in gaps_by.items()),
                      key=lambda x: -x[1])[:TOP]
    return {"busy_s": float(np.mean(busy)), "device_ops": top_ops,
            "idle_gaps": top_gaps}


def anchor_ns(events: Dict) -> Optional[int]:
    """Trace-clock start of the ``bench.anchor`` annotation."""
    starts = [s for name, s, _ in events["host"] if name == ANCHOR]
    return min(starts) if starts else None
