"""Summarize and validate a campaign Chrome trace (``trace_*.json``).

Reads the ``trace_event`` JSON written by ``Telemetry.export_trace`` /
``SpanTracer.export`` (complete ``"ph": "X"`` events whose ``args`` carry
the span id, parent id and nesting depth) and prints:

* **top spans** — per-name count / total / self / mean / max duration,
  sorted by total time; a span's self time is its duration minus the part
  of it that its child spans cover;
* **per-stage share** — the span tree under each request span
  (``tile_eval`` for a campaign tile, ``mini_campaign`` for a query's
  exact path), each stage's total and self time as a share of the
  request's total, so "where does a tile's wall go?" is one glance and the
  self column adds up to 100% without counting a nested stage twice;
* **worker utilization** — per-worker busy time from ``tile_eval`` spans
  that carry a ``worker`` attr (fabric traces), as a share of the trace's
  observed wall.

``--check`` turns the reader into a CI gate: it exits non-zero unless every
required span name (default ``tile_eval``, ``checkpoint_write``, ``lease``
— the instrumented smoke campaign must produce all three) is present, and
every event's parent/depth bookkeeping is sane — a named parent id exists
in the trace, the child starts no earlier than its parent, ends no later
(small float slack), and sits at ``parent.depth + 1`` on the same thread.

    python tools/trace_report.py artifacts/bench/trace_dse_campaign.json
    python tools/trace_report.py trace.json --check
    python tools/trace_report.py trace.json --check --require tile_eval
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional

# containment slack (µs): a child's end may exceed its parent's by float
# rounding of the two (t - epoch) * 1e6 conversions, never by real time
SLACK_US = 0.5

DEFAULT_REQUIRED = ("tile_eval", "checkpoint_write", "lease")
# roots of the per-stage table: the spans of one request
REQUEST_SPANS = ("tile_eval", "mini_campaign")


def load_events(path: str) -> List[Dict]:
    """The trace's complete ("X") events; raises on a malformed file."""
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{path}: no traceEvents list — not a Chrome trace")
    return [e for e in events if e.get("ph") == "X"]


def _sid(e: Dict):
    return e.get("args", {}).get("sid")


def self_times(events: List[Dict]) -> List[float]:
    """Each event's self time (µs): its ``dur`` minus the union of its
    children's intervals, clipped to its own."""
    children: Dict[object, List[Dict]] = defaultdict(list)
    for e in events:
        parent = e.get("args", {}).get("parent", -1)
        if parent != -1:
            children[parent].append(e)
    out = []
    for e in events:
        lo, hi = e["ts"], e["ts"] + e["dur"]
        covered, end = 0.0, lo
        for c in sorted(children.get(_sid(e), ()), key=lambda c: c["ts"]):
            s, t = max(c["ts"], end), min(c["ts"] + c["dur"], hi)
            if t > s:
                covered += t - s
                end = t
        out.append(max(e["dur"] - covered, 0.0))
    return out


def summarize(events: List[Dict]) -> Dict[str, Dict]:
    """Per-name aggregates over the events' ``dur`` and self time (µs)."""
    agg: Dict[str, Dict] = {}
    for e, self_us in zip(events, self_times(events)):
        row = agg.setdefault(e["name"], {"count": 0, "total_us": 0.0,
                                         "self_us": 0.0, "max_us": 0.0})
        row["count"] += 1
        row["total_us"] += e["dur"]
        row["self_us"] += self_us
        row["max_us"] = max(row["max_us"], e["dur"])
    for row in agg.values():
        row["mean_us"] = row["total_us"] / row["count"]
    return agg


def stage_tree(events: List[Dict]) -> Dict[tuple, Dict]:
    """Total and self time (µs) and count per span path: the names from
    the root span down (a span whose parent is not in the trace roots its
    own path)."""
    by_sid = {_sid(e): e for e in events if _sid(e) is not None}
    paths: Dict[object, tuple] = {}

    def path(e: Dict) -> tuple:
        sid = _sid(e)
        if sid in paths:
            return paths[sid]
        parent = by_sid.get(e.get("args", {}).get("parent", -1))
        p = (path(parent) if parent is not None else ()) + (e["name"],)
        if sid is not None:
            paths[sid] = p
        return p

    tree: Dict[tuple, Dict] = {}
    for e, self_us in zip(events, self_times(events)):
        row = tree.setdefault(path(e), {"count": 0, "total_us": 0.0,
                                        "self_us": 0.0})
        row["count"] += 1
        row["total_us"] += e["dur"]
        row["self_us"] += self_us
    return tree


def print_report(events: List[Dict], top: int = 15) -> None:
    if not events:
        print("trace holds no complete spans")
        return
    agg = summarize(events)

    print(f"{len(events)} spans, {len(agg)} distinct names\n")
    print(f"{'span':<20} {'count':>7} {'total_ms':>10} {'self_ms':>10} "
          f"{'mean_us':>10} {'max_us':>10}")
    for name, row in sorted(agg.items(), key=lambda kv: -kv[1]["total_us"])[:top]:
        print(f"{name:<20} {row['count']:>7} {row['total_us'] / 1e3:>10.3f} "
              f"{row['self_us'] / 1e3:>10.3f} "
              f"{row['mean_us']:>10.1f} {row['max_us']:>10.1f}")

    tree = stage_tree(events)
    for root in REQUEST_SPANS:
        total = tree.get((root,), {}).get("total_us", 0.0)
        if total <= 0:
            continue
        print(f"\nper-stage share of {root} ({total / 1e3:.3f} ms total, "
              f"{tree[(root,)]['count']} spans):")
        print(f"  {'stage':<24} {'total':>7} {'self':>7}")
        for p in sorted((p for p in tree if p[0] == root),
                        key=lambda p: [(-tree[p[:i + 1]]["total_us"], p[i])
                                       for i in range(len(p))]):
            row = tree[p]
            label = "  " * (len(p) - 1) + p[-1]
            print(f"  {label:<24} {row['total_us'] / total:>7.1%} "
                  f"{row['self_us'] / total:>7.1%}")

    by_worker: Dict[object, float] = defaultdict(float)
    for e in events:
        if e["name"] == "tile_eval" and "worker" in e.get("args", {}):
            by_worker[e["args"]["worker"]] += e["dur"]
    if by_worker:
        t0 = min(e["ts"] for e in events)
        t1 = max(e["ts"] + e["dur"] for e in events)
        wall = max(t1 - t0, 1e-9)
        print("\nworker utilization (tile_eval busy / trace wall):")
        for w in sorted(by_worker, key=str):
            print(f"  worker {w!s:<6} {by_worker[w] / 1e3:>10.3f} ms "
                  f"{by_worker[w] / wall:>7.1%}")


def check(events: List[Dict], required) -> List[str]:
    """The CI gate: missing required spans + nesting violations."""
    errors: List[str] = []
    names = {e["name"] for e in events}
    for name in required:
        if name not in names:
            errors.append(f"required span {name!r} absent from trace")

    by_sid: Dict[int, Dict] = {}
    for e in events:
        args = e.get("args", {})
        if "sid" not in args or "parent" not in args or "depth" not in args:
            errors.append(f"span {e['name']!r} lacks sid/parent/depth args")
            continue
        by_sid[args["sid"]] = e
    for e in by_sid.values():
        args = e["args"]
        parent_sid = args["parent"]
        if parent_sid == -1:
            if args["depth"] != 0:
                errors.append(f"root span {e['name']!r} (sid {args['sid']}) "
                              f"has depth {args['depth']}, expected 0")
            continue
        parent = by_sid.get(parent_sid)
        if parent is None:
            # the ring buffer may have evicted an old parent; only flag a
            # dangling parent when the buffer never wrapped (all sids seen)
            continue
        p_args = parent["args"]
        if args["depth"] != p_args["depth"] + 1:
            errors.append(
                f"span {e['name']!r} (sid {args['sid']}) at depth "
                f"{args['depth']} under parent {parent['name']!r} at depth "
                f"{p_args['depth']}")
        if e.get("tid") != parent.get("tid"):
            errors.append(
                f"span {e['name']!r} (sid {args['sid']}) nests under "
                f"{parent['name']!r} on a different thread")
        if e["ts"] < parent["ts"] - SLACK_US:
            errors.append(
                f"span {e['name']!r} (sid {args['sid']}) starts before its "
                f"parent {parent['name']!r}")
        if e["ts"] + e["dur"] > parent["ts"] + parent["dur"] + SLACK_US:
            errors.append(
                f"span {e['name']!r} (sid {args['sid']}) ends after its "
                f"parent {parent['name']!r}")
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace JSON written by "
                                  "Telemetry.export_trace")
    ap.add_argument("--check", action="store_true",
                    help="gate: fail on missing required spans or bad "
                         "nesting")
    ap.add_argument("--require", default=",".join(DEFAULT_REQUIRED),
                    help="comma-separated span names --check requires "
                         f"(default: {','.join(DEFAULT_REQUIRED)})")
    ap.add_argument("--top", type=int, default=15,
                    help="rows in the top-spans table")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    print_report(events, top=args.top)
    if args.check:
        required = [n for n in args.require.split(",") if n]
        errors = check(events, required)
        if errors:
            print(f"\nFAIL: {len(errors)} trace violation(s):",
                  file=sys.stderr)
            for err in errors:
                print(f"  - {err}", file=sys.stderr)
            return 1
        print(f"\nOK: required spans {required} present, nesting sane "
              f"({len(events)} spans)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
