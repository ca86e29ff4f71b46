"""Streaming DSE campaign benchmark — the mega-space sweep as a CI artifact.

Runs the default campaign (ALL cached dry-run workloads x the >=100k-point
``default_campaign_space``) with the float64 engine, verifies the streamed
frontier of one workload is IDENTICAL to one-shot ``dse.pareto_search`` on
the same concatenated space, and persists ``BENCH_dse_campaign.json``
(frontier members + per-tile trajectory + candidates/sec throughput) so CI
can diff frontiers across PRs.

It then races the evaluator tiers on the same space — the PR-3 ``"jit"``
per-workload baseline (``pipeline=False``), the fused multi-workload jit
sweep, and the fused Pallas DSE-sweep kernel (interpret mode on CPU) — and
persists ``BENCH_evaluator_speedup.json`` with per-evaluator
``candidates_per_sec`` (best of ``EVAL_REPEATS`` runs, the suite's standard
best-of timing), the pallas-vs-numpy frontier-identity verdict, and both
fused frontiers for the CI evaluator diff.  Gates (after artifacts are
written): pallas must reproduce the numpy frontier's exact candidate set
with hypervolume within 1e-6 relative, and the fused pallas pipeline must
beat the jit baseline's throughput by >= 3x.

Finally the distributed matrix: the same default campaign through the
multiprocess fabric at 1 and 2 workers on the jit evaluator — including a
2-worker run with an injected worker crash mid-tile plus a duplicated
payload delivery — persisted as ``BENCH_distributed_campaign.json``.
Gates: every fabric frontier must be BITWISE-identical to the
single-process jit frontier, and 2 workers must reach >= 1.8x the
1-worker candidates/sec on the busy-CPU clock (total candidate evaluations
divided by the slowest worker's summed per-tile ``time.process_time`` —
CPU actually burned on tiles, excluding compile warm-up, so the scaling
row measures work-splitting rather than host core count; the wall-clock
window from all-workers-ready to last fold is reported unguarded).

The adaptive matrix (``BENCH_adaptive_campaign.json``) runs the
surrogate-guided ``AdaptiveCampaign`` with default knobs against the exact
sweep: per-cell frontier hypervolume ratios under the exact campaign's
pinned reference points, the fraction of the space evaluated exactly, and
the budget=100% degenerate-identity check.  Gates: worst-cell hv ratio
>= 0.99 while evaluating <= 10% of the space, and budget=100% bitwise
equal to the exact jit sweep.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import (ART_DIR, OUT_DIR, csv_row, ensure_artifacts,
                               write_report)
from repro.core import costmodel, dse
from repro.dse_campaign import (AdaptiveCampaign, AdaptiveConfig, Campaign,
                                CampaignConfig, FaultInjection, LocalFabric,
                                MultiprocessFabric, canonical_frontier,
                                candidate_to_dict, default_campaign_space,
                                frontiers_identical, hypervolume_2d, store)
from repro.hw import get_chip, mesh_factorizations
from repro.telemetry import Telemetry

EVAL_REPEATS = 3          # best-of runs per evaluator (benchmarks.common.timed
                          # convention: min over repeats rides out CI noise)
FUSED_CHUNK = 32768       # fused evaluators amortize per-launch overhead over
                          # bigger staging tiles; the frontier is tile-size
                          # invariant (tests/test_dse_campaign.py), so this is
                          # an execution detail, not a space change
EVALUATOR_BENCH_NAME = "BENCH_evaluator_speedup.json"
DISTRIBUTED_BENCH_NAME = "BENCH_distributed_campaign.json"
ADAPTIVE_BENCH_NAME = "BENCH_adaptive_campaign.json"
ADAPTIVE_CHUNK = 512      # adaptive tiles are acquisition quanta: small
                          # enough that a 10% budget buys many rounds, big
                          # enough that fused launches stay amortized
ADAPTIVE_HV_GATE = 0.99   # adaptive frontier hv / exact-sweep hv, worst cell
ADAPTIVE_BUDGET_GATE = 0.10  # fraction of the space evaluated exactly
TRACE_ARTIFACT_NAME = "trace_dse_campaign.json"
SCALING_GATE = 1.8        # 2-worker busy-CPU throughput vs 1 worker
TELEMETRY_OVERHEAD_GATE = 0.02  # attributed instrumentation cost / sweep wall


def mesh_tie_report(wl: dse.Workload, chip_name: str = "tpu-v5e",
                    n_chips: int = 64) -> dict:
    """Before/after view of the factorization axis on one same-count family:
    the mesh-agnostic model ties every factorization of ``n_chips``; the
    topology model separates them.  Returns the counts the report prints."""
    chip = get_chip(chip_name)
    meshes = mesh_factorizations(n_chips, 3)
    legacy, topo = [], []
    for mesh in meshes:
        cand = dse.Candidate(chip_name, n_chips, mesh, chip.max_freq_mhz)
        ana = dse._scale_analysis(wl.base_analysis, wl.base_chips, cand)
        legacy.append(costmodel.simulate(
            ana, chip, n_chips, chip.max_freq_mhz).t_collective)
        topo.append(costmodel.simulate(
            ana, chip, n_chips, chip.max_freq_mhz, mesh=mesh).t_collective)
    ties_before = len(meshes) - len(set(legacy))
    ties_after = len(meshes) - len(set(topo))
    return {"chip": chip_name, "n_chips": n_chips, "meshes": meshes,
            "t_coll_topology": topo, "ties_before": ties_before,
            "ties_after": ties_after,
            "ties_broken": ties_before - ties_after}


def frontier_points(result) -> dict:
    """Campaign frontiers in the BENCH points shape (for the CI diff)."""
    out = {}
    for (arch, shape), front in sorted(result.frontiers.items()):
        out[f"{arch}|{shape}"] = {
            "feasible_count": front.feasible_count,
            "points": [{**candidate_to_dict(c), "energy_j": float(e),
                        "latency_s": float(l), "index": int(i)}
                       for c, e, l, i in zip(front.candidates, front.energy_j,
                                             front.latency_s, front.indices)],
        }
    return out


def final_hv(result) -> dict:
    return {f"{a}|{s}": snaps[-1].hypervolume
            for (a, s), snaps in sorted(result.trajectories.items()) if snaps}


def hv_with_ref(front, ref_e, ref_l) -> float:
    """Frontier hypervolume under an EXPLICIT reference point (shared
    ``hypervolume_2d``) — the trajectory proxy pins its ref from the first
    feasible tile, which depends on chunk size, so cross-chunk evaluator
    comparisons must re-anchor both frontiers to one ref."""
    return hypervolume_2d(front.energy_j, front.latency_s, ref_e, ref_l)


def same_candidate_set(a, b) -> bool:
    """Frontiers hold the same candidates at the same space indices (values
    may differ by evaluator precision)."""
    ca, _, _, ia = canonical_frontier(a)
    cb, _, _, ib = canonical_frontier(b)
    return ca == cb and np.array_equal(ia, ib)


def evaluator_matrix(workloads, cons, numpy_result, refs) -> tuple:
    """Race the evaluator tiers; returns (payload, report_lines, rows).

    ``refs`` maps workload key -> the numpy campaign's hypervolume reference
    point, re-used for every evaluator so the identity comparison is
    ref-consistent across chunk sizes."""
    configs = [
        # name, evaluator, pipeline, chunk, precision note
        ("jit-baseline", "jit", False, 4096, "float32, per-workload loop"),
        ("jit", "jit", True, FUSED_CHUNK, "float32, fused sweep"),
        ("pallas", "pallas", True, FUSED_CHUNK, "float64 (interpret), "
                                                "fused Pallas kernel"),
    ]
    evaluators = {"numpy": {
        "candidates_per_sec": numpy_result.candidates_per_sec,
        "sweep_wall_s": numpy_result.sweep_wall_s,
        "chunk_size": 4096, "pipeline": True,
        "precision": "float64, per-workload loop",
    }}
    results = {"numpy": numpy_result}
    for name, ev, pipe, chunk, note in configs:
        spec = default_campaign_space(chunk_size=chunk)
        best = None
        for _ in range(EVAL_REPEATS):
            r = Campaign(workloads, spec, constraint=cons, evaluator=ev,
                         pipeline=pipe).run()
            assert r.complete
            if best is None or r.candidates_per_sec > best.candidates_per_sec:
                best = r
        results[name] = best
        evaluators[name] = {
            "candidates_per_sec": best.candidates_per_sec,
            "sweep_wall_s": best.sweep_wall_s,
            "chunk_size": chunk, "pipeline": pipe, "precision": note,
        }

    keys = sorted(numpy_result.frontiers)
    identical = all(same_candidate_set(numpy_result.frontiers[k],
                                       results["pallas"].frontiers[k])
                    for k in keys)
    hv_rel = 0.0
    for k in keys:
        ref_e, ref_l = refs[k]
        a = hv_with_ref(numpy_result.frontiers[k], ref_e, ref_l)
        b = hv_with_ref(results["pallas"].frontiers[k], ref_e, ref_l)
        if a:
            hv_rel = max(hv_rel, abs(b - a) / abs(a))
    speedup = (evaluators["pallas"]["candidates_per_sec"]
               / evaluators["jit-baseline"]["candidates_per_sec"])

    payload = {
        "bench": "dse_evaluator_speedup",
        "python": platform.python_version(),
        "sim_model_version": costmodel.SIM_MODEL_VERSION,
        "space": default_campaign_space().to_dict(),
        "fused_chunk_size": FUSED_CHUNK,
        "repeats": EVAL_REPEATS,
        "workloads": [f"{a}|{s}" for a, s in keys],
        "evaluators": evaluators,
        "speedup_pallas_vs_jit_baseline": speedup,
        "pallas_vs_numpy": {"identical_candidate_set": identical,
                            "max_hv_rel_diff": hv_rel},
        "hv": {name: final_hv(r) for name, r in results.items()},
        "frontiers": {"jit": frontier_points(results["jit"]),
                      "pallas": frontier_points(results["pallas"])},
    }
    lines = ["", "## evaluator matrix (best of "
             f"{EVAL_REPEATS} runs, {len(keys)} workloads)", ""]
    for name, row in evaluators.items():
        lines.append(
            f"  {name:>12}: {row['candidates_per_sec']:>12,.0f} cands/sec "
            f"(sweep {row['sweep_wall_s']:6.2f}s, chunk {row['chunk_size']}, "
            f"{row['precision']})")
    lines += [
        f"  pallas vs numpy: identical candidate set = {identical}, "
        f"max hv rel diff = {hv_rel:.2e}",
        f"  fused pallas speedup vs PR-3 jit baseline: {speedup:.2f}x",
    ]
    rows = [csv_row(f"dse_evaluator_{name}",
                    1e6 / max(row["candidates_per_sec"], 1e-9),
                    f"cands_per_sec={row['candidates_per_sec']:.0f};"
                    f"chunk={row['chunk_size']}")
            for name, row in evaluators.items()]
    rows.append(csv_row("dse_evaluator_speedup", 0.0,
                        f"pallas_vs_jit_baseline={speedup:.2f}x;"
                        f"identical_set={identical};hv_rel={hv_rel:.2e}"))
    return payload, lines, rows


def distributed_matrix(workloads, cons) -> tuple:
    """The fabric scaling + identity matrix on the default campaign space.

    Runs the default jit campaign single-process (the bitwise reference),
    then through ``MultiprocessFabric`` at 1 worker, 2 workers, and
    2 workers with the full injected-failure script (worker crash mid-tile
    + duplicated payload delivery).  Throughput is busy-CPU based: total
    candidate evaluations / the slowest worker's summed per-tile
    ``process_time`` — a machine-independent work-splitting metric that
    holds on single-core CI runners where two workers cannot beat one on
    wall clock.  Returns (payload, report_lines, csv_rows).
    """
    spec = default_campaign_space()
    single = Campaign(workloads, spec, constraint=cons, evaluator="jit").run()
    assert single.complete
    total_cands = single.candidates_evaluated

    configs = [
        ("1-worker", 1, None),
        ("2-worker", 2, None),
        ("2-worker-faults", 2, FaultInjection(kill_worker=1,
                                              kill_after_tiles=1,
                                              duplicate=True)),
    ]
    runs = {}
    for name, n_workers, fault in configs:
        campaign = Campaign(workloads, spec, constraint=cons, evaluator="jit")
        fabric = MultiprocessFabric(campaign, n_workers=n_workers,
                                    fault=fault, lease_timeout_s=600.0)
        result = fabric.run()
        assert result.complete, (name, result.tiles_done, result.n_tiles)
        stats = fabric.stats
        identical = all(
            frontiers_identical(single.frontiers[k], result.frontiers[k])
            for k in single.frontiers)
        runs[name] = {
            "n_workers": n_workers,
            "identical_to_single_process": identical,
            "cands_per_busy_sec": total_cands
            / max(stats["max_worker_busy_s"], 1e-9),
            "worker_busy_s": {str(w): b
                              for w, b in sorted(stats["worker_busy_s"].items())},
            "max_worker_busy_s": stats["max_worker_busy_s"],
            "total_busy_s": stats["total_busy_s"],
            "window_s": stats["window_s"],
            "deliveries": stats["deliveries"],
            "duplicates": stats["duplicates"],
            "reissued_tiles": stats["reissued_tiles"],
            "lost_workers": stats["lost_workers"],
        }
    faults = runs["2-worker-faults"]
    assert faults["lost_workers"], "injected worker crash never fired"
    assert faults["duplicates"] >= 1, "duplicate delivery never folded"
    scaling = (runs["2-worker"]["cands_per_busy_sec"]
               / runs["1-worker"]["cands_per_busy_sec"])
    all_identical = all(r["identical_to_single_process"]
                       for r in runs.values())

    payload = {
        "bench": "dse_distributed_campaign",
        "python": platform.python_version(),
        "sim_model_version": costmodel.SIM_MODEL_VERSION,
        "space": spec.to_dict(),
        "evaluator": "jit",
        "workloads": sorted(f"{a}|{s}" for a, s in single.frontiers),
        "candidates_evaluated": total_cands,
        "single_process": {
            "cands_per_busy_sec": total_cands / max(single.sweep_wall_s, 1e-9),
            "sweep_wall_s": single.sweep_wall_s,
        },
        "runs": runs,
        "scaling_2w_vs_1w": scaling,
        "scaling_gate": SCALING_GATE,
        "all_identical_to_single_process": all_identical,
        "hv": final_hv(single),
    }
    lines = ["", f"## distributed fabric ({len(single.frontiers)} workloads, "
             f"{spec.n_tiles()} tiles, jit evaluator)", ""]
    for name, row in runs.items():
        busy = ", ".join(f"w{w}={b:.2f}s"
                         for w, b in row["worker_busy_s"].items())
        lines.append(
            f"  {name:>16}: {row['cands_per_busy_sec']:>12,.0f} cands/busy-sec "
            f"(busy {busy}; window {row['window_s']:5.2f}s; "
            f"{row['duplicates']} dup, {row['reissued_tiles']} reissued, "
            f"lost {row['lost_workers']}) "
            f"identical={row['identical_to_single_process']}")
    lines += [
        f"  2-worker scaling vs 1-worker (busy-CPU): {scaling:.2f}x "
        f"(gate >= {SCALING_GATE}x)",
        f"  all fabric frontiers bitwise == single process: {all_identical}",
    ]
    rows = [csv_row(f"dse_distributed_{name}",
                    1e6 / max(row["cands_per_busy_sec"], 1e-9),
                    f"cands_per_busy_sec={row['cands_per_busy_sec']:.0f};"
                    f"workers={row['n_workers']};"
                    f"identical={row['identical_to_single_process']}")
            for name, row in runs.items()]
    rows.append(csv_row("dse_distributed_scaling", 0.0,
                        f"scaling_2w_vs_1w={scaling:.2f}x;"
                        f"identical={all_identical};"
                        f"faults_lost={faults['lost_workers']};"
                        f"faults_dup={faults['duplicates']}"))
    return payload, lines, rows


def adaptive_matrix(workloads, cons, exact_result, refs) -> tuple:
    """Surrogate-guided campaign vs the exact sweep: the >=99%-hypervolume-
    at-<=10%-evaluated headline.  Returns (payload, report_lines, csv_rows).

    The adaptive run uses the default ``AdaptiveConfig`` on the default
    space re-tiled to ``ADAPTIVE_CHUNK`` (the frontier is tile-size
    invariant, so this is an acquisition granularity, not a space change).
    Hypervolume ratios are computed per workload cell against the exact
    float64 campaign's frontier under ITS pinned reference points — the
    worst cell is the gated quantity.  A second pair of runs checks the
    degenerate contract: ``budget_fraction=1.0`` must reproduce the exact
    jit sweep on the same config bitwise.

    Wall clock is reported but NOT gated: on this ~125k-point space the
    exact fused sweep is already sub-second, so surrogate fitting and
    acquisition scoring eat most of what the skipped evaluations save — the
    evaluation-count reduction (1 / fraction evaluated) is the quantity
    that transfers to spaces where a single tile costs minutes.  The gates
    are frontier quality and budget only.
    """
    sweep_spec = default_campaign_space(chunk_size=FUSED_CHUNK)
    sweep = Campaign(workloads, sweep_spec, constraint=cons,
                     evaluator="jit").run()
    assert sweep.complete
    hv_exact = {k: hv_with_ref(exact_result.frontiers[k], *refs[k])
                for k in refs}

    spec = default_campaign_space(chunk_size=ADAPTIVE_CHUNK)
    acfg = AdaptiveConfig()
    tel = Telemetry()
    adaptive = AdaptiveCampaign(
        workloads, CampaignConfig(space=spec, evaluator="jit",
                                  constraint=cons, adaptive=acfg),
        telemetry=tel)
    ares = adaptive.run()

    ratios = {}
    for k in sorted(refs):
        hv_a = hv_with_ref(adaptive.frontiers[k], *refs[k])
        ratios[f"{k[0]}|{k[1]}"] = hv_a / hv_exact[k] if hv_exact[k] else 1.0
    min_ratio = min(ratios.values())
    eval_reduction = 1.0 / max(ares.fraction_evaluated, 1e-12)
    wall_speedup = sweep.sweep_wall_s / max(ares.result.sweep_wall_s, 1e-9)

    # degenerate contract: budget=100% == the exact jit sweep, bitwise
    exact_jit = Campaign(workloads, CampaignConfig(
        space=spec, evaluator="jit", constraint=cons))
    exact_jit.run()
    full = AdaptiveCampaign(workloads, CampaignConfig(
        space=spec, evaluator="jit", constraint=cons,
        adaptive=AdaptiveConfig(budget_fraction=1.0)))
    full.run()
    budget100_identical = all(
        frontiers_identical(exact_jit.frontiers[k], full.frontiers[k])
        for k in exact_jit.frontiers)

    counters = {c["name"]: c["value"] for c in tel.snapshot()["counters"]
                if c["name"].startswith("adaptive_")}
    payload = {
        "bench": "dse_adaptive_campaign",
        "python": platform.python_version(),
        "sim_model_version": costmodel.SIM_MODEL_VERSION,
        "space": spec.to_dict(),
        "adaptive_config": acfg.to_dict(),
        "workloads": sorted(ratios),
        "candidates_evaluated": ares.candidates_evaluated,
        "space_size": ares.space_size,
        "fraction_evaluated": ares.fraction_evaluated,
        "budget_gate": ADAPTIVE_BUDGET_GATE,
        "hv_ratio": ratios,
        "min_hv_ratio": min_ratio,
        "hv_ratio_gate": ADAPTIVE_HV_GATE,
        "rounds": len(ares.rounds),
        "tiles_evaluated": ares.tiles_evaluated,
        "n_tiles": ares.n_tiles,
        "stopped_on": ares.stopped_on,
        "hv_history": ares.hv_history,
        "budget100_identical_to_exact": budget100_identical,
        "adaptive_wall_s": ares.result.sweep_wall_s,
        "exact_sweep_wall_s": sweep.sweep_wall_s,
        "wall_speedup_vs_fused_sweep": wall_speedup,
        "eval_count_reduction": eval_reduction,
        "counters": dict(sorted(counters.items())),
        "frontiers": frontier_points(ares.result),
    }
    lines = ["", f"## adaptive campaign (surrogate-guided, chunk "
             f"{ADAPTIVE_CHUNK}, {len(ratios)} workloads)", ""]
    for cell, r in sorted(ratios.items()):
        lines.append(f"  {cell:>24}: hv ratio {r:.5f}")
    lines += [
        f"  evaluated {ares.candidates_evaluated:,} / {ares.space_size:,} "
        f"candidates = {ares.fraction_evaluated:.2%} "
        f"(gate <= {ADAPTIVE_BUDGET_GATE:.0%}; {eval_reduction:.1f}x fewer "
        f"evaluations)",
        f"  min hv ratio {min_ratio:.5f} (gate >= {ADAPTIVE_HV_GATE}); "
        f"{len(ares.rounds)} rounds, stopped on {ares.stopped_on}",
        f"  wall: adaptive {ares.result.sweep_wall_s:.1f}s vs exact fused "
        f"sweep {sweep.sweep_wall_s:.1f}s ({wall_speedup:.2f}x — not gated; "
        f"the eval-count reduction is the transferable quantity)",
        f"  budget=100% bitwise == exact sweep: {budget100_identical}",
    ]
    rows = [
        csv_row("dse_adaptive_campaign", ares.result.sweep_wall_s * 1e6,
                f"min_hv_ratio={min_ratio:.5f};"
                f"fraction_evaluated={ares.fraction_evaluated:.4f};"
                f"rounds={len(ares.rounds)};stopped={ares.stopped_on}"),
        csv_row("dse_adaptive_identity", 0.0,
                f"budget100_identical={budget100_identical};"
                f"eval_reduction={eval_reduction:.1f}x;"
                f"wall_speedup={wall_speedup:.2f}x"),
    ]
    return payload, lines, rows


def _op_cost_s(fn, n: int) -> float:
    """Mean wall cost of one ``fn()`` call over ``n`` in-process repeats."""
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def telemetry_matrix(workloads, cons) -> tuple:
    """Telemetry cost + trace artifact: (payload, report_lines, csv_rows).

    Races the fused jit campaign uninstrumented (default ``NullTelemetry``)
    against fully instrumented (``Telemetry()``, tracing on), interleaved
    best of ``EVAL_REPEATS`` each.  Gates (asserted in ``run`` after
    artifacts are written): the two frontiers are BITWISE identical — no
    instrumented value feeds computation — and the instrumentation's
    *attributed* cost stays < ``TELEMETRY_OVERHEAD_GATE`` of the sweep.

    The gated overhead is attributed, not end-to-end differenced: the run's
    instrumentation totals ~100 µs of spans and counter bumps, while the
    sweep's run-to-run wall spread on a shared CI box is several percent —
    a none-vs-``NullTelemetry()`` control (byte-identical code paths)
    differed by ~7% in calibration, so an end-to-end delta gates machine
    noise, not telemetry.  Instead the instrumented run is charged for
    every operation it actually performed — exact span count from its
    tracer ring, counter-inc count reconstructed from its own counters — at
    per-op costs measured in-process on the same primitives.  The raw
    end-to-end delta still rides in the artifact (``end_to_end_frac``) as
    an informational reading.

    Then an instrumented ``LocalFabric`` run (leases + checkpoints + tile
    evaluation in one process) produces the Perfetto-ready
    ``trace_dse_campaign.json`` artifact, validated by
    ``tools/trace_report.py --check`` (required spans present, parent/depth
    nesting sane).
    """
    spec = default_campaign_space(chunk_size=FUSED_CHUNK)

    def one(telemetry):
        r = Campaign(workloads, spec, constraint=cons, evaluator="jit",
                     telemetry=telemetry).run()
        assert r.complete
        return r

    one(None)                              # jit compile warm-up
    # interleaved best-of: alternating uninstrumented / instrumented runs so
    # machine drift (thermal, cache, background load) cannot bias one side
    base = instr = instr_tel = None
    for _ in range(EVAL_REPEATS):
        b = one(None)
        t = Telemetry()
        i = one(t)
        if base is None or b.sweep_wall_s < base.sweep_wall_s:
            base = b
        if instr is None or i.sweep_wall_s < instr.sweep_wall_s:
            instr, instr_tel = i, t
    identical = all(
        frontiers_identical(base.frontiers[k], instr.frontiers[k])
        for k in base.frontiers)
    end_to_end = (instr.sweep_wall_s - base.sweep_wall_s) / base.sweep_wall_s

    # per-op calibration on the same primitives the campaign uses
    cal = Telemetry()
    cal_counter = cal.counter("calibration_total")

    def _span_once():
        with cal.span("calibration", tile=0):
            pass

    span_cost = _op_cost_s(_span_once, 20_000)
    inc_cost = _op_cost_s(cal_counter.inc, 50_000)

    # what the best instrumented run actually did: spans from its ring,
    # counter incs from its own counters (one inc per fused launch, per
    # overflowed workload and per copy of a launch's full rows; candidates
    # + tiles_total per tile; one per checkpoint)
    n_spans_run = len(instr_tel.tracer.records)
    tiles = instr_tel.counter("campaign_tiles_total").value
    launches = instr_tel.counter("evaluator_fused_launches_total").value
    overflows = instr_tel.counter("evaluator_overflows_total").value
    full_fetches = instr_tel.counter("evaluator_full_fetches_total").value
    ckpts = instr_tel.counter("campaign_checkpoint_writes_total").value
    counter_ops = launches + overflows + full_fetches + 2 * tiles + ckpts
    attributed_s = n_spans_run * span_cost + counter_ops * inc_cost
    overhead = attributed_s / instr.sweep_wall_s

    # the trace artifact: one instrumented LocalFabric sweep — the single
    # process that emits lease AND checkpoint_write AND tile_eval spans
    tel = Telemetry()
    campaign = Campaign(workloads, spec, constraint=cons, evaluator="jit",
                        telemetry=tel)
    with tempfile.TemporaryDirectory() as tmp:
        LocalFabric(campaign, n_workers=2, seed=0).run(
            checkpoint_path=os.path.join(tmp, "fabric_ckpt.json"))
    os.makedirs(OUT_DIR, exist_ok=True)
    trace_path = tel.export_trace(os.path.join(OUT_DIR, TRACE_ARTIFACT_NAME))
    n_spans = len(tel.tracer.records)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    check = subprocess.run(
        [sys.executable, os.path.join(repo_root, "tools", "trace_report.py"),
         trace_path, "--check"], capture_output=True, text=True)
    trace_ok = check.returncode == 0

    payload = {
        "overhead": {
            "base_sweep_wall_s": base.sweep_wall_s,
            "instrumented_sweep_wall_s": instr.sweep_wall_s,
            # informational only — noise-bound on a shared box (see
            # telemetry_matrix docstring); the gate rides overhead_frac
            "end_to_end_frac": end_to_end,
            "span_cost_us": span_cost * 1e6,
            "counter_inc_cost_us": inc_cost * 1e6,
            "spans_recorded": n_spans_run,
            "counter_ops": counter_ops,
            "attributed_s": attributed_s,
            "overhead_frac": overhead,
            "gate": TELEMETRY_OVERHEAD_GATE,
            "repeats": EVAL_REPEATS,
            "identical_frontiers": identical,
        },
        "trace_artifact": TRACE_ARTIFACT_NAME,
        "trace_spans": n_spans,
        "trace_check_ok": trace_ok,
        "trace_check_output": (check.stdout + check.stderr)[-2000:],
        "metrics": tel.snapshot(),
    }
    lines = [
        "", "## telemetry (fused jit sweep, interleaved best of "
        f"{EVAL_REPEATS} runs)", "",
        f"  uninstrumented sweep: {base.sweep_wall_s:6.3f}s; "
        f"instrumented: {instr.sweep_wall_s:6.3f}s "
        f"(end-to-end delta {end_to_end:+.2%}, informational)",
        f"  attributed cost: {n_spans_run} spans x {span_cost * 1e6:.2f}us "
        f"+ {counter_ops:.0f} counter incs x {inc_cost * 1e6:.2f}us = "
        f"{attributed_s * 1e3:.3f}ms -> {overhead:.3%} of sweep "
        f"(gate < {TELEMETRY_OVERHEAD_GATE:.0%})",
        f"  instrumented frontier bitwise == uninstrumented: {identical}",
        f"  trace artifact: {trace_path} ({n_spans} spans, "
        f"trace_report --check {'OK' if trace_ok else 'FAILED'})",
    ]
    rows = [csv_row("dse_telemetry_overhead", overhead * 1e6,
                    f"overhead_frac={overhead:.6f};identical={identical};"
                    f"trace_spans={n_spans};trace_check_ok={trace_ok}")]
    return payload, lines, rows


def run() -> list:
    ensure_artifacts()
    spec = default_campaign_space()
    cons = dse.Constraint(max_power_w=40_000, min_hbm_fit=False)
    campaign = Campaign.from_artifacts(ART_DIR, spec, constraint=cons)
    result = campaign.run()
    assert result.complete, (result.tiles_done, result.n_tiles)

    n_cands = len(spec)
    n_workloads = len(campaign.workloads)
    us_per_cand = result.sweep_wall_s / max(result.candidates_evaluated, 1) * 1e6

    # acceptance gate: streamed frontier == one-shot pareto_search on the
    # SAME space (first workload; the one-shot side materializes the whole
    # space once, which is exactly the cost the campaign path avoids)
    wl = campaign.workloads[0]
    key = (wl.arch, wl.shape)
    oneshot = dse.pareto_search(wl, spec.slice(0, n_cands), cons)[key]
    identical = frontiers_identical(result.frontiers[key], oneshot)

    # telemetry: overhead/identity gates + the Perfetto trace artifact; its
    # metrics snapshot rides in BENCH_dse_campaign.json under "telemetry"
    tel_payload, tel_lines, tel_rows = telemetry_matrix(
        campaign.workloads, cons)

    path = store.save_campaign(
        result, spec.to_dict(), dataclasses.asdict(cons), campaign.evaluator,
        OUT_DIR, seed=0, extra={"telemetry": tel_payload})

    report = [
        "# Streaming DSE campaign (mega-space sweep)",
        f"space: {n_cands} candidates ({spec.n_rows} rows x "
        f"{spec.freq_points} DVFS points), {result.n_tiles} tiles of "
        f"{spec.chunk_size}",
        f"workloads: {n_workloads}; evaluations: "
        f"{result.candidates_evaluated}",
        f"throughput: {result.candidates_per_sec:,.0f} candidates/sec "
        f"({us_per_cand:.2f} us/candidate incl. tile materialization)",
        f"streamed-vs-oneshot frontier identical: {identical}",
        f"artifact: {path}",
        "",
        "frontier trajectory (first workload, every 5th tile):",
    ]
    for snap in result.trajectories[key][::5]:
        report.append(
            f"  tile {snap.tile:3d}: evaluated {snap.evaluated:7d}, "
            f"frontier {snap.frontier_size:4d}, "
            f"best {snap.best_energy_j:10.1f} J / "
            f"{snap.best_latency_s * 1e3:8.2f} ms, "
            f"hv {snap.hypervolume:.3e}")
    for (arch, shape), front in sorted(result.frontiers.items()):
        report.append(f"  {arch} x {shape}: {len(front)} frontier points of "
                      f"{front.feasible_count} feasible")

    # topology model: the factorization axis now carries signal — report the
    # frontier rows WITH their meshes and the same-count ties it broke
    ties = mesh_tie_report(wl)
    report += [
        "",
        f"mesh factorization signal ({ties['chip']} x{ties['n_chips']}, "
        f"{len(ties['meshes'])} same-count meshes):",
        f"  frontier ties before (mesh-agnostic model): {ties['ties_before']}",
        f"  frontier ties after  (topology model):      {ties['ties_after']}",
        f"  ties broken: {ties['ties_broken']}",
    ]
    for mesh, t in zip(ties["meshes"], ties["t_coll_topology"]):
        report.append(f"    mesh {'x'.join(map(str, mesh)):>8}: "
                      f"t_coll {t * 1e3:9.3f} ms")
    front = result.frontiers[key]
    report.append("")
    report.append("mesh-differentiated frontier rows (first workload, "
                  "first 12 by latency):")
    for cand, e, lat in list(zip(front.candidates, front.energy_j,
                                 front.latency_s))[:12]:
        report.append(
            f"    {cand.chip:>8} x{cand.n_chips:<4} "
            f"mesh {'x'.join(map(str, cand.mesh)):>8} @ "
            f"{cand.freq_mhz:7.1f} MHz   {lat * 1e3:9.2f} ms   "
            f"{e:12.1f} J")

    # evaluator race: PR-3 jit baseline vs fused jit vs fused Pallas kernel
    refs = {k: (fr.ref_energy_j, fr.ref_latency_s)
            for k, fr in campaign.frontiers.items()}
    eval_payload, eval_lines, eval_rows = evaluator_matrix(
        campaign.workloads, cons, result, refs)
    report += eval_lines
    os.makedirs(OUT_DIR, exist_ok=True)
    eval_path = os.path.join(OUT_DIR, EVALUATOR_BENCH_NAME)
    with open(eval_path, "w") as f:
        json.dump(eval_payload, f, indent=1)
    report.append(f"  artifact: {eval_path}")

    # distributed fabric: N workers, one frontier, same bits
    dist_payload, dist_lines, dist_rows = distributed_matrix(
        campaign.workloads, cons)
    report += dist_lines
    dist_path = os.path.join(OUT_DIR, DISTRIBUTED_BENCH_NAME)
    with open(dist_path, "w") as f:
        json.dump(dist_payload, f, indent=1)
    report.append(f"  artifact: {dist_path}")

    # adaptive campaign: the surrogate-guided budgeted search vs the sweep
    ad_payload, ad_lines, ad_rows = adaptive_matrix(
        campaign.workloads, cons, result, refs)
    report += ad_lines
    ad_path = os.path.join(OUT_DIR, ADAPTIVE_BENCH_NAME)
    with open(ad_path, "w") as f:
        json.dump(ad_payload, f, indent=1)
    report.append(f"  artifact: {ad_path}")
    report += tel_lines
    write_report("dse_campaign.md", "\n".join(report))

    rows = eval_rows + dist_rows + ad_rows + tel_rows + [
        csv_row("dse_campaign_throughput", us_per_cand,
                f"cands_per_sec={result.candidates_per_sec:.0f};"
                f"space={n_cands};tiles={result.n_tiles};"
                f"workloads={n_workloads}"),
        csv_row("dse_campaign_frontier", 0.0,
                ";".join(f"{a}x{s}={len(f)}" for (a, s), f
                         in sorted(result.frontiers.items()))),
        csv_row("dse_campaign_identity", 0.0,
                f"streamed_equals_oneshot={identical}"),
        csv_row("dse_campaign_mesh_signal", 0.0,
                f"ties_before={ties['ties_before']};"
                f"ties_after={ties['ties_after']};"
                f"ties_broken={ties['ties_broken']}"),
    ]
    # gate AFTER report/rows so a mismatch still leaves diagnostics behind
    assert identical, "streamed frontier diverged from one-shot pareto_search"
    assert ties["ties_broken"] > 0, \
        "topology model failed to break same-count mesh ties"
    pvn = eval_payload["pallas_vs_numpy"]
    assert pvn["identical_candidate_set"], \
        "pallas evaluator frontier candidate set diverged from numpy"
    assert pvn["max_hv_rel_diff"] <= 1e-6, \
        f"pallas hypervolume drifted {pvn['max_hv_rel_diff']:.2e} (> 1e-6)"
    assert dist_payload["all_identical_to_single_process"], \
        "a distributed fabric frontier diverged from the single-process run"
    assert ad_payload["budget100_identical_to_exact"], \
        "adaptive campaign at budget=100% diverged from the exact jit sweep"
    assert ad_payload["min_hv_ratio"] >= ADAPTIVE_HV_GATE, \
        f"adaptive frontier hypervolume ratio {ad_payload['min_hv_ratio']:.5f}" \
        f" (worst cell) below the {ADAPTIVE_HV_GATE} gate"
    assert ad_payload["fraction_evaluated"] <= ADAPTIVE_BUDGET_GATE, \
        f"adaptive campaign evaluated {ad_payload['fraction_evaluated']:.2%} " \
        f"of the space (gate <= {ADAPTIVE_BUDGET_GATE:.0%})"
    tover = tel_payload["overhead"]
    assert tover["identical_frontiers"], \
        "instrumented campaign frontier diverged from uninstrumented"
    assert tel_payload["trace_check_ok"], \
        "trace_dse_campaign.json failed tools/trace_report.py --check:\n" \
        + tel_payload["trace_check_output"]
    # throughput gates LAST: machine-sensitive, must never mask a
    # correctness verdict above
    assert tover["overhead_frac"] < TELEMETRY_OVERHEAD_GATE, \
        f"attributed telemetry cost {tover['overhead_frac']:.3%} " \
        f"({tover['spans_recorded']} spans + {tover['counter_ops']:.0f} " \
        f"counter incs) exceeds {TELEMETRY_OVERHEAD_GATE:.0%} of the sweep"
    speedup = eval_payload["speedup_pallas_vs_jit_baseline"]
    assert speedup >= 3.0, \
        f"fused pallas pipeline only {speedup:.2f}x over the jit baseline"
    scaling = dist_payload["scaling_2w_vs_1w"]
    assert scaling >= SCALING_GATE, \
        f"2-worker fabric only {scaling:.2f}x over 1 worker (busy-CPU)"
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
