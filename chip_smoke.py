"""Bring-up smoke run of the main path on one TPU chip.

    python chip_smoke.py

One process, four phases, through the entry points a user calls.  JAX's
first device must be a TPU: anything else exits non-zero before any phase
runs and before any result is printed.

1. census     ``repro.launch.dryrun.run_cell`` lowers the six benchmark
              cells at published widths on 512 host placeholder devices
              (the CPU backend), into a temporary directory;
2. device     the chip's platform, kind and count are printed;
3. campaign   ``Campaign.from_artifacts`` + ``CampaignConfig`` sweep the
              default 125,440-candidate space over five of the cells with
              the compiled Pallas sweep and the fused jit sweep, both checked
              against the host float64 numpy oracle;
4. selection  ``repro.launch.serve`` builds a ``FrontierIndex`` from the
              Pallas campaign's checkpoint and answers the self-check, the
              held-out sixth cell (one fused mini-campaign on the chip) and
              a deadline query (predictors fitted from the census).

The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; any failed check raises instead.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
os.environ.setdefault("REPRO_SAVE_HLO", "0")

# first repro import: the dry-run sets the host placeholder device count
# before JAX initializes a backend
from repro.launch import dryrun  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import costmodel, dataset, dse, predictors  # noqa: E402
from repro.dse_campaign import (Campaign, CampaignConfig,  # noqa: E402
                                canonical_frontier, default_campaign_space,
                                hypervolume_2d, store)
from repro.dse_campaign.runner import workload_to_dict  # noqa: E402
from repro.kernels import dse_sweep, ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

# the six benchmark cells; the last one is held out of the campaign and
# asked of the selection engine as a novel family
CELLS = (("qwen3_14b", "train_4k"), ("qwen3_14b", "decode_32k"),
         ("stablelm_1_6b", "train_4k"), ("stablelm_1_6b", "prefill_32k"),
         ("mamba2_130m", "train_4k"), ("zamba2_1_2b", "train_4k"))
HELD_OUT = CELLS[-1]
CHUNK = 32768                 # the campaign benchmark's fused tile size
CONSTRAINT = dse.Constraint(max_power_w=40_000, min_hbm_fit=False)
# float32 device arithmetic against the float64 oracle: frontier values
# agree to this relative tolerance, and a candidate may be on one frontier
# and not the other only when the other frontier holds a point within this
# tolerance of dominating it (a float32 near-tie)
NEAR_TIE_RTOL = 1e-5


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- phases -------------------------------------------------------------------


def require_tpu() -> dict:
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        sys.stderr.write(f"chip_smoke: no TPU found (JAX device: {dev}); "
                         "this run needs one TPU chip\n")
        raise SystemExit(2)
    return dev


def census(campaign_dir: str, held_out_dir: str) -> None:
    for arch, shape in CELLS:
        out = held_out_dir if (arch, shape) == HELD_OUT else campaign_dir
        t0 = time.perf_counter()
        r = dryrun.run_cell(arch, shape, multi_pod=False, out_dir=out)
        log(f"[census] {arch} x {shape}: {r['mesh']} on host placeholder "
            f"devices, state/dev {r['memory']['state_gb_per_device']:.2f} GB, "
            f"{time.perf_counter() - t0:.1f} s")
    for d, n in ((campaign_dir, len(CELLS) - 1), (held_out_dir, 1)):
        found = dataset.load_dryrun_artifacts(d)
        check(len(found) == n, f"census wrote {len(found)} cells to {d}, "
                               f"expected {n}")


def check_compiled_kernel(cfg: CampaignConfig, n_workloads: int) -> None:
    """The Pallas sweep the campaign runs is compiled, not interpreted, and
    its program holds the TPU kernel."""
    check(not ops.default_interpret(), "the Pallas sweep would be interpreted")
    cons = cfg.resolved_constraint
    fn = dse_sweep._jit_dse_sweep(cfg.sim, cons.max_power_w,
                                  cons.max_latency_s, cons.min_hbm_fit, False,
                                  cfg.max_survivors)
    lanes = dse_sweep.padded_lanes(CHUNK, n_workloads)
    text = fn.lower(
        jax.ShapeDtypeStruct((len(dse_sweep.CAND_COLS), lanes), np.float32),
        jax.ShapeDtypeStruct((n_workloads, len(costmodel.WL_COLS)),
                             np.float32)).compile().as_text()
    check("tpu_custom_call" in text,
          "the compiled Pallas sweep holds no tpu_custom_call")
    log("[campaign] compiled Pallas sweep holds a tpu_custom_call")


def run_campaign(art_dir: str, cfg: CampaignConfig):
    """Warm run of one tile (compilation + one tile), then the full sweep
    on the warm program; returns (campaign, result)."""
    t0 = time.perf_counter()
    Campaign.from_artifacts(art_dir, cfg).run(max_tiles=1)
    first_tile_s = time.perf_counter() - t0
    camp = Campaign.from_artifacts(art_dir, cfg)
    t0 = time.perf_counter()
    result = camp.run()
    steady_s = time.perf_counter() - t0
    check(result.complete, f"{cfg.evaluator} campaign did not complete")
    log(f"[campaign] {cfg.evaluator}: first tile (compile or cache load + 1 "
        f"tile) {first_tile_s:.6f} s, compile ~"
        f"{first_tile_s - steady_s / result.n_tiles:.6f} s; steady sweep "
        f"{steady_s:.6f} s over {result.n_tiles} tiles x "
        f"{len(camp.workloads)} workloads, "
        f"{result.candidates_evaluated / steady_s:,.0f} candidates/s")
    return camp, result


def frontier_agreement(device: dse.ParetoFrontier,
                       oracle: dse.ParetoFrontier, rtol: float) -> dict:
    """Compare a float32 device frontier with the float64 oracle's.

    Candidates on both must agree in value to ``rtol``; a candidate on only
    one side must be ``rtol``-dominated by a point of the other side (a
    float32 near-tie).  Returns the counts and the verdict."""
    _, de, dl, di = canonical_frontier(device)
    _, oe, ol, oi = canonical_frontier(oracle)
    common, d_at, o_at = np.intersect1d(di, oi, return_indices=True)
    rel = 0.0
    if common.size:
        rel = float(max(np.max(np.abs(de[d_at] / oe[o_at] - 1.0)),
                        np.max(np.abs(dl[d_at] / ol[o_at] - 1.0))))

    def near_dominated(e, l, other_e, other_l):
        return bool(np.any((other_e <= e * (1 + rtol))
                           & (other_l <= l * (1 + rtol))))

    only_d = np.setdiff1d(np.arange(di.size), d_at)
    only_o = np.setdiff1d(np.arange(oi.size), o_at)
    ties = (all(near_dominated(de[j], dl[j], oe, ol) for j in only_d)
            and all(near_dominated(oe[j], ol[j], de, dl) for j in only_o))
    return {"common": int(common.size), "only_device": int(only_d.size),
            "only_oracle": int(only_o.size), "max_rel_diff": rel,
            "ok": bool(common.size and rel <= rtol and ties)}


def compare_to_oracle(name: str, result, oracle_camp, oracle):
    for key in sorted(oracle.frontiers):
        agree = frontier_agreement(result.frontiers[key],
                                   oracle.frontiers[key], NEAR_TIE_RTOL)
        ref = oracle_camp.frontiers[key]
        hv = [hypervolume_2d(f.energy_j, f.latency_s, ref.ref_energy_j,
                             ref.ref_latency_s)
              for f in (result.frontiers[key], oracle.frontiers[key])]
        hv_rel = abs(hv[0] / hv[1] - 1.0) if hv[1] else abs(hv[0])
        log(f"[oracle] {name} {key[0]}|{key[1]}: {agree['common']} common, "
            f"{agree['only_device']} only on device, {agree['only_oracle']} "
            f"only in oracle, max rel diff {agree['max_rel_diff']:.3e}, "
            f"hv rel diff {hv_rel:.3e}")
        check(agree["ok"], f"{name} frontier of {key} differs from the "
                           f"numpy oracle beyond float32 near-ties: {agree}")
        check(hv_rel <= NEAR_TIE_RTOL,
              f"{name} hypervolume of {key} off by {hv_rel:.3e}")


def selection(tmp: str, ckpt: str, held_out_dir: str, campaign_dir: str,
              oracle_space_cfg: CampaignConfig) -> None:
    index_path = serve.build_index(ckpt, os.path.join(tmp, "index.json"))

    answers, engine = serve.select_queries(index_path)
    check(answers and all(a.provenance == "index_exact" for a in answers),
          f"self-check provenances {[a.provenance for a in answers]}")
    check(engine.fused_launches == 0, "an index hit launched a sweep")
    no_error(answers)

    held = Campaign.from_artifacts(held_out_dir, oracle_space_cfg).workloads
    qpath = os.path.join(tmp, "held_out.json")
    with open(qpath, "w") as f:
        json.dump([{"workload": workload_to_dict(held[0])}], f)
    t0 = time.perf_counter()
    answers, engine = serve.select_queries(index_path, qpath)
    log(f"[selection] held-out mini-campaign {time.perf_counter() - t0:.3f} s "
        "(compile included)")
    no_error(answers)
    check([a.provenance for a in answers] == ["mini_campaign"],
          f"held-out provenance {[a.provenance for a in answers]}")
    check(engine.fused_launches == 1,
          f"held-out query took {engine.fused_launches} fused launches")
    oracle = Campaign(held, oracle_space_cfg).run()
    agree = frontier_agreement(answers[0].frontier(),
                               oracle.frontiers[HELD_OUT], NEAR_TIE_RTOL)
    log(f"[oracle] held-out mini-campaign {HELD_OUT[0]}|{HELD_OUT[1]}: "
        f"{agree}")
    check(agree["ok"], f"held-out frontier differs from the oracle: {agree}")

    X, y_power, y_cycles, _ = dataset.build_dataset(campaign_dir)
    rf = predictors.RandomForestRegressor().fit(X, y_power)
    knn = predictors.KNNRegressor().fit(X, y_cycles)
    qpath = os.path.join(tmp, "deadline.json")
    with open(qpath, "w") as f:
        json.dump([{"workload": workload_to_dict(held[0]),
                    "deadline_s": 0.0}], f)
    answers, engine = serve.select_queries(index_path, qpath,
                                           power_model=rf, cycles_model=knn)
    no_error(answers)
    check([(a.provenance, a.degraded_reason) for a in answers]
          == [("predictor_only", "deadline")],
          f"deadline answer {[(a.provenance, a.degraded_reason) for a in answers]}")
    check(answers[0].choices, "predictor-only answer holds no choice")


def no_error(answers) -> None:
    """A failed sweep degrades an answer instead of raising; count it."""
    bad = [a.qid for a in answers if a.degraded_reason == "mini_campaign_error"]
    check(not bad, f"answers {bad} degraded after a failed mini-campaign")


def main() -> None:
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    dev = require_tpu()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        campaign_dir = os.path.join(tmp, "census")
        held_out_dir = os.path.join(tmp, "held_out")
        for d in (campaign_dir, held_out_dir):
            os.makedirs(d)
        census(campaign_dir, held_out_dir)
        log(f"[device] platform={dev['platform']} kind={dev['kind']} "
            f"count={dev['count']}; compile cache {cache_dir} "
            f"{'warm' if warm else 'cold'} at start")

        space = default_campaign_space(chunk_size=CHUNK)
        base = CampaignConfig(space=space, constraint=CONSTRAINT)
        check(len(space) == 125_440, f"default space holds {len(space)}")
        pallas_camp, pallas = run_campaign(
            campaign_dir, base.replace(evaluator="pallas"))
        _, jit = run_campaign(campaign_dir, base.replace(evaluator="jit"))
        check_compiled_kernel(base, len(CELLS) - 1)
        t0 = time.perf_counter()
        oracle_camp = Campaign.from_artifacts(campaign_dir, base)
        oracle = oracle_camp.run()
        log(f"[campaign] numpy oracle (host float64): "
            f"{time.perf_counter() - t0:.3f} s")
        compare_to_oracle("pallas", pallas, oracle_camp, oracle)
        compare_to_oracle("jit", jit, oracle_camp, oracle)

        ckpt = os.path.join(tmp, "pallas.ckpt.json")
        store.save_checkpoint(pallas_camp.state_dict(), ckpt)
        selection(tmp, ckpt, held_out_dir, campaign_dir, base)
    log(f"[done] {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
