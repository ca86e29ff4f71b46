"""Adaptive (surrogate-guided) campaign tests: budget=100% degenerates
bitwise to the exact sweep, resume == fresh, the frontier only ever contains
exactly-evaluated candidates, the distributed runner is bitwise-identical to
single-process (crashes and duplicates included), and the hypervolume-gain
acquisition matches the brute-force oracle."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised on bare installs
    from _hypothesis_stub import given, settings, st

from repro.core import dse
from repro.dse_campaign import (AdaptiveCampaign, AdaptiveConfig, Campaign,
                                CampaignConfig, FaultInjection, LeaseBoard,
                                frontiers_identical, hypervolume_2d,
                                hypervolume_gain_2d, run_adaptive_distributed,
                                tile_span, tiny_campaign_space)

BASE = {"flops": 3.2e14, "hbm_bytes": 4.5e13, "collective_bytes": 5e11,
        "wire_bytes": 7e11}
WL = dse.Workload("qwen3_14b", "train_4k", BASE, 256, 0.5)
KEY = ("qwen3_14b", "train_4k")
CONS = dse.Constraint(max_power_w=50_000)


def adaptive_cfg(**kw):
    """Tiny-space knobs: enough budget for a seed round plus a few acquire
    rounds at chunk 64 (800 candidates / 13 tiles)."""
    kw.setdefault("budget_fraction", 0.6)
    kw.setdefault("seed_fraction", 0.15)
    kw.setdefault("round_fraction", 0.08)
    kw.setdefault("train_sample", 48)
    kw.setdefault("plateau_rounds", 2)
    return AdaptiveConfig(**kw)


def campaign_cfg(acfg=None, **kw):
    kw.setdefault("space", tiny_campaign_space(chunk_size=64))
    kw.setdefault("evaluator", "jit")
    kw.setdefault("constraint", CONS)
    return CampaignConfig(adaptive=acfg, **kw)


# --- config ------------------------------------------------------------------


def test_adaptive_config_validation():
    for bad in [dict(budget_fraction=0.0), dict(budget_fraction=1.5),
                dict(seed_fraction=0.0), dict(round_fraction=0.0),
                dict(plateau_rounds=0), dict(train_sample=0),
                dict(n_trees=0), dict(refresh_trees=9, n_trees=8)]:
        with pytest.raises(ValueError):
            AdaptiveConfig(**bad)


def test_adaptive_config_dict_roundtrip():
    acfg = adaptive_cfg(explore_weight=1.7, seed=3)
    assert AdaptiveConfig.from_dict(acfg.to_dict()) == acfg


def test_adaptive_campaign_requires_adaptive_config():
    with pytest.raises(ValueError, match="config.adaptive"):
        AdaptiveCampaign([WL], campaign_cfg(acfg=None))


# --- budget=100%: the degenerate exact sweep ---------------------------------


def test_budget_100_is_bitwise_exact_sweep():
    exact = Campaign([WL], campaign_cfg())
    er = exact.run()
    ad = AdaptiveCampaign(
        [WL], campaign_cfg(adaptive_cfg(budget_fraction=1.0)))
    ar = ad.run()
    assert frontiers_identical(ad.frontiers[KEY], exact.frontiers[KEY])
    assert ar.candidates_evaluated == er.candidates_evaluated == ar.space_size
    assert ar.fraction_evaluated == 1.0
    assert ar.tiles_evaluated == ar.n_tiles


# --- budget + frontier-subset invariants -------------------------------------


def run_tiny(acfg, telemetry=None):
    ad = AdaptiveCampaign([WL], campaign_cfg(acfg), telemetry=telemetry)
    return ad, ad.run()


def assert_frontier_subset_of_evaluated(ad, res):
    evaluated = set()
    for rtiles in res.rounds:
        for t in rtiles:
            lo, hi = tile_span(ad.space, t)
            evaluated.update(range(lo, hi))
    for key, fr in ad.frontiers.items():
        assert len(fr.indices), f"empty frontier for {key}"
        missing = [int(i) for i in fr.indices if int(i) not in evaluated]
        assert not missing, (
            f"{key}: frontier indices {missing} were never exactly evaluated")


def test_adaptive_respects_budget_and_frontier_is_exact():
    ad, res = run_tiny(adaptive_cfg())
    assert res.stopped_on in ("plateau", "budget", "exhausted")
    assert res.fraction_evaluated <= ad.acfg.budget_fraction + 1e-12
    assert res.candidates_evaluated == sum(
        tile_span(ad.space, t)[1] - tile_span(ad.space, t)[0]
        for r in res.rounds for t in r)
    assert_frontier_subset_of_evaluated(ad, res)
    # hv against the pinned refs only ever grows as the frontier accretes
    hv = np.asarray(res.hv_history)
    assert np.all(np.diff(hv) >= -1e-12)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000), budget=st.sampled_from([0.3, 0.45, 0.6]))
def test_adaptive_frontier_subset_property(seed, budget):
    """Whatever the rng seed and budget, every frontier point comes from an
    exactly-evaluated tile — surrogate scores never fabricate candidates."""
    ad, res = run_tiny(adaptive_cfg(budget_fraction=budget, seed=seed))
    assert_frontier_subset_of_evaluated(ad, res)
    assert res.fraction_evaluated <= budget + 1e-12


# --- resume == fresh ---------------------------------------------------------


def test_adaptive_resume_matches_fresh(tmp_path):
    acfg = adaptive_cfg()
    fresh, fr = run_tiny(acfg)

    ckpt = str(tmp_path / "adaptive.ckpt.json")
    part = AdaptiveCampaign([WL], campaign_cfg(acfg))
    pr = part.run(checkpoint_path=ckpt, max_rounds=2)
    assert pr.stopped_on == "max_rounds"
    assert len(pr.rounds) == 2

    resumed = AdaptiveCampaign.from_checkpoint(ckpt)
    assert resumed.rounds == fresh.rounds[:2]
    assert resumed.acq_refs == {k: v for k, v in part.acq_refs.items()}
    rr = resumed.run(checkpoint_path=ckpt)

    assert rr.rounds == fr.rounds
    assert rr.hv_history == fr.hv_history
    assert rr.stopped_on == fr.stopped_on
    assert rr.candidates_evaluated == fr.candidates_evaluated
    assert frontiers_identical(resumed.frontiers[KEY], fresh.frontiers[KEY])


def test_adaptive_checkpoint_serializes_acquisition_refs(tmp_path):
    acfg = adaptive_cfg()
    ad = AdaptiveCampaign([WL], campaign_cfg(acfg))
    ckpt = str(tmp_path / "refs.ckpt.json")
    ad.run(checkpoint_path=ckpt, max_rounds=1)
    state = ad.state_dict()
    # the acquisition reference points are explicit in the schema — a resume
    # must score candidates against the same (pinned) refs, not re-derive them
    refs = state["adaptive"]["acq_refs"]
    assert set(refs) == {f"{a}|{s}" for a, s in ad.acq_refs}
    for (a, s), v in ad.acq_refs.items():
        assert v is not None
        assert refs[f"{a}|{s}"] == [v[0], v[1]]
    resumed = AdaptiveCampaign.from_checkpoint(ckpt)
    assert resumed.acq_refs == ad.acq_refs


def test_plain_campaign_resume_rejects_missing_adaptive_state(tmp_path):
    ckpt = str(tmp_path / "plain.ckpt.json")
    camp = Campaign([WL], campaign_cfg())
    camp.run(checkpoint_path=ckpt)
    with pytest.raises(ValueError, match="no 'adaptive' state"):
        AdaptiveCampaign.from_checkpoint(ckpt)


# --- hypervolume-gain acquisition vs brute-force oracle ----------------------


def hv_union(e, l, ref_e, ref_l):
    """Brute-force dominated area of an ARBITRARY point set (running-min
    sweep; ``hypervolume_2d`` itself assumes a non-dominated input)."""
    e, l = np.asarray(e, np.float64), np.asarray(l, np.float64)
    inside = (e < ref_e) & (l < ref_l)
    if not inside.any():
        return 0.0
    e, l = e[inside], l[inside]
    order = np.lexsort((e, l))
    e, l = e[order], l[order]
    e_run = np.minimum.accumulate(e)
    right = np.append(l[1:], ref_l)
    return float(np.sum((ref_e - e_run) * (right - l)))


def hv_gain_oracle(e, l, fe, fl, ref_e, ref_l):
    base = hv_union(fe, fl, ref_e, ref_l)
    return np.array([
        hv_union(np.append(fe, ei), np.append(fl, li), ref_e, ref_l) - base
        for ei, li in zip(e, l)])


def test_hv_union_oracle_matches_hypervolume_2d_on_frontier():
    # on a genuinely non-dominated set the two definitions coincide — the
    # oracle below is anchored to the library's own hypervolume
    fe = np.array([8.0, 5.0, 3.0, 1.0])
    fl = np.array([1.0, 2.0, 4.0, 7.0])
    assert hv_union(fe, fl, 10.0, 10.0) == hypervolume_2d(fe, fl, 10.0, 10.0)


def test_hypervolume_gain_matches_oracle():
    rng = np.random.default_rng(7)
    fe, fl = rng.uniform(1, 9, 40), rng.uniform(1, 9, 40)
    e, l = rng.uniform(0.5, 11, 300), rng.uniform(0.5, 11, 300)
    gains = hypervolume_gain_2d(e, l, fe, fl, 10.0, 10.0)
    np.testing.assert_allclose(gains, hv_gain_oracle(e, l, fe, fl, 10.0, 10.0),
                               rtol=1e-12, atol=1e-12)


def test_hypervolume_gain_edge_cases():
    fe = np.array([2.0, 1.0])
    fl = np.array([1.0, 3.0])
    # dominated candidate: zero gain; outside the ref box: zero gain
    gains = hypervolume_gain_2d(np.array([2.5, 12.0, 0.5]),
                                np.array([2.5, 1.0, 0.5]),
                                fe, fl, 10.0, 10.0)
    assert gains[0] == 0.0 and gains[1] == 0.0 and gains[2] > 0.0
    # empty frontier: gain is the candidate's own rectangle
    alone = hypervolume_gain_2d(np.array([4.0]), np.array([6.0]),
                                np.array([]), np.array([]), 10.0, 10.0)
    np.testing.assert_allclose(alone, [(10.0 - 4.0) * (10.0 - 6.0)])


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.floats(0.1, 9.9), st.floats(0.1, 9.9)),
                min_size=0, max_size=25),
       st.lists(st.tuples(st.floats(0.05, 12.0), st.floats(0.05, 12.0)),
                min_size=1, max_size=25))
def test_hypervolume_gain_oracle_property(front, cands):
    fe = np.array([p[0] for p in front])
    fl = np.array([p[1] for p in front])
    e = np.array([p[0] for p in cands])
    l = np.array([p[1] for p in cands])
    gains = hypervolume_gain_2d(e, l, fe, fl, 10.0, 10.0, chunk=4)
    oracle = hv_gain_oracle(e, l, fe, fl, 10.0, 10.0)
    np.testing.assert_allclose(gains, oracle, rtol=1e-9, atol=1e-9)
    assert np.all(gains >= 0.0)


# --- LeaseBoard acquisition-priority leasing ---------------------------------


def test_leaseboard_set_priority_orders_leases():
    board = LeaseBoard(6, done=[5])
    board.set_priority([4, 1])
    order = [board.next_tile(0) for _ in range(5)]
    # ranked tiles first (in rank order), then the rest by index
    assert order == [4, 1, 0, 2, 3]
    assert board.next_tile(0) is None


def test_leaseboard_set_priority_survives_revoke():
    board = LeaseBoard(5)
    board.set_priority([3, 0, 2])
    assert board.next_tile(1) == 3
    board.revoke_worker(1)         # tile 3 re-pends at its rank
    assert [board.next_tile(0) for _ in range(5)] == [3, 0, 2, 1, 4]


def test_leaseboard_set_priority_rejects_duplicates():
    with pytest.raises(ValueError):
        LeaseBoard(4).set_priority([1, 1])


# --- distributed == single-process -------------------------------------------


@pytest.mark.parametrize("fault", [
    None,
    FaultInjection(kill_worker=1, kill_after_tiles=1),
], ids=["clean", "worker_crash"])
def test_adaptive_distributed_matches_single_process(fault):
    acfg = adaptive_cfg()
    cfg = campaign_cfg(acfg, n_workers=2)
    single = AdaptiveCampaign([WL], cfg)
    sr = single.run()

    dr, stats = run_adaptive_distributed([WL], cfg, fault=fault)
    assert dr.rounds == sr.rounds
    assert dr.hv_history == sr.hv_history
    assert dr.stopped_on == sr.stopped_on
    assert frontiers_identical(dr.frontiers[KEY], single.frontiers[KEY])
    if fault is not None:
        assert stats["lost_workers"] == [1]
        assert stats["reissued_tiles"] >= 1


def test_adaptive_worker_pool_refuses_tpu_parent(monkeypatch):
    """A parent whose JAX backend is the TPU holds the chip; the adaptive
    worker pool refuses to start instead of spawning workers that would
    reach for it, and spawns no process.  The parent's backend is steered
    here by replacing ``jax.default_backend``."""
    import multiprocessing as mp
    import jax
    cfg = campaign_cfg(adaptive_cfg(), n_workers=1)
    before = set(mp.active_children())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        run_adaptive_distributed([WL], cfg)
    assert set(mp.active_children()) == before
