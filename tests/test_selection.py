"""Accelerator-selection serving tests: index-hit identity with offline
campaign picks, novel-workload fallback parity, deadline degradation,
batched-vs-sequential equality with the one-fused-launch assertion, the
FrontierIndex version gates, and the four-entry-points-one-CampaignConfig
API contract."""

import dataclasses
import json
import warnings

import numpy as np
import pytest

from repro.core import costmodel, dse
from repro.dse_campaign import (Campaign, CampaignConfig, SliceVariant,
                                SpaceSpec, TileEvaluator, frontiers_identical,
                                run_distributed, store)
from repro.dse_campaign.frontier import StreamingFrontier
from repro.serving.engine import PROVENANCES, SelectionEngine
from repro.serving.frontier_index import (INDEX_SCHEMA_VERSION, FrontierIndex,
                                          family_key)
from repro.dse_campaign.fabric import FakeClock
from repro.telemetry import Telemetry

BASE = {"flops": 3.2e14, "hbm_bytes": 4.5e13, "collective_bytes": 5e11,
        "wire_bytes": 7e11}


def wl(arch="qwen3_14b", shape="train_4k", scale=1.0, chips=256, gb=0.5):
    return dse.Workload(arch, shape,
                        {k: v * scale for k, v in BASE.items()}, chips, gb)


CACHED = [wl(),
          wl("stablelm_1_6b", scale=0.3, chips=64, gb=0.2),
          wl("mamba2_130m", scale=0.05, chips=16, gb=0.05)]
NOVEL = wl(scale=1.07)                     # same (arch, shape), new census


def small_spec(**kw):
    kw.setdefault("chips", ("tpu-v5e", "tpu-v4"))
    kw.setdefault("chip_counts", (16, 64))
    kw.setdefault("freq_points", 5)
    kw.setdefault("variants", (SliceVariant(),))
    kw.setdefault("chunk_size", 64)
    return SpaceSpec(**kw)


def serving_config(**kw):
    kw.setdefault("space", small_spec())
    kw.setdefault("evaluator", "jit")
    kw.setdefault("constraint", dse.Constraint(max_power_w=50_000))
    return CampaignConfig(**kw)


class StubModel:
    """Deterministic ``.predict(X)`` stand-in for a fitted predictor."""

    def __init__(self, scale):
        self.scale = scale

    def predict(self, X):
        X = np.asarray(X, np.float64)
        return self.scale * (1.0 + np.abs(X).sum(axis=1)
                             / (1.0 + np.abs(X).max() * X.shape[1]))


@pytest.fixture(scope="module")
def offline():
    """One completed campaign + its index, shared by the module's tests."""
    camp = Campaign(CACHED, serving_config())
    result = camp.run()
    assert result.complete
    return camp, result, FrontierIndex.from_campaign(camp)


# --- FrontierIndex ------------------------------------------------------------


def test_index_roundtrip_and_lookup(tmp_path, offline):
    camp, result, index = offline
    path = index.save(str(tmp_path / "index.json"))
    loaded = FrontierIndex.load(path)
    assert len(loaded) == len(CACHED)
    assert set(loaded.keys) == {(w.arch, w.shape) for w in CACHED}
    for w in CACHED:
        entry = loaded.lookup(w)
        assert entry is not None and entry.arch == w.arch
        assert frontiers_identical(entry.frontier(),
                                   result.frontiers[(w.arch, w.shape)])
    assert loaded.lookup(NOVEL) is None    # perturbed census: not a hit
    near, dist = loaded.nearest(CACHED[0])
    assert near.arch == CACHED[0].arch and dist == 0.0
    near, dist = loaded.nearest(NOVEL)
    assert near.arch == NOVEL.arch and dist > 0.0


def test_index_version_and_completeness_gates(tmp_path, offline):
    _, _, index = offline
    path = index.save(str(tmp_path / "index.json"))
    with open(path) as f:
        payload = json.load(f)
    stale = dict(payload, sim_model_version=costmodel.SIM_MODEL_VERSION - 1)
    stale_path = tmp_path / "stale.json"
    stale_path.write_text(json.dumps(stale))
    with pytest.raises(ValueError, match="cost-model version"):
        FrontierIndex.load(str(stale_path))
    bad = dict(payload, index_schema_version=INDEX_SCHEMA_VERSION + 1)
    stale_path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="schema version"):
        FrontierIndex.load(str(stale_path))
    partial = Campaign(CACHED, serving_config())
    partial.run(max_tiles=1)
    with pytest.raises(ValueError, match="incomplete"):
        FrontierIndex.from_campaign(partial)


def test_index_from_checkpoint_inherits_version_gate(tmp_path, offline):
    camp, result, _ = offline
    ckpt = str(tmp_path / "ckpt.json")
    store.save_checkpoint(camp.state_dict(), ckpt)
    index = FrontierIndex.from_checkpoint(ckpt)
    for w in CACHED:
        assert frontiers_identical(index.lookup(w).frontier(),
                                   result.frontiers[(w.arch, w.shape)])
    state = camp.state_dict()
    state["sim_model_version"] = costmodel.SIM_MODEL_VERSION - 1
    (tmp_path / "old.json").write_text(json.dumps(state))
    with pytest.raises(ValueError, match="rebuild any FrontierIndex"):
        FrontierIndex.from_checkpoint(str(tmp_path / "old.json"))


def test_family_key_is_wl_cols_order():
    key = family_key(CACHED[0])
    np.testing.assert_array_equal(
        key, [BASE["flops"], BASE["hbm_bytes"], BASE["collective_bytes"],
              BASE["wire_bytes"], 256, 0.5])


# --- SelectionEngine: the three provenances -----------------------------------


def test_index_hit_identity_on_all_cached_cells(tmp_path, offline):
    """The acceptance gate: served answers == offline campaign picks, exact
    candidate identity, for every cached workload cell — through a full
    index save/load round trip."""
    camp, result, index = offline
    loaded = FrontierIndex.load(index.save(str(tmp_path / "index.json")))
    engine = SelectionEngine(loaded)
    for w in CACHED:
        answer = engine.select(w)
        assert answer.provenance == "index_exact"
        assert frontiers_identical(answer.frontier(),
                                   result.frontiers[(w.arch, w.shape)])
        best = answer.choices[0]
        assert best.exact and best.energy_j == float(
            min(result.frontiers[(w.arch, w.shape)].energy_j))
    assert engine.fused_launches == 0      # no sweep ran
    assert engine.stats["index_exact"] == len(CACHED)


def engine_with(index, **kw):
    """A ``SelectionEngine`` over ``index`` with config fields overridden
    (``evaluator="pallas"`` runs the kernel in interpret mode here)."""
    return SelectionEngine(
        index, SelectionEngine._config_from_index(index).replace(**kw))


def stage_counts(engine):
    """(misses, hits) of the engine's whole-space staging."""
    tel = engine.telemetry
    return (tel.counter("selection_stage_misses_total").value,
            tel.counter("selection_stage_hits_total").value)


@pytest.mark.parametrize("evaluator", ["jit", "pallas"])
def test_novel_workload_fallback_parity(offline, evaluator):
    """A novel family's mini-campaign answer equals a standalone campaign
    on the same slice (here: the full serving space, swept independently
    through the tile loop).  Only the Pallas evaluator stages the space."""
    _, _, index = offline
    engine = engine_with(index, evaluator=evaluator)
    answer = engine.select(NOVEL)
    assert answer.provenance == "mini_campaign"
    assert answer.verified_gidx.size == len(engine.space)
    standalone = Campaign([NOVEL], engine.config).run()
    assert frontiers_identical(answer.frontier(),
                               standalone.frontiers[(NOVEL.arch, NOVEL.shape)])
    assert stage_counts(engine) == ((1, 0) if evaluator == "pallas"
                                    else (0, 0))


def test_whole_space_queries_reuse_one_staged_matrix(offline):
    """Repeated whole-space queries on one Pallas engine stage the space
    once (one miss, then a hit per query) and answer bitwise as a fresh
    engine's first query and as ``Campaign.run`` do."""
    _, _, index = offline
    queries = [NOVEL, wl("stablelm_1_6b", scale=0.41, chips=64, gb=0.2),
               wl("mamba2_130m", scale=0.06, chips=16, gb=0.05), NOVEL]
    engine = engine_with(index, evaluator="pallas")
    for n, q in enumerate(queries, start=1):
        answer = engine.select(q)
        assert answer.verified_gidx.size == len(engine.space)
        assert stage_counts(engine) == (1, n - 1)
        fresh = engine_with(index, evaluator="pallas").select(q)
        assert frontiers_identical(answer.frontier(), fresh.frontier())
        standalone = Campaign([q], engine.config).run()
        assert frontiers_identical(answer.frontier(),
                                   standalone.frontiers[(q.arch, q.shape)])


def test_group_of_eight_stages_its_own_lane_count(offline, monkeypatch):
    """A flush of eight novel queries launches on another padded lane count
    than one query does (blocks of 256 lanes for eight rows, 2,048 for one,
    over 280 candidates: 512 lanes against 384), so it stages its own
    matrix, and its answers equal eight sequential ones."""
    from repro.kernels import dse_sweep
    monkeypatch.setattr(dse_sweep, "BLOCK_ELEMS", 2048)
    dse_sweep._jit_dse_sweep.cache_clear()
    _, _, index = offline
    engine = engine_with(index, evaluator="pallas",
                         space=small_spec(freq_points=20))
    n = len(engine.space)
    assert (dse_sweep.padded_lanes(n, 8), dse_sweep.padded_lanes(n, 1)) == (
        512, 384)
    novel = [wl(scale=1.01 + 0.02 * i) for i in range(8)]
    try:
        for w in novel:
            engine.submit(w)
        batched = engine.flush()
        assert engine.fused_launches == 1
        assert stage_counts(engine) == (1, 0)
        for i, w in enumerate(novel):
            solo = engine.select(w)
            assert frontiers_identical(batched[i].frontier(), solo.frontier())
            assert stage_counts(engine) == (2, i)
    finally:
        dse_sweep._jit_dse_sweep.cache_clear()


def test_constraint_override_forces_exact_path(offline):
    """A known family under a non-index constraint cannot be served from
    the index — the engine re-evaluates under the queried constraint."""
    _, _, index = offline
    engine = SelectionEngine(index)
    tight = dse.Constraint(max_power_w=20_000)
    answer = engine.select(CACHED[0], constraint=tight)
    assert answer.provenance == "mini_campaign"
    standalone = Campaign(
        [CACHED[0]], engine.config.replace(constraint=tight)).run()
    assert frontiers_identical(
        answer.frontier(),
        standalone.frontiers[(CACHED[0].arch, CACHED[0].shape)])


def test_deadline_exceeded_degrades_to_predictor_only(offline):
    _, _, index = offline
    cfg = SelectionEngine._config_from_index(index).replace(
        power_model=StubModel(40.0), cycles_model=StubModel(1e9))
    engine = SelectionEngine(index, cfg)
    answer = engine.select(NOVEL, deadline_s=0.0)
    assert answer.provenance == "predictor_only"
    assert answer.choices and all(not c.exact for c in answer.choices)
    assert engine.fused_launches == 0
    # same query, no deadline: the exact path answers
    assert engine.select(NOVEL).provenance == "mini_campaign"
    # without predictors a deadline cannot degrade — exact is the only path
    bare = SelectionEngine(index)
    assert bare.select(NOVEL, deadline_s=0.0).provenance == "mini_campaign"
    assert set(engine.stats) >= set(PROVENANCES)


@pytest.mark.parametrize("evaluator", ["jit", "pallas"])
def test_predictor_pruned_slice_is_verified_exactly(offline, evaluator):
    """With predictors, the fallback verifies a pruned slice; the served
    frontier equals a direct exact evaluation of that same slice.  A slice
    changes per query, so it is never staged: no hit, no miss."""
    _, _, index = offline
    cfg = SelectionEngine._config_from_index(index).replace(
        evaluator=evaluator, power_model=StubModel(40.0),
        cycles_model=StubModel(1e9))
    engine = SelectionEngine(index, cfg, verify_top=16)
    answer = engine.select(NOVEL)
    assert answer.provenance == "mini_campaign"
    gidx = answer.verified_gidx
    assert 0 < gidx.size < len(engine.space)
    assert stage_counts(engine) == (0, 0)
    ev = TileEvaluator([NOVEL], engine.config)
    batch = dse.CandidateBatch.from_candidates(
        engine.space.candidates_at(gidx))
    tr = ev.reduce_tile(batch, 0)
    fr = StreamingFrontier()
    loc = tr.surv_gidx[0]
    fr.merge_reduced(engine.space.candidates_at(gidx[loc]),
                     tr.surv_energy[0], tr.surv_latency[0], loc,
                     span=(0, int(gidx.size)), n_feasible=tr.n_feasible[0],
                     ref_energy_j=tr.ref_energy_j[0],
                     ref_latency_s=tr.ref_latency_s[0])
    direct = fr.as_pareto_frontier(NOVEL)
    direct = dse.ParetoFrontier(
        workload=NOVEL, candidates=direct.candidates,
        energy_j=direct.energy_j, latency_s=direct.latency_s,
        indices=gidx[direct.indices], feasible_count=direct.feasible_count)
    assert frontiers_identical(answer.frontier(), direct)


def test_batched_queries_one_launch_and_equal_to_sequential(offline):
    """All novel queries of one flush ride ONE fused sweep launch
    (measured via ``fused_launches``, not assumed), and batched answers are
    bitwise identical to sequential single-query answers."""
    _, _, index = offline
    novel = [wl(scale=1.07), wl("stablelm_1_6b", scale=0.41, chips=64,
                                gb=0.2), wl("mamba2_130m", scale=0.06,
                                            chips=16, gb=0.05)]
    batched = SelectionEngine(index)
    for w in novel:
        batched.submit(w)
    batched.submit(CACHED[0])              # index hit rides along for free
    before = batched.fused_launches
    answers = batched.flush()
    assert batched.fused_launches - before == 1
    assert [a.provenance for a in answers] == ["mini_campaign"] * 3 + [
        "index_exact"]
    sequential = SelectionEngine(index)
    for w, got in zip(novel, answers):
        solo = sequential.select(w)
        assert frontiers_identical(got.frontier(), solo.frontier())
    assert sequential.fused_launches == 3  # one launch per lone query


def test_novel_query_spans_nest_under_its_request(offline):
    """A novel query's exact path records ``predict`` (with predictors),
    the launch, and a ``merge`` with its own stages under the request's
    ``mini_campaign``; its wait in the queue is a root ``queue_wait``; and
    the first call on a new jitted sweep records JAX's ``lower``."""
    _, _, index = offline
    cfg = SelectionEngine._config_from_index(index).replace(
        power_model=StubModel(40.0), cycles_model=StubModel(1e9))
    tel = Telemetry()
    engine = SelectionEngine(index, cfg, verify_top=16, telemetry=tel)
    # a constraint no other test sweeps under: its sweep compiles here
    answer = engine.select(NOVEL, constraint=dse.Constraint(
        max_power_w=51_234.0))
    assert answer.provenance == "mini_campaign"
    records = tel.tracer.records
    by_sid = {r.sid: r for r in records}
    (mini,) = [r for r in records if r.name == "mini_campaign"]
    assert mini.attrs["qids"] == [answer.qid]
    under = {r.name for r in records if r.parent == mini.sid}
    assert {"predict", "pad", "launch", "compact", "merge"} <= under
    (merge,) = [r for r in records if r.name == "merge"]
    assert {r.name for r in records if r.parent == merge.sid} == {
        "materialize", "fold", "snapshot"}
    (wait,) = [r for r in records if r.name == "queue_wait"]
    assert wait.parent == -1 and wait.attrs == {"qid": answer.qid}
    assert wait.t1 <= mini.t0
    lowered = [r for r in records if r.name == "lower"]
    assert lowered and all(r.parent not in by_sid for r in lowered)


def test_selection_latency_is_submit_to_answer():
    """``selection_latency_s`` observes each query's time from ``submit``
    to the ``flush`` that answers it, not a share of the flush."""
    camp = Campaign(CACHED, serving_config())
    camp.run()
    clock = FakeClock(100.0)
    tel = Telemetry(clock=clock)
    engine = SelectionEngine(FrontierIndex.from_campaign(camp),
                             telemetry=tel)
    engine.submit(CACHED[0])
    clock.advance(2.0)
    engine.submit(CACHED[1])
    clock.advance(1.0)
    engine.flush()
    hist = tel.histogram("selection_latency_s", path="index_exact")
    assert hist.samples == [3.0, 1.0]
    waits = [r for r in tel.tracer.records if r.name == "queue_wait"]
    assert [r.dur for r in waits] == [3.0, 1.0]


# --- the one-CampaignConfig API contract --------------------------------------


def test_all_entry_points_construct_from_one_config(tmp_path, offline):
    """Campaign, TileEvaluator, run_distributed and SelectionEngine all
    take the same frozen CampaignConfig."""
    _, _, index = offline
    cfg = serving_config(
        space=small_spec(chip_counts=(16,), freq_points=3, chunk_size=32),
        n_workers=1, checkpoint_path=str(tmp_path / "fab.json"))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        camp = Campaign(CACHED, cfg)
        ev = TileEvaluator(CACHED, cfg)
        eng = SelectionEngine(index, cfg)
        dist, stats = run_distributed(CACHED, cfg)
    assert camp.config is ev.config is eng.config is cfg
    assert dist.complete and stats["deliveries"] >= 1
    single = camp.run()
    for key in single.frontiers:
        assert frontiers_identical(single.frontiers[key],
                                   dist.frontiers[key])


def test_legacy_keyword_construction_warns_but_works():
    spec = small_spec(chip_counts=(16,), freq_points=3, chunk_size=32)
    cons = dse.Constraint(max_power_w=50_000)
    with pytest.warns(DeprecationWarning, match="CampaignConfig"):
        camp = Campaign(CACHED[:1], spec, evaluator="jit", constraint=cons)
    with pytest.warns(DeprecationWarning, match="CampaignConfig"):
        ev = TileEvaluator(CACHED[:1], spec, evaluator="jit", constraint=cons)
    assert camp.space == ev.space == spec
    assert camp.evaluator == ev.evaluator == "jit"
    legacy = camp.run()
    fresh = Campaign(
        CACHED[:1], CampaignConfig(space=spec, evaluator="jit",
                                   constraint=cons)).run()
    for key in fresh.frontiers:
        assert frontiers_identical(legacy.frontiers[key],
                                   fresh.frontiers[key])
    with pytest.warns(DeprecationWarning, match="CampaignConfig"):
        _, _ = run_distributed(
            Campaign(CACHED[:1],
                     CampaignConfig(space=spec, evaluator="jit",
                                    constraint=cons)), n_workers=1)
    with pytest.raises(TypeError):        # config AND legacy kwargs: refused
        Campaign(CACHED[:1], CampaignConfig(space=spec), evaluator="jit")
    with pytest.raises(TypeError):        # unknown kwarg: refused
        Campaign(CACHED[:1], spec, evaluatr="jit")


def test_config_chunk_size_override_and_validation():
    spec = small_spec(chunk_size=64)
    cfg = CampaignConfig(space=spec, chunk_size=32)
    assert cfg.resolved_space.chunk_size == 32
    assert cfg.resolved_space == dataclasses.replace(spec, chunk_size=32)
    assert CampaignConfig(space=spec).resolved_space is spec
    with pytest.raises(ValueError, match="evaluator"):
        CampaignConfig(space=spec, evaluator="warp")
    with pytest.raises(ValueError, match="power_model"):
        CampaignConfig(space=spec, evaluator="fast")
    with pytest.raises(TypeError, match="SpaceSpec"):
        CampaignConfig(space="not-a-space")


# --- launch CLI + store durability --------------------------------------------


def test_serve_cli_build_index_and_select(tmp_path, offline, capsys):
    from repro.launch.serve import build_index, select_queries
    from repro.dse_campaign.runner import workload_to_dict

    camp, result, _ = offline
    ckpt = str(tmp_path / "ckpt.json")
    store.save_checkpoint(camp.state_dict(), ckpt)
    idx_path = build_index(ckpt, str(tmp_path / "index.json"))
    answers, engine = select_queries(idx_path)   # self-check: all families
    assert [a.provenance for a in answers] == ["index_exact"] * len(CACHED)
    assert engine.fused_launches == 0
    queries = [{"workload": workload_to_dict(CACHED[0])},
               {"workload": workload_to_dict(NOVEL), "deadline_s": 60.0}]
    qpath = tmp_path / "queries.json"
    qpath.write_text(json.dumps(queries))
    answers, engine = select_queries(idx_path, str(qpath))
    assert [a.provenance for a in answers] == ["index_exact",
                                               "mini_campaign"]
    assert engine.fused_launches == 1
    assert "fused launches" in capsys.readouterr().out


def test_atomic_write_json_durable_path(tmp_path):
    """The checkpoint writer leaves no temp file behind and the renamed
    file is complete, well-formed JSON (fsync-before-rename path)."""
    path = str(tmp_path / "nested" / "out.json")
    store.atomic_write_json({"a": [1, 2, 3]}, path)
    assert json.load(open(path)) == {"a": [1, 2, 3]}
    assert not (tmp_path / "nested" / "out.json.tmp").exists()
