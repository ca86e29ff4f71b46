"""The campaign-ds3 cell on the CPU: the split reference against the
program's float64 evaluator, the census reader, the refusal of a program or
census without a per-axis split, a whole run with its checks and control,
and the split-share counter.  Runs use the test-sized copy of the
configuration (``data/tiny-camp-ds3.json``) and the DeepSeek-V3 census
records of ``data/census-ds3.json``."""

import copy
import json
import pathlib
import sys
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import census as census_mod  # noqa: E402
from bench import check, harness, reference, reference_split, traffic  # noqa: E402
from bench.drivers import campaign, campaign_split  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "campaign-ds3"
TINY = "tests/bench/data/tiny-camp-ds3.json"
SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((DATA / "tiny-camp-ds3.json").read_text())
    records = json.loads((DATA / "census-ds3.json").read_text())
    space = reference_split.Space(cfg)
    return cfg, records, space, space.arrays()


def fake_census(cells):
    recs = {(r["arch"], r["shape"]): r
            for r in json.loads((DATA / "census-ds3.json").read_text())}
    return [copy.deepcopy(recs[tuple(c)]) for c in cells]


def tiny_spec():
    spec = copy.deepcopy(harness.load_spec())
    for c in spec["configs"]:
        if c["name"] == "camp-ds3-ep16-3wl":
            c["file"] = TINY
    return spec


def setup_cell(cfg, traced=False, census=fake_census):
    return campaign_split.setup(cfg, traffic.load("closed-campaigns"), SEED,
                                traced=traced, census=census)


def test_the_tiny_copy_differs_from_the_cell_only_in_its_space(tiny):
    cfg = tiny[0]
    full = json.loads((REPO / "bench/configs/camp-ds3-ep16-3wl.json")
                      .read_text())
    assert {k for k in full if full[k] != cfg[k]} == {"name", "deployment",
                                                      "space"}
    assert cfg["constraint"]["min_hbm_fit"] is True
    assert cfg["driver"] == "campaign_split"


def test_census_records_carry_the_split_of_the_artifact(tiny):
    for rec in tiny[1]:
        split = rec["wire_bytes_by_axis"]
        assert split["data"] + split["model"] == rec["wire_bytes"]
        assert rec["coll_model_share"] == split["model"] / (
            split["data"] + split["model"])
    art = {"hxa": {k: tiny[1][0][k] for k in
                   ("flops", "hbm_bytes", "collective_bytes", "wire_bytes")},
           "roofline": {"n_chips": 256},
           "memory": {"state_gb_per_device": 10.5}}
    with pytest.raises(KeyError, match="wire_bytes_by_axis"):
        reference_split.census_record("deepseek_v3_671b", "train_4k", art)
    art["hxa"]["wire_bytes_by_axis"] = {"data": 3.0, "model": 1.0,
                                        "both_axes": 0.5}
    rec = reference_split.census_record("deepseek_v3_671b", "train_4k", art)
    assert rec["coll_model_share"] == 0.25
    assert rec["wire_bytes_by_axis"]["both_axes"] == 0.5


def test_split_reference_equals_the_programs_float64_evaluator(tiny):
    from repro.core import dse
    cfg, records, space, cols = tiny
    prog = campaign.program_config(cfg)
    batch = prog.resolved_space.slice(0, len(space), with_candidates=False)
    for rec in records:
        wl = campaign_split.with_share(campaign.workload(rec), rec)
        res, feas = dse.evaluate_workload_tile(
            wl, batch, prog.resolved_constraint, sim=prog.sim)
        e, l, f = reference_split.evaluate(cols, rec, cfg)
        np.testing.assert_allclose(e, res.energy_j, rtol=1e-14)
        np.testing.assert_allclose(l, res.latency_s, rtol=1e-14)
        np.testing.assert_array_equal(f, feas)
        assert 0 < f.sum() < f.size      # the HBM-fit branch cuts the space
        np.testing.assert_array_equal(
            reference_split.pareto(e, l, f),
            np.flatnonzero(dse.pareto_mask(res.energy_j, res.latency_s,
                                           feas)))


def test_the_share_moves_the_answer(tiny):
    """Priced with the configuration's 50/50 fraction, a DeepSeek-V3
    census gets other latencies than with its own split."""
    cfg, records, _, cols = tiny
    for rec in records:
        own = reference_split.evaluate(cols, rec, cfg)[1]
        flat = reference.evaluate(cols, rec, cfg)[1]
        assert not np.array_equal(own, flat)


def test_a_program_without_the_split_fails_at_set_up(tiny, monkeypatch):
    from repro.core import costmodel
    cfg = tiny[0]
    monkeypatch.setattr(costmodel, "WL_COLS", costmodel.WL_COLS[:6])
    lowered = []

    def census(cells):
        lowered.append(cells)
        return fake_census(cells)
    t = time.perf_counter()
    with pytest.raises(RuntimeError, match="coll_model_share"):
        setup_cell(cfg, census=census)
    assert not lowered and time.perf_counter() - t < 5.0


def test_a_census_without_the_split_fails_at_set_up(tiny, tmp_path,
                                                    monkeypatch):
    """Records without a share are read again from the cached artifacts,
    and an artifact without ``wire_bytes_by_axis`` is refused."""
    cfg = tiny[0]
    monkeypatch.setattr(census_mod, "CACHE", str(tmp_path))
    out = census_mod.census_dir(census_mod.code_hash())
    pathlib.Path(out).mkdir(parents=True)
    for rec in tiny[1]:
        art = {"hxa": {k: rec[k] for k in ("flops", "hbm_bytes",
                                           "collective_bytes", "wire_bytes")},
               "roofline": {"n_chips": rec["base_chips"]},
               "memory": {"state_gb_per_device": rec["state_gb_per_device"]}}
        (pathlib.Path(out) / census_mod.artifact_name(
            rec["arch"], rec["shape"])).write_text(json.dumps(art))

    def shareless(cells):
        return [{k: v for k, v in r.items()
                 if k not in ("coll_model_share", "wire_bytes_by_axis")}
                for r in fake_census(cells)]
    with pytest.raises(KeyError, match="wire_bytes_by_axis"):
        setup_cell(cfg, census=shareless)


def test_a_run_of_the_cell_is_correct_and_reports_its_metrics(monkeypatch,
                                                            tmp_path):
    monkeypatch.setattr(census_mod, "CACHE", str(tmp_path))
    monkeypatch.setattr(harness, "enable_compile_cache_for_bench",
                        lambda: "off")
    r = harness.run_cell(CELL, SEED, 0.5, False, t_start=time.perf_counter(),
                         spec=tiny_spec(), require_tpu=False,
                         census=fake_census)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert sorted(r["metrics"]) == ["campaign_cand_per_s", "setup_s"]
    assert list(r["checks"]) == ["value_rel_err", "missed_rel",
                                 "spurious_rel"]


def test_control_in_the_programs_place_is_not_correct(tiny):
    cfg = tiny[0]
    c = setup_cell(cfg)
    c.window(time.perf_counter(), 0.5)
    assert check.verdict(c.check("program"), cfg["limits"])
    assert not check.verdict(c.check("control"), cfg["limits"])


def test_a_fifty_fifty_answer_is_not_correct(tiny, monkeypatch):
    """Campaigns priced with the configuration's fraction in place of each
    census's share fail the comparison with the split reference."""
    cfg = tiny[0]
    c = setup_cell(cfg)
    monkeypatch.setattr(campaign_split, "with_share", lambda wl, rec: wl)
    c.window(time.perf_counter(), 0.5)
    assert not check.verdict(c.check("program"), cfg["limits"])


def test_traced_window_reads_every_evaluation_as_split(tiny):
    cfg = tiny[0]
    c = setup_cell(cfg, traced=True)
    obs = c.window(time.perf_counter(), 0.3)
    counters = obs["counters"]
    n = len(c.config.resolved_space) * len(c.records)
    assert counters["evaluator_candidates_total"] == n * len(c.done)
    assert counters["evaluator_split_evals_total"] == n * len(c.done)
    share = harness.load_module("metrics", "census_split_share.ds3")
    assert share.read(obs) == 100.0
    assert share.read({"kind": "campaign"}) is None
    assert check.verdict(c.check("program"), cfg["limits"])
