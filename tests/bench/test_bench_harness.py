"""The benchmark harness on the CPU: names resolve, the contract's shape,
the refusal of a non-TPU device, the result line, the control and the
planted faults.  Runs use the test-sized copies of the configurations
(``data/tiny-*.json``) and a synthetic census (``data/census.json``)."""

import copy
import dataclasses
import json
import os
import pathlib
import re
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import census as census_mod  # noqa: E402
from bench import check, harness, traffic  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELLS = ("campaign-default", "select-novel")
TINY = {"camp-default125k-5wl": "tests/bench/data/tiny-camp.json",
        "select-idx5-exact": "tests/bench/data/tiny-select.json"}
SEED = 2 ** 31 + 11
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def tiny_spec():
    spec = copy.deepcopy(harness.load_spec())
    for c in spec["configs"]:
        c["file"] = TINY[c["name"]]
    return spec


def fake_census(cells):
    recs = {(r["arch"], r["shape"]): r
            for r in json.loads((DATA / "census.json").read_text())}
    return [dict(recs[tuple(c)]) for c in cells]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


@pytest.fixture
def cpu_run(cache_dir, monkeypatch):
    """A run of a tiny cell on the CPU, with the checkout cache in a
    temporary directory and the persistent compile cache left alone."""
    monkeypatch.setattr(census_mod, "CACHE", str(cache_dir))
    monkeypatch.setattr(harness, "enable_compile_cache_for_bench",
                        lambda: "off")

    def run(cell, seconds=0.5, seed=SEED):
        return harness.run_cell(cell, seed, seconds, False,
                                t_start=time.perf_counter(),
                                spec=tiny_spec(), require_tpu=False,
                                census=fake_census)
    return run


def tiny_cell(name):
    spec = tiny_spec()
    entry = harness.find(spec["workloads"], name, "workload")
    cfg = json.loads((REPO / TINY[entry["config"]]).read_text())
    driver = harness.load_module("drivers", cfg["driver"])
    return cfg, driver.setup(cfg, traffic.load(entry["traffic"]), SEED,
                             traced=False, census=fake_census)


# -- names and the contract ---------------------------------------------------


def test_every_name_in_the_benchmark_resolves_to_its_files():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        conf = harness.find(spec["configs"], w["config"], "config")
        cfg = json.loads((REPO / conf["file"]).read_text())
        assert cfg["name"] == conf["name"]
        assert traffic.load(w["traffic"])["kind"] in ("campaign", "selection")
        assert hasattr(harness.load_module("drivers", cfg["driver"]), "setup")
        assert cfg["limits"] and set(cfg["limits"]) <= set(check.NUMBERS)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_unknown_names_are_refused():
    spec = harness.load_spec()
    with pytest.raises(SystemExit):
        harness.find(spec["workloads"], "no-such-cell", "workload")
    with pytest.raises(SystemExit):
        harness.load_module("metrics", "no_such_metric")


def test_benchmark_json_keeps_the_contract():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in spec["paths"]:
        assert (REPO / p).is_dir()
    assert 1 <= spec["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert all(w in [c["name"] for c in spec["workloads"]]
                   for w in m.get("workloads", []))
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        e = [m["name"] for m in harness.cell_metrics(spec, w["name"], False)]
        assert "setup_s" in e and len(e) >= 2
        assert harness.cell_metrics(spec, w["name"], True)


def test_metrics_of_a_cell_follow_their_workloads_key():
    spec = harness.load_spec()
    names = lambda cell, t: sorted(m["name"] for m in
                                   harness.cell_metrics(spec, cell, t))
    assert names("campaign-default", False) == ["campaign_cand_per_s",
                                                "setup_s"]
    assert names("select-novel", False) == ["query_p50_ms", "query_p95_ms",
                                            "setup_s"]
    assert "sweep_hbm_roofline" in names("campaign-default", True)
    assert "sweep_hbm_roofline" not in names("select-novel", True)


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["source"]
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99")


# -- refusal of a device that is not a TPU ------------------------------------


def test_a_cpu_device_is_refused():
    with pytest.raises(harness.NoDevice) as e:
        harness.device_info(1)
    assert e.value.code != 0


def test_run_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "campaign-default",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_allocator_settings_are_accepted_by_glibc():
    """``bench.allocator.fix`` raises where glibc refuses a setting, so a
    run never measures with the allocator left to its defaults."""
    p = subprocess.run(
        [sys.executable, "-c", "from bench import allocator; allocator.fix()"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr


# -- a whole run, its result line, the control and the faults -----------------


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_the_contract_keys(cell, cpu_run):
    r = cpu_run(cell)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = harness.load_spec()
    assert sorted(r["metrics"]) == sorted(
        m["name"] for m in harness.cell_metrics(spec, cell, False))
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    spec = tiny_spec()
    conf = harness.find(spec["configs"], harness.find(
        spec["workloads"], cell, "workload")["config"], "config")
    limits = json.loads((REPO / conf["file"]).read_text())["limits"]
    assert list(r["checks"]) == list(limits)
    json.loads(json.dumps(r))


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell, cache_dir,
                                                      monkeypatch):
    monkeypatch.setattr(census_mod, "CACHE", str(cache_dir))
    cfg, c = tiny_cell(cell)
    c.window(time.perf_counter(), 0.5)
    assert check.verdict(c.check("program"), cfg["limits"])
    assert not check.verdict(c.check("control"), cfg["limits"])


def _altered_answer(monkeypatch):
    """Every fused sweep's survivors come back 1% off in energy."""
    from repro.core import costmodel
    real = costmodel.build_sweep_reduced

    def altered(out, max_survivors):
        red = real(out, max_survivors)
        return dataclasses.replace(red, surv_energy=red.surv_energy * 1.01)
    monkeypatch.setattr(costmodel, "build_sweep_reduced", altered)


def _half_batch(monkeypatch):
    """Every tile's odd lanes are marked invalid: half of each batch is
    left out of the sweep."""
    from repro.dse_campaign.runner import TileEvaluator
    real = TileEvaluator.padded_tile_arrays

    def half(self, batch):
        arrays = real(self, batch)
        arrays["valid"] = arrays["valid"].copy()
        arrays["valid"][1::2] = 0.0
        return arrays
    monkeypatch.setattr(TileEvaluator, "padded_tile_arrays", half)


def _stale_answer(monkeypatch):
    """From the window's start an answer made for another question is
    served: every campaign returns the warm-up campaign's frontiers, and
    the index takes a family within 20% of a cached one for a hit (so a
    scaled census of an indexed family gets the family's own frontier)."""
    from repro.dse_campaign import Campaign
    from repro.serving.frontier_index import FrontierIndex
    real_run, real_lookup = Campaign.run, FrontierIndex.lookup
    first = []

    def run(self, *a, **kw):
        if not first:
            first.append(real_run(self, *a, **kw))
        return first[0]

    def lookup(self, wl, match_rtol=1e-9):
        return real_lookup(self, wl, 0.2)

    real_load = harness.load_module

    def load_module(kind, name):
        mod = real_load(kind, name)
        if kind == "drivers":
            real_window = mod.Cell.window

            def window(self, *a, **kw):
                monkeypatch.setattr(Campaign, "run", run)
                monkeypatch.setattr(FrontierIndex, "lookup", lookup)
                return real_window(self, *a, **kw)
            mod.Cell.window = window
        return mod
    monkeypatch.setattr(harness, "load_module", load_module)


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch,
                                   _stale_answer],
                         ids=["altered_answer", "half_batch", "stale_answer"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_makes_the_run_incorrect(cell, fault, cpu_run,
                                                 monkeypatch):
    fault(monkeypatch)
    r = cpu_run(cell)
    assert r["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in r["checks"].values())


def test_an_answer_swept_over_part_of_the_space_is_not_correct(cpu_run,
                                                              monkeypatch):
    """The exact path sweeps a random half of the space and says so in
    ``verified_gidx``: every answer is exact on what it swept, and the
    comparison over the whole space has to refuse it."""
    import numpy as np
    from repro.serving.engine import SelectionEngine
    rng = np.random.default_rng(SEED)

    def half(self, workloads, constraint):
        n = len(self.space)
        return np.sort(rng.choice(n, size=n // 2, replace=False))
    monkeypatch.setattr(SelectionEngine, "_candidate_slice", half)
    r = cpu_run("select-novel")
    assert r["correct"] is False
    assert r["checks"]["missed_rel"]["value"] is None or (
        r["checks"]["missed_rel"]["value"]
        > r["checks"]["missed_rel"]["limit"])
