"""The per-request span readers: a stage's span total per tile or per novel
query, the overflow readers, the windows of a program that records no
such spans, and a traced window of each tiny cell on the CPU."""

import copy
import json
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import census as census_mod  # noqa: E402
from bench import harness, traffic  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY = {"camp-default125k-5wl": "tests/bench/data/tiny-camp.json",
        "select-idx5-exact": "tests/bench/data/tiny-select.json"}
SEED = 2 ** 31 + 23

# reader -> (span it reads, request span it divides by)
STAGE_READERS = {
    **{f"{s}_ms_per_tile": (s, "tile_eval") for s in (
        "pack", "dispatch", "device_wait", "fetch", "host_compact",
        "materialize", "fold", "snapshot", "tile_wait")},
    **{f"{s}_ms_per_query": (s, "mini_campaign") for s in (
        "pack", "device_wait", "fetch", "merge")},
}
OVERFLOW_READERS = {"overflow_ms_per_tile": "tile_eval",
                    "overflow_ms_per_query": "mini_campaign"}


def reader(name):
    return harness.load_module("metrics", name).read


def window(request, n_req, **spans):
    """A window's ``obs`` with ``n_req`` request spans of 20 ms and the
    given ``name=(total_s, count)`` spans."""
    return {"spans": {request: [0.02 * n_req, n_req], **spans}}


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_is_span_total_per_request(name):
    span, request = STAGE_READERS[name]
    obs = window(request, 8, **{span: [0.012, 24]})
    assert reader(name)(obs) == pytest.approx(0.012 / 8 * 1e3)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_is_none_without_its_spans(name):
    span, request = STAGE_READERS[name]
    read = reader(name)
    assert read(window(request, 8)) is None            # no such stage
    assert read({"spans": {span: [0.012, 24]}}) is None  # no request
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(OVERFLOW_READERS))
def test_overflow_time_per_request(name):
    request = OVERFLOW_READERS[name]
    read = reader(name)
    stage = {"device_wait": [0.001, 8]}
    assert read(window(request, 8, **stage,
                       overflow_reduce=[0.004, 2])) == pytest.approx(0.5)
    assert read(window(request, 8, **stage)) == 0.0
    # a program without the launch's stage spans reports nothing
    assert read(window(request, 8)) is None
    assert read({"spans": stage}) is None


def test_overflow_share_of_a_campaign_window():
    read = reader("overflow_share.campaign")
    sweep = {"candidates": 125_440 * 3,
             "candidate_workloads": 125_440 * 3 * 5}
    obs = {**window("tile_eval", 12, device_wait=[0.01, 12],
                    overflow_reduce=[0.03, 6]), "sweep": sweep}
    assert read(obs) == pytest.approx(6 / (12 * 5) * 100)
    no_overflow = {**window("tile_eval", 12, device_wait=[0.01, 12]),
                   "sweep": sweep}
    assert read(no_overflow) == 0.0
    assert read({**window("tile_eval", 12), "sweep": sweep}) is None
    assert read({k: v for k, v in obs.items() if k != "sweep"}) is None


def test_overflow_share_of_novel_queries():
    read = reader("overflow_share.novel")
    stage = {"device_wait": [0.01, 40]}
    assert read(window("mini_campaign", 40, **stage,
                       overflow_reduce=[0.2, 10])) == pytest.approx(25.0)
    assert read(window("mini_campaign", 40, **stage)) == 0.0
    assert read(window("mini_campaign", 40)) is None


def test_every_new_reader_has_an_entry_that_names_its_cell():
    spec = harness.load_spec()
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    names = (list(STAGE_READERS) + list(OVERFLOW_READERS)
             + ["overflow_share.campaign", "overflow_share.novel"])
    for name in names:
        m = per_layer[name]
        cell = ("campaign-default" if name.endswith(("_tile", ".campaign"))
                else "select-novel")
        assert m["workloads"] == [cell]
        assert m["source"] == "program_span" and m["better"] == "lower"


def _fake_census(cells):
    recs = {(r["arch"], r["shape"]): r
            for r in json.loads((DATA / "census.json").read_text())}
    return [dict(recs[tuple(c)]) for c in cells]


@pytest.mark.parametrize("cell", ["campaign-default", "select-novel"])
def test_a_traced_cpu_window_reports_every_span_metric(cell, tmp_path,
                                                       monkeypatch):
    """The drivers' traced windows collect the program's spans; every
    ``program_span`` metric of the cell reads a number from them."""
    monkeypatch.setattr(census_mod, "CACHE", str(tmp_path))
    spec = copy.deepcopy(harness.load_spec())
    entry = harness.find(spec["workloads"], cell, "workload")
    conf = harness.find(spec["configs"], entry["config"], "config")
    cfg = json.loads((REPO / TINY[conf["name"]]).read_text())
    driver = harness.load_module("drivers", cfg["driver"])
    state = driver.setup(cfg, traffic.load(entry["traffic"]), SEED,
                         traced=True, census=_fake_census)
    obs = state.window(time.perf_counter(), 0.5)
    metrics = [m for m in harness.cell_metrics(spec, cell, True)
               if m["source"] == "program_span"]
    values = {m["name"]: reader(m["name"])(obs) for m in metrics}
    assert all(v is not None and v >= 0 for v in values.values()), values
    if cell == "campaign-default":
        # one launch per tile: the launch's stages fit inside it
        stages = sum(values[f"{s}_ms_per_tile"] for s in (
            "pack", "dispatch", "device_wait", "fetch", "host_compact"))
        assert stages <= values["launch_ms_per_tile"]
