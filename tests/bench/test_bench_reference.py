"""The plain reference against the program's float64 numpy evaluator at a
test size, and the comparison numbers on known differences."""

import json
import pathlib
import sys

import ml_dtypes
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, reference  # noqa: E402
from bench.drivers.campaign import (program_config, reference_question,  # noqa: E402
                                    workload)

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((DATA / "tiny-camp.json").read_text())
    census = json.loads((DATA / "census.json").read_text())
    space = reference.Space(cfg)
    return cfg, census, space, space.arrays()


def test_space_enumerates_the_programs_space(tiny):
    cfg, _, space, cols = tiny
    prog = program_config(cfg).resolved_space
    assert len(space) == len(prog) == 800
    batch = prog.slice(0, len(prog), with_candidates=False)
    for k, v in (("n_chips", batch.n_chips), ("freq_mhz", batch.freq_mhz),
                 ("mesh_pod", batch.pod_axis()),
                 ("mesh_data", batch.mesh_data),
                 ("mesh_model", batch.mesh_model),
                 ("peak_flops_bf16", batch.chip_cols["peak_flops_bf16"])):
        np.testing.assert_array_equal(cols[k], v)
    idx = np.asarray([3, 250, 799])
    sub = space.arrays(idx)
    for k in cols:
        np.testing.assert_array_equal(sub[k], cols[k][idx])


def test_reference_equals_the_programs_float64_evaluator(tiny):
    from repro.core import dse
    cfg, census, space, cols = tiny
    prog = program_config(cfg)
    batch = prog.resolved_space.slice(0, len(space), with_candidates=False)
    for rec in census:
        res, feas = dse.evaluate_workload_tile(
            workload(rec), batch, prog.resolved_constraint, sim=prog.sim)
        e, l, f = reference.evaluate(cols, rec, cfg)
        np.testing.assert_allclose(e, res.energy_j, rtol=1e-14)
        np.testing.assert_allclose(l, res.latency_s, rtol=1e-14)
        np.testing.assert_array_equal(f, feas)
        np.testing.assert_array_equal(
            reference.pareto(e, l, f),
            np.flatnonzero(dse.pareto_mask(res.energy_j, res.latency_s,
                                           feas)))


def test_mesh_factorizations_match_the_program():
    from repro.hw import mesh_factorizations
    for n in (4, 8, 16, 64, 256, 1024):
        for dims in (2, 3):
            assert reference.mesh_factorizations(n, dims) == \
                list(mesh_factorizations(n, dims))


def test_comparison_numbers(tiny):
    cfg, census, space, cols = tiny
    ref = reference_question(space, cols, census[0], cfg)
    f = ref["front"]
    served = (ref["index"][f], ref["energy"][f], ref["latency"][f])
    assert check.compare_frontier(*served, ref) == {
        "value_rel_err": 0.0, "missed_rel": 0.0, "spurious_rel": 0.0}
    off = check.compare_frontier(served[0], served[1] * 1.001, served[2],
                                 ref)
    assert off["value_rel_err"] == pytest.approx(1e-3)
    # a frontier point left out reads how far the rest is from covering it
    missing = check.compare_frontier(*(a[1:] for a in served), ref)
    assert missing["missed_rel"] > 0
    # a dominated candidate served as a frontier point
    dominated = np.flatnonzero((ref["energy"] > ref["energy"][f].max())
                               & (ref["latency"] > ref["latency"][f].max())
                               & (ref["excess"] == 0))[:1]
    assert dominated.size
    spur = check.compare_frontier(
        np.concatenate([served[0], dominated]),
        np.concatenate([served[1], ref["energy"][dominated]]),
        np.concatenate([served[2], ref["latency"][dominated]]), ref)
    assert spur["spurious_rel"] > 0 and spur["missed_rel"] == 0.0
    outside = check.compare_frontier(served[0] + len(space), *served[1:],
                                     ref)
    assert all(v == float("inf") for v in outside.values())


def test_bfloat16_control_reads_far_above_float32(tiny):
    cfg, census, space, cols = tiny
    worst = {}
    for dt in (np.float32, ml_dtypes.bfloat16):
        readings = []
        for rec in census:
            ref = reference_question(space, cols, rec, cfg)
            low = reference_question(space, cols, rec, cfg, dtype=dt)
            f = low["front"]
            readings.append(check.compare_frontier(
                low["index"][f], low["energy"][f], low["latency"][f], ref))
        worst[dt] = check.worst(readings)
    assert worst[np.float32]["value_rel_err"] < 1e-6
    assert worst[ml_dtypes.bfloat16]["value_rel_err"] > 1e-3
    assert check.verdict(worst[np.float32], cfg["limits"])
    assert not check.verdict(worst[ml_dtypes.bfloat16], cfg["limits"])
