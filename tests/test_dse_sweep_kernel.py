"""Fused DSE-sweep kernel tests: kernel-vs-scalar-oracle parity across chunk
sizes, constraint-mask edge cases, merge_reduced == raw-merge identity
(hypothesis property), campaign frontier identity and resume==fresh under
``evaluator="pallas"``, and the Pallas interpret auto-detection."""

import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised on bare installs
    from _hypothesis_stub import given, settings, st

from repro.core import costmodel, dse
from repro.dse_campaign import (Campaign, CampaignConfig, SliceVariant,
                                SpaceSpec, StreamingFrontier, TileEvaluator,
                                canonical_frontier, frontiers_identical)
from repro.hw import get_chip
from repro.kernels import ops

BASE = {"flops": 3.2e14, "hbm_bytes": 4.5e13, "collective_bytes": 5e11,
        "wire_bytes": 7e11}
WLS = [dse.Workload("qwen3_14b", "train_4k", BASE, 256, 0.5),
       dse.Workload("stablelm_1_6b", "train_4k",
                    {k: v * 0.2 for k, v in BASE.items()}, 256, 0.1)]
CONS = dse.Constraint(max_power_w=40_000, min_hbm_fit=False)


def small_spec(**kw):
    kw.setdefault("chips", ("tpu-v5e", "tpu-edge"))
    kw.setdefault("chip_counts", (16,))
    kw.setdefault("freq_points", 5)
    kw.setdefault("variants", (SliceVariant(), SliceVariant("bin85", 0.85)))
    kw.setdefault("chunk_size", 64)
    return SpaceSpec(**kw)


def sweep_tile(spec, workloads, lo, hi, cons=CONS, **kw):
    """One fused kernel launch over spec[lo:hi) via the campaign's packing."""
    camp = Campaign(workloads, spec, constraint=cons, evaluator="pallas", **kw)
    batch = spec.slice(lo, hi, with_candidates=False)
    return camp._sweep_tile_reduced(batch), batch


def oracle_rows(spec, wl, cons=CONS):
    """Scalar ``costmodel.simulate`` loop — the ground-truth oracle."""
    energy, latency, feasible = [], [], []
    for i in range(len(spec)):
        cand = spec.candidate(i)
        chip = get_chip(cand.chip)
        ana = dse._scale_analysis(wl.base_analysis, wl.base_chips, cand)
        res = costmodel.simulate(ana, chip, cand.n_chips,
                                 freq_mhz=cand.freq_mhz, mesh=cand.mesh)
        ok = True
        if cons.min_hbm_fit:
            state_pd = wl.state_gb_per_device * wl.base_chips / cand.n_chips
            ok &= state_pd * 1e9 <= chip.hbm_bytes * 0.9
        if cons.max_power_w is not None:
            ok &= res.power_w * cand.n_chips <= cons.max_power_w
        if cons.max_latency_s is not None:
            ok &= res.latency_s <= cons.max_latency_s
        energy.append(res.energy_j)
        latency.append(res.latency_s)
        feasible.append(ok)
    return (np.asarray(energy), np.asarray(latency), np.asarray(feasible))


# --- kernel vs scalar oracle --------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 7, 4096])
def test_kernel_matches_scalar_oracle_over_chunks(chunk):
    """The fused Pallas launch reproduces the scalar oracle's energy /
    latency / constraint mask on every tile, for tile sizes {1, 7, 4096}
    (4096 = whole space in one padded launch).  float32 tolerance is the
    contract; interpret mode actually runs float64 (~1 ulp)."""
    spec = small_spec(chunk_size=chunk)
    oracles = [oracle_rows(spec, wl) for wl in WLS]
    n = len(spec)
    for t, lo, _ in spec.tiles(with_candidates=False):
        hi = min(lo + chunk, n)
        red, _ = sweep_tile(spec, WLS, lo, hi)
        for wi, (o_e, o_l, o_f) in enumerate(oracles):
            e, l, f = (np.asarray(r)[wi, :hi - lo] for r in red.full)
            np.testing.assert_allclose(e, o_e[lo:hi], rtol=1e-6)
            np.testing.assert_allclose(l, o_l[lo:hi], rtol=1e-6)
            np.testing.assert_array_equal(f, o_f[lo:hi])
            # the on-device screen keeps a feasible SUPERSET of the tile's
            # exact Pareto set, and its aggregates are the oracle's
            keep_exact, n_feas, ref_e, ref_l = costmodel.skyline_reduce(
                o_e[lo:hi], o_l[lo:hi], o_f[lo:hi])
            k = int(red.n_survivors[wi])
            assert k <= red.max_survivors
            surv = set(red.surv_idx[wi][:k].tolist())
            assert set(np.flatnonzero(keep_exact).tolist()) <= surv
            assert all(o_f[lo:hi][i] for i in surv)
            assert int(red.n_feasible[wi]) == int(n_feas)
            if n_feas:
                np.testing.assert_allclose(
                    [red.ref_energy[wi], red.ref_latency[wi]],
                    [ref_e, ref_l], rtol=1e-6)


@pytest.mark.parametrize("cons", [
    # HBM fit: 2 GB/device at base 256 fits 64-chip v5e (8 GB/dev) but not
    # 16-chip (32 GB/dev) — the hbm branch splits the space
    dse.Constraint(min_hbm_fit=True),
    # latency cap splits the space along the chip-count axis
    dse.Constraint(max_latency_s=500.0, min_hbm_fit=False),
    dse.Constraint(max_power_w=40_000, max_latency_s=500.0,
                   min_hbm_fit=True),
])
def test_kernel_matches_oracle_constraint_branches(cons):
    """The in-kernel constraint mask covers every branch: HBM fit, slice
    power budget, and the latency cap — each actually splitting the space."""
    spec = small_spec(chip_counts=(16, 64))
    wl = dse.Workload("qwen3_14b", "train_4k", BASE, 256, 2.0)
    o_e, o_l, o_f = oracle_rows(spec, wl, cons)
    assert 0 < o_f.sum() < len(spec)            # the mask actually bites
    red, _ = sweep_tile(spec, [wl], 0, len(spec), cons=cons)
    np.testing.assert_array_equal(
        np.asarray(red.full[2])[0, :len(spec)], o_f)
    assert int(red.n_feasible[0]) == int(o_f.sum())


def test_all_infeasible_tile():
    """Constraint-mask edge case: a power budget nothing satisfies."""
    spec = small_spec()
    cons = dse.Constraint(max_power_w=1e-3, min_hbm_fit=False)
    red, batch = sweep_tile(spec, WLS[:1], 0, len(spec), cons=cons)
    assert int(red.n_feasible[0]) == 0
    assert int(red.n_survivors[0]) == 0
    assert not np.asarray(red.full[2])[0].any()
    fr = StreamingFrontier()
    fr.merge_reduced([], [], [], [], span=(0, len(batch)), n_feasible=0,
                     tile=0)
    assert len(fr) == 0 and fr.ref_energy_j is None
    assert fr.evaluated == len(batch) and fr.feasible_seen == 0


def test_campaign_all_infeasible_matches_numpy():
    cons = dse.Constraint(max_power_w=1e-3, min_hbm_fit=False)
    spec = small_spec()
    a = Campaign(WLS, spec, constraint=cons, evaluator="numpy").run()
    b = Campaign(WLS, spec, constraint=cons, evaluator="pallas").run()
    for key in a.frontiers:
        assert len(a.frontiers[key]) == len(b.frontiers[key]) == 0
        assert ([s.as_dict() for s in a.trajectories[key]]
                == [s.as_dict() for s in b.trajectories[key]])


# --- merge_reduced == raw merge ----------------------------------------------


def _cands(indices):
    return [dse.Candidate("tpu-v5e", 1, (1, 1), 1000.0 + i) for i in indices]


def _reduced_merge_span(fr, e, l, feas, lo, hi, tile=-1, superset=False):
    """Feed one [lo, hi) span through merge_reduced the way the fused
    evaluators do: survivors from the skyline (or a feasible superset) plus
    the tile aggregates."""
    keep, n_feas, ref_e, ref_l = costmodel.skyline_reduce(e, l, feas)
    if superset:
        keep = feas                      # every feasible point rides along
    idx = np.flatnonzero(keep)
    fr.merge_reduced(_cands(lo + idx), e[idx], l[idx], lo + idx,
                     span=(lo, hi), n_feasible=int(n_feas),
                     ref_energy_j=float(ref_e), ref_latency_s=float(ref_l),
                     tile=tile)
    return fr


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.floats(0.1, 100.0), st.floats(0.1, 100.0),
                          st.booleans()), min_size=1, max_size=32),
       st.integers(1, 5), st.booleans())
def test_merge_reduced_equals_raw_merge_property(pts, n_chunks, superset):
    """Any contiguous tiling merged reduced (exact skyline survivors OR the
    full feasible superset) produces the same frontier AND the same
    trajectory snapshots as raw merges of the full tiles."""
    e = np.asarray([p[0] for p in pts])
    l = np.asarray([p[1] for p in pts])
    feas = np.asarray([p[2] for p in pts])
    bounds = np.unique(np.linspace(0, len(pts), n_chunks + 1).astype(int))
    raw, red = StreamingFrontier(), StreamingFrontier()
    for lo, hi in zip(bounds, bounds[1:]):
        raw.merge(_cands(range(lo, hi)), e[lo:hi], l[lo:hi], feas[lo:hi],
                  indices=np.arange(lo, hi), tile=int(lo))
        _reduced_merge_span(red, e[lo:hi], l[lo:hi], feas[lo:hi],
                            int(lo), int(hi), tile=int(lo),
                            superset=superset)
    np.testing.assert_array_equal(raw.energy_j, red.energy_j)
    np.testing.assert_array_equal(raw.latency_s, red.latency_s)
    np.testing.assert_array_equal(raw.indices, red.indices)
    assert raw.candidates == red.candidates
    assert ([s.as_dict() for s in raw.trajectory]
            == [s.as_dict() for s in red.trajectory])
    assert (raw.evaluated, raw.feasible_seen) == (red.evaluated,
                                                  red.feasible_seen)


def test_merge_reduced_idempotent_and_rejects_partial_overlap():
    e = np.asarray([3.0, 2.0, 1.0, 5.0])
    l = np.asarray([1.0, 2.0, 3.0, 5.0])
    feas = np.ones(4, bool)
    fr = _reduced_merge_span(StreamingFrontier(), e, l, feas, 0, 4)
    size, ev = len(fr), fr.evaluated
    _reduced_merge_span(fr, e, l, feas, 0, 4)         # re-merge: no-op
    assert len(fr) == size and fr.evaluated == ev
    with pytest.raises(ValueError, match="partially overlaps"):
        fr.merge_reduced(_cands([4]), [1.0], [1.0], [4], span=(2, 6),
                         n_feasible=1, ref_energy_j=1.0, ref_latency_s=1.0)
    with pytest.raises(ValueError, match="outside span"):
        fr.merge_reduced(_cands([9]), [1.0], [1.0], [9], span=(4, 8),
                         n_feasible=1, ref_energy_j=1.0, ref_latency_s=1.0)


@pytest.mark.parametrize("n_workloads,lo,hi", [(1, 0, 20), (2, 5, 17)])
def test_staged_launch_equals_one_call(n_workloads, lo, hi):
    """Staging a tile, then launching on the staged matrix, gives the one
    call's ``SweepReduced`` field by field, bitwise (interpret mode); the
    stage runs once, inside the launch."""
    spec = small_spec(chunk_size=32)
    ev = TileEvaluator(WLS[:n_workloads], CampaignConfig(
        space=spec, constraint=CONS, evaluator="pallas"))
    batch = spec.slice(lo, hi, with_candidates=False)
    arrays = ev.padded_tile_arrays(batch)
    kw = dict(sim=ev.sim, constraint=CONS, max_survivors=4, interpret=True)
    one = ops.dse_sweep(arrays, ev.wl_cols, n_valid=len(batch), **kw)
    staged = ops.stage_dse_sweep(arrays, n_workloads, n_valid=len(batch),
                                 interpret=True)
    assert staged.shape[1] == ev.launch_lanes(len(batch))
    calls = []
    got = ops.dse_sweep_staged(lambda: calls.append(1) or staged,
                               ev.wl_cols, **kw)
    assert calls == [1]
    for field in dataclasses.fields(costmodel.SweepReduced):
        a, b = getattr(one, field.name), getattr(got, field.name)
        assert np.asarray(a).dtype == np.asarray(b).dtype, field.name
        assert np.array_equal(a, b), field.name
    jit = TileEvaluator(WLS[:n_workloads], CampaignConfig(
        space=spec, constraint=CONS, evaluator="jit"))
    with pytest.raises(ValueError, match="pallas"):
        jit.sweep_reduced(batch, lambda: staged)


def _compact_rows_host(keep, energy, latency, max_survivors: int):
    """numpy oracle of the survivor compaction: (surv_idx, surv_e, surv_l)
    as [W, K] with ascending lanes, entries past the row's survivor count
    zero, an overflowing row's first K survivors kept."""
    w_count, n = keep.shape
    k = min(int(max_survivors), n)
    surv_idx = np.zeros((w_count, k), np.int64)
    surv_e = np.zeros((w_count, k), energy.dtype)
    surv_l = np.zeros((w_count, k), latency.dtype)
    for w in range(w_count):
        pos = np.flatnonzero(keep[w])[:k]
        surv_idx[w, :pos.size] = pos
        surv_e[w, :pos.size] = energy[w, pos]
        surv_l[w, :pos.size] = latency[w, pos]
    return surv_idx, surv_e, surv_l


# rows of several compaction blocks, the last one part padding
COMPACT_K, COMPACT_N = 40, 300
assert COMPACT_N % costmodel._COMPACT_BLOCK and COMPACT_N > 3 * costmodel._COMPACT_BLOCK


def _keep_case(case):
    """[3, COMPACT_N] survivor masks of one compaction edge case."""
    rng = np.random.default_rng(7)
    w, n, k = 3, COMPACT_N, COMPACT_K
    if case == "random":
        return rng.random((w, n)) < 0.1
    keep = np.zeros((w, n), bool)
    count = {"none": 0, "exactly_k": k, "k_plus_1": k + 1, "all": n}.get(case)
    for r in range(w):
        if case == "padded":       # lanes past 250 are padding
            keep[r, :250] = rng.random(250) < 0.15
        else:
            keep[r, rng.choice(n, size=count, replace=False)] = True
    return keep


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("case", ["random", "none", "exactly_k", "k_plus_1",
                                  "all", "padded"])
def test_compact_rows_device_matches_host(case, dtype):
    """The device compaction equals the numpy oracle bitwise, in float32 and
    in float64 (interpret mode's precision): alone, and inside the jitted
    launch tail (``_reduce_launch``) the fused launches run.  The rows lie on
    one anti-diagonal (energy rising, latency falling), so the screen drops
    no feasible lane and the survivors are the mask itself;
    ``n_survivors`` reports the true count past ``K``."""
    import functools
    import jax
    keep = _keep_case(case)
    rng = np.random.default_rng(11)
    e = np.sort(rng.random(keep.shape) + 0.5, axis=1)
    l = np.sort(rng.random(keep.shape) + 0.5, axis=1)[:, ::-1]
    if case == "padded":           # padding lanes copy lane 0
        e[:, 250:], l[:, 250:] = e[:, :1], l[:, :1]
    e, l = e.astype(dtype), np.ascontiguousarray(l).astype(dtype)
    k = COMPACT_K
    with jax.enable_x64(dtype == "float64"):
        forms = [jax.jit(costmodel._compact_rows_device,
                         static_argnums=3)(keep, e, l, k)]
        red = costmodel.run_reduced_launch(
            jax.jit(functools.partial(costmodel._reduce_launch,
                                      max_survivors=k)), (e, l, keep), k)
    hi, he, hl = _compact_rows_host(keep, e, l, k)
    for got_i, got_e, got_l in forms + [(red.surv_idx, red.surv_energy,
                                         red.surv_latency)]:
        got_i, got_e, got_l = map(np.asarray, (got_i, got_e, got_l))
        assert got_e.dtype == got_l.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(got_i, hi)
        assert np.array_equal(got_e, he) and np.array_equal(got_l, hl)
    counts = keep.sum(axis=1)
    np.testing.assert_array_equal(red.n_survivors, counts)
    np.testing.assert_array_equal(red.n_feasible, counts)
    assert [red.overflowed(w) for w in range(3)] == list(counts > k)
    ref_e = np.where(keep, e, -np.inf).max(axis=1)
    assert np.array_equal(red.ref_energy, ref_e)
    for got, want in zip(red.full, (e, l, keep)):
        assert np.array_equal(np.asarray(got), want)


def test_launch_survivors_are_gathered_from_its_full_rows():
    """On a real fused launch (interpret mode): the survivors are ascending
    feasible lanes, their energy and latency are the full rows' values at
    those lanes bitwise, and the fill past ``n_survivors`` is zero."""
    spec = small_spec(chip_counts=(16, 64), freq_points=20)
    red, _ = sweep_tile(spec, WLS, 0, len(spec), max_survivors=32)
    import jax
    full = jax.device_get(red.full)
    for wi in range(len(WLS)):
        k = min(int(red.n_survivors[wi]), red.surv_idx.shape[1])
        idx = red.surv_idx[wi][:k]
        assert k > 0 and np.all(np.diff(idx) > 0)
        assert full[2][wi, idx].all()
        assert np.array_equal(red.surv_energy[wi][:k], full[0][wi, idx])
        assert np.array_equal(red.surv_latency[wi][:k], full[1][wi, idx])
        assert not red.surv_idx[wi][k:].any()
        assert not red.surv_energy[wi][k:].any()


def _counting_launches(monkeypatch):
    """Record, per fused launch, whether any workload overflowed."""
    real = TileEvaluator.sweep_reduced
    launches = []

    def sweep(self, batch, stage=None):
        red = real(self, batch, stage)
        launches.append(any(red.overflowed(w)
                            for w in range(len(red.n_survivors))))
        return red
    monkeypatch.setattr(TileEvaluator, "sweep_reduced", sweep)
    return launches


@pytest.mark.parametrize("max_survivors", [2048, 16])
def test_full_rows_copied_only_for_an_overflowed_launch(max_survivors,
                                                        monkeypatch):
    """A Pallas campaign (interpret) copies a launch's full rows once for
    each launch with an overflowed workload and never otherwise; with a
    tiny ``max_survivors`` its frontiers still equal the numpy
    evaluator's."""
    from repro.telemetry import Telemetry
    launches = _counting_launches(monkeypatch)
    tel = Telemetry()
    spec = small_spec(chip_counts=(16, 64), freq_points=20, chunk_size=32)
    cfg = dict(space=spec, constraint=CONS)
    got = Campaign(WLS, CampaignConfig(evaluator="pallas",
                                       max_survivors=max_survivors, **cfg),
                   telemetry=tel).run()
    fetches = tel.counter("evaluator_full_fetches_total").value
    assert fetches == sum(launches)
    fetch_spans = [r for r in tel.tracer.records if r.name == "fetch_full"]
    assert len(fetch_spans) == fetches
    if max_survivors == 2048:
        assert fetches == 0
    else:
        assert 0 < fetches < len(launches)
        want = Campaign(WLS, CampaignConfig(evaluator="numpy", **cfg)).run()
        for key in want.frontiers:
            assert_same_candidate_set(want.frontiers[key],
                                      got.frontiers[key], rtol=1e-12)


def test_adaptive_samples_read_from_one_full_copy(monkeypatch):
    """An adaptive campaign's training samples are the launch's full rows
    at the sampled lanes, bitwise, read from one host copy per launch."""
    import jax
    from repro.dse_campaign import AdaptiveConfig
    from repro.telemetry import Telemetry
    spec = small_spec(chunk_size=32)
    tel = Telemetry()
    ev = TileEvaluator(WLS, CampaignConfig(
        space=spec, constraint=CONS, evaluator="pallas",
        adaptive=AdaptiveConfig(train_sample=8)), telemetry=tel)
    reds = []
    real = ev.sweep_reduced
    monkeypatch.setattr(ev, "sweep_reduced",
                        lambda *a: reds.append(real(*a)) or reds[-1])
    batch = spec.slice(0, 32, with_candidates=False)
    tr = ev.reduce_tile(batch, 0)
    assert tel.counter("evaluator_full_fetches_total").value == 1
    full = jax.device_get(reds[0].full)
    lidx = tr.sample_lidx
    assert len(lidx) == 8
    for wi in range(len(WLS)):
        for got, row in ((tr.sample_energy[wi], full[0][wi]),
                         (tr.sample_latency[wi], full[1][wi])):
            assert got.dtype == np.float64
            assert np.array_equal(got, np.asarray(row, np.float64)[lidx])


# --- campaign-level identity --------------------------------------------------


def assert_same_candidate_set(a: dse.ParetoFrontier, b: dse.ParetoFrontier,
                              rtol=1e-9):
    ca, ea, la, ia = canonical_frontier(a)
    cb, eb, lb, ib = canonical_frontier(b)
    assert ca == cb
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_allclose(ea, eb, rtol=rtol)
    np.testing.assert_allclose(la, lb, rtol=rtol)


@pytest.mark.parametrize("chunk", [7, 64])
def test_campaign_pallas_matches_numpy_frontier(chunk):
    """The acceptance gate in miniature: evaluator='pallas' (interpret mode)
    produces the numpy evaluator's exact frontier candidate set, values to
    ~1 ulp, hypervolume to well within 1e-6 relative."""
    spec = small_spec(chunk_size=chunk)
    a = Campaign(WLS, spec, constraint=CONS, evaluator="numpy").run()
    b = Campaign(WLS, spec, constraint=CONS, evaluator="pallas").run()
    for key in a.frontiers:
        assert_same_candidate_set(a.frontiers[key], b.frontiers[key],
                                  rtol=1e-12)
        assert (a.frontiers[key].feasible_count
                == b.frontiers[key].feasible_count)
        ha = a.trajectories[key][-1].hypervolume
        hb = b.trajectories[key][-1].hypervolume
        assert hb == pytest.approx(ha, rel=1e-6)


def test_campaign_pallas_multi_block_grid_matches_numpy(monkeypatch):
    """A tile spanning several lane blocks of the kernel's grid (128-lane
    blocks here, 320 candidates padded to three of them) still holds the
    numpy evaluator's exact frontier candidate set."""
    from repro.kernels import dse_sweep
    monkeypatch.setattr(dse_sweep, "MAX_BLOCK_LANES", dse_sweep.LANE)
    dse_sweep._jit_dse_sweep.cache_clear()
    spec = small_spec(chip_counts=(16, 64), freq_points=20, chunk_size=320)
    assert dse_sweep.padded_lanes(len(spec), len(WLS)) == 3 * dse_sweep.LANE
    try:
        a = Campaign(WLS, spec, constraint=CONS, evaluator="numpy").run()
        b = Campaign(WLS, spec, constraint=CONS, evaluator="pallas").run()
    finally:
        dse_sweep._jit_dse_sweep.cache_clear()
    for key in a.frontiers:
        assert len(a.frontiers[key]) > 1
        assert_same_candidate_set(a.frontiers[key], b.frontiers[key],
                                  rtol=1e-12)


def test_campaign_jit_fused_matches_numpy_candidate_set():
    """The float32 fused jit evaluator lands on the same frontier candidate
    set (values only to float32 tolerance)."""
    spec = small_spec()
    a = Campaign(WLS, spec, constraint=CONS, evaluator="numpy").run()
    b = Campaign(WLS, spec, constraint=CONS, evaluator="jit").run()
    for key in a.frontiers:
        assert_same_candidate_set(a.frontiers[key], b.frontiers[key],
                                  rtol=1e-5)


def test_campaign_pallas_overflow_fallback_identical():
    """max_survivors=1 forces the full-array fallback on every tile; the
    frontier must not change."""
    spec = small_spec()
    a = Campaign(WLS, spec, constraint=CONS, evaluator="pallas").run()
    b = Campaign(WLS, spec, constraint=CONS, evaluator="pallas",
                 max_survivors=1).run()
    for key in a.frontiers:
        assert frontiers_identical(a.frontiers[key], b.frontiers[key])
        assert ([s.as_dict() for s in a.trajectories[key]]
                == [s.as_dict() for s in b.trajectories[key]])


def test_campaign_pallas_resume_equals_fresh(tmp_path):
    spec = small_spec(chunk_size=16)
    ckpt = str(tmp_path / "ckpt.json")
    interrupted = Campaign(WLS, spec, constraint=CONS, evaluator="pallas")
    partial = interrupted.run(checkpoint_path=ckpt, max_tiles=2)
    assert not partial.complete and partial.tiles_done == 2
    resumed = Campaign.from_checkpoint(ckpt)
    assert resumed.evaluator == "pallas" and resumed.next_tile == 2
    final = resumed.run(checkpoint_path=ckpt)
    assert final.complete
    fresh = Campaign(WLS, spec, constraint=CONS, evaluator="pallas").run()
    for key in fresh.frontiers:
        assert frontiers_identical(final.frontiers[key], fresh.frontiers[key])
        assert ([s.as_dict() for s in final.trajectories[key]]
                == [s.as_dict() for s in fresh.trajectories[key]])


def test_partial_tile_padding_is_masked():
    """Fused evaluators pad the last tile to chunk_size; the padded lanes
    must never count as evaluated, feasible, or frontier members."""
    spec = small_spec(chunk_size=15)            # 20 candidates -> 15 + 5
    assert len(spec) % 15 != 0
    a = Campaign(WLS, spec, constraint=CONS, evaluator="numpy").run()
    b = Campaign(WLS, spec, constraint=CONS, evaluator="pallas").run()
    for key in a.frontiers:
        assert_same_candidate_set(a.frontiers[key], b.frontiers[key],
                                  rtol=1e-12)
        assert (a.trajectories[key][-1].evaluated
                == b.trajectories[key][-1].evaluated == len(spec))


# --- runner plumbing ----------------------------------------------------------


def test_tile_prefetcher_propagates_and_closes():
    from repro.dse_campaign.runner import _TilePrefetcher

    def gen():
        yield 1
        yield 2
        raise RuntimeError("boom")

    pf = _TilePrefetcher(gen())
    assert next(pf) == 1 and next(pf) == 2
    with pytest.raises(RuntimeError, match="boom"):
        next(pf)
    pf.close()

    slow = _TilePrefetcher(iter(range(100)))
    assert next(slow) == 0
    slow.close()                                 # early stop must not hang
    slow._thread.join(timeout=5)
    assert not slow._thread.is_alive()


def test_campaign_rejects_unknown_evaluator():
    with pytest.raises(ValueError, match="unknown evaluator"):
        Campaign(WLS, small_spec(), evaluator="warp")


def test_candidates_at_matches_candidate():
    spec = small_spec()
    idx = [0, 3, len(spec) - 1]
    assert spec.candidates_at(idx) == [spec.candidate(i) for i in idx]
    with pytest.raises(IndexError):
        spec.candidates_at([len(spec)])


def test_legacy_checkpoint_without_pipeline_key_stays_legacy(tmp_path):
    """Pre-fusion checkpoints (no 'pipeline' key) ran the per-workload jit
    loop; resuming them must stay on that engine rather than splicing the
    fused float32 sweep into a half-done frontier."""
    from repro.dse_campaign import store

    spec = small_spec(chunk_size=16)
    camp = Campaign(WLS, spec, constraint=CONS, evaluator="jit")
    camp.run(max_tiles=1)
    state = camp.state_dict()
    assert state["pipeline"] is True
    del state["pipeline"]
    path = str(tmp_path / "legacy.json")
    store.save_checkpoint(state, path)
    resumed = Campaign.from_checkpoint(path)
    assert resumed.pipeline is False and not resumed.fused
    # new-format checkpoints round-trip the flag
    path2 = str(tmp_path / "new.json")
    store.save_checkpoint(camp.state_dict(), path2)
    assert Campaign.from_checkpoint(path2).pipeline is True


# --- CI evaluator diff --------------------------------------------------------


def test_compare_evaluators_gates():
    from benchmarks.compare_campaign import compare_evaluators

    def payload(hv_jit, hv_pallas, identical=True):
        return {"frontiers": {"jit": {"a|s": {"points": []}},
                              "pallas": {"a|s": {"points": []}}},
                "hv": {"jit": {"a|s": hv_jit}, "pallas": {"a|s": hv_pallas}},
                "pallas_vs_numpy": {"identical_candidate_set": identical,
                                    "max_hv_rel_diff": 0.0}}

    ok, _ = compare_evaluators(payload(100.0, 100.0 + 1e-6))
    assert ok
    ok, _ = compare_evaluators(payload(100.0, 90.0))       # 10% divergence
    assert not ok
    ok, _ = compare_evaluators(payload(0.0, 50.0))         # collapsed jit hv
    assert not ok
    ok, _ = compare_evaluators(payload(0.0, 0.0))
    assert ok
    ok, _ = compare_evaluators(payload(100.0, 100.0, identical=False))
    assert not ok                                          # numpy identity


# --- interpret auto-detection -------------------------------------------------


def test_default_interpret_autodetect(monkeypatch):
    """Interpret mode follows the backend alone: the environment cannot put
    a TPU run into the interpreter, only an explicit ``interpret=`` can."""
    import jax
    expected = jax.default_backend() != "tpu"
    assert ops.default_interpret() is expected   # CPU container -> True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_interpret() is False
    assert ops._resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops._resolve_interpret(None) is True
    assert ops._resolve_interpret(False) is False
