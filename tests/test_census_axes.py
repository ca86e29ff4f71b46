"""Collectives on the mesh axis the census puts them on: HxA's attribution
of wire bytes to mesh axes (hand-written HLO, a shard_map program and the
DeepSeek-V3 census on small host meshes), and the cost model's pricing of a
workload's ``coll_model_share`` on every path."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import costmodel, dse, hxa
from repro.dse_campaign import Campaign, CampaignConfig, SpaceSpec, TileEvaluator
from repro.hw import get_chip
from repro.serving.frontier_index import FrontierIndex, family_key
from repro.telemetry import Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESH = {"data": 2, "model": 4}

# --- axis attribution on hand-written HLO ------------------------------------


@pytest.mark.parametrize("attrs, axes", [
    ("replica_groups=[2,4]<=[8]", ("model",)),                  # iota
    ("replica_groups=[4,2]<=[2,4]T(1,0)", ("data",)),           # transposed
    ("replica_groups={{0,1,2,3},{4,5,6,7}}", ("model",)),
    ("replica_groups={{0,4},{1,5},{2,6},{3,7}}", ("data",)),
    ("replica_groups={{0,1},{2,3},{4,5},{6,7}}", ("model",)),   # sub-axis
    ("replica_groups={{0,1,2,3,4,5,6,7}}", ("data", "model")),
    ("replica_groups=[1,8]<=[8]", ("data", "model")),
    ("replica_groups={}", ("data", "model")),                   # all devices
    ("source_target_pairs={{0,4},{4,0},{1,5},{5,1}}", ("data",)),
    ("source_target_pairs={{0,1},{1,2},{2,3},{3,0}}", ("model",)),
])
def test_groups_vary_along_the_axes_they_span(attrs, axes):
    line = f"channel_id=1, {attrs}, use_global_device_ids=true"
    assert hxa.group_axes(hxa.group_attr(line), MESH) == axes


def test_iota_groups_expand_as_written():
    assert hxa.device_groups("replica_groups=[4,2]<=[2,4]T(1,0)") == [
        [0, 4], [1, 5], [2, 6], [3, 7]]
    assert hxa.device_groups("replica_groups=[2,4]<=[4,2]T(1,0)") == [
        [0, 2, 4, 6], [1, 3, 5, 7]]
    assert hxa.device_groups("replica_groups={}", 4) == [[0, 1, 2, 3]]


def test_split_charges_spanning_and_pod_groups_to_data():
    wire = {"replica_groups=[2,4]<=[8]": 96.0,
            "replica_groups=[4,2]<=[2,4]T(1,0)": 64.0,
            "replica_groups=[1,8]<=[8]": 8.0}
    assert hxa.split_wire_bytes(wire, MESH) == {
        "data": 72.0, "model": 96.0, "both_axes": 8.0}
    pod = {"pod": 2, "data": 2, "model": 2}
    assert hxa.group_axes("replica_groups={{0,4},{1,5}}", pod) == ("pod",)
    assert hxa.split_wire_bytes({"replica_groups={{0,4},{1,5}}": 5.0},
                                pod) == {"data": 5.0, "model": 0.0,
                                         "both_axes": 5.0}


@pytest.mark.parametrize("bad", [
    "channel_id=1, to_apply=%add",                  # no groups at all
    "replica_groups=(0,1)",                         # unreadable
])
def test_a_collective_without_readable_groups_is_an_error(bad):
    with pytest.raises(ValueError):
        hxa.group_attr(bad)


def test_groups_outside_the_mesh_or_of_unknown_size_are_errors():
    with pytest.raises(ValueError, match="outside"):
        hxa.group_axes("replica_groups={{0,8}}", MESH)
    with pytest.raises(ValueError, match="device count"):
        hxa.device_groups("replica_groups={}")


SPLIT_HLO = """
HloModule split

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %bound = s32[] constant(3)
  ROOT %lt = pred[] compare(%it, %bound), direction=LT
}

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %nit = s32[] add(%it, %one)
  %ar = f32[4,4] all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  ROOT %t = (s32[], f32[4,4]) tuple(%nit, %ar)
}

ENTRY %main (x: f32[4,4]) -> f32[8,4] {
  %x = f32[4,4] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4,4]) tuple(%zero, %x)
  %w = (s32[], f32[4,4]) while(%init), condition=%cond, body=%body
  %y = f32[4,4] get-tuple-element(%w), index=1
  %all = f32[4,4] all-reduce(%y), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%add
  ROOT %ag = f32[8,4] all-gather(%all), channel_id=3, replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}, use_global_device_ids=true
}
"""


def test_synthetic_module_splits_by_hand_count():
    """3 trips of a 64-B all-reduce over model (ring 4: x1.5), a 64-B
    all-reduce over all 8 devices (x1.75) and a 128-B all-gather over data
    (ring 2: x0.5)."""
    res = hxa.analyze_hlo_text(SPLIT_HLO, mesh=MESH)
    assert res["wire_bytes_by_axis"] == {
        "model": 3 * 64 * 1.5, "data": 64 * 1.75 + 128 * 0.5,
        "both_axes": 64 * 1.75}
    split = res["wire_bytes_by_axis"]
    assert split["data"] + split["model"] == res["wire_bytes"]
    assert "wire_bytes_by_axis" not in hxa.analyze_hlo_text(SPLIT_HLO)


# --- real lowerings on 8 host devices (subprocesses) -------------------------


def _run(body: str, timeout=600):
    code = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_shard_map_collectives_land_on_their_axes():
    """A psum over ``model`` of a [4, 4] f32 shard (64 B, ring 4: 96 B on
    the wire) and an all_gather over ``data`` to [8, 4] (128 B, ring 2:
    64 B), counted by hand."""
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import hxa
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ('data', 'model'))

    def f(x, y):
        return (jax.lax.psum(x, 'model'),
                jax.lax.all_gather(y, 'data', tiled=True))

    g = jax.shard_map(f, mesh=mesh, in_specs=(P('data', 'model'),) * 2,
                      out_specs=(P('data', 'model'), P(None, 'model')),
                      check_vma=False)
    x = jnp.zeros((8, 16), jnp.float32)
    text = jax.jit(g).lower(x, x).compile().as_text()
    res = hxa.analyze_hlo_text(text, mesh={'data': 2, 'model': 4})
    assert res['wire_bytes_by_axis'] == {
        'data': 64.0, 'model': 96.0, 'both_axes': 0.0}, res
    assert res['wire_bytes'] == 160.0
    print('ok')
    """)
    assert "ok" in out


def test_reduced_deepseek_v3_census_splits_every_collective():
    """``deepseek_v3_671b.reduced()`` lowered on a 2x4 host mesh in each
    kind of shape: the per-axis parts add up to ``wire_bytes`` exactly,
    and both axes carry bytes."""
    out = _run("""
    import json
    from repro.configs.base import (SMOKE_DECODE, SMOKE_PREFILL, SMOKE_TRAIN,
                                    get_config)
    from repro.launch.lowering import lower_cell
    from repro.launch.mesh import make_mesh
    from repro.telemetry import Telemetry
    mesh = make_mesh((2, 4), ('data', 'model'))
    cfg = get_config('deepseek_v3_671b').reduced()
    tel = Telemetry()
    for shape in (SMOKE_TRAIN, SMOKE_PREFILL, SMOKE_DECODE):
        h = lower_cell(cfg, shape, mesh, tracer=tel)['hxa']
        split = h['wire_bytes_by_axis']
        assert split['data'] + split['model'] == h['wire_bytes'], split
        assert split['data'] > 0 and split['model'] > 0, (shape, split)
        print(json.dumps([shape.name, split]))
    names = [r.name for r in tel.tracer.records]
    assert names == ['census_axes'] * 3, names
    """)
    assert out.count("smoke_") == 3


# --- the cost model prices the share on every path ----------------------------

BASE = {"flops": 3.2e14, "hbm_bytes": 4.5e13, "collective_bytes": 5e11,
        "wire_bytes": 7e11}
SPLIT = dict(BASE, coll_model_share=0.708)
WL = dse.Workload("deepseek_v3_671b", "train_4k", SPLIT, 256, 10.5)
FLAT = dse.Workload("qwen3_14b", "train_4k", BASE, 256, 0.5)
CONS = dse.Constraint(max_power_w=40_000, min_hbm_fit=True)


def small_spec(**kw):
    kw.setdefault("chips", ("tpu-v5e", "tpu-v5p", "tpu-edge"))
    kw.setdefault("chip_counts", (16, 64, 256))
    kw.setdefault("freq_points", 4)
    kw.setdefault("mesh_dims", 3)
    kw.setdefault("chunk_size", 128)
    return SpaceSpec(**kw)


def test_scalar_equals_batch_with_a_share():
    """The scalar oracle and the numpy batch agree bitwise on the
    collective term, where the share enters; latency and energy to the
    ulp (Python's ``sum`` of the three terms is compensated since 3.12,
    numpy's is not: the same residual as without a share)."""
    spec = small_spec()
    batch = spec.slice(0, len(spec))
    res, _ = dse.evaluate_workload_tile(WL, batch, CONS)
    for i, cand in enumerate(batch.candidates):
        ana = dse._scale_analysis(WL.base_analysis, WL.base_chips, cand)
        ref = costmodel.simulate(ana, get_chip(cand.chip), cand.n_chips,
                                 freq_mhz=cand.freq_mhz, mesh=cand.mesh)
        assert float(res.t_collective[i]) == ref.t_collective, cand
        assert float(res.t_compute[i]) == ref.t_compute, cand
        assert abs(float(res.latency_s[i]) - ref.latency_s) <= (
            4e-16 * ref.latency_s), cand
        assert abs(float(res.energy_j[i]) - ref.energy_j) <= (
            4e-16 * abs(ref.energy_j)), cand


def test_the_share_splits_the_payload_where_the_fraction_did():
    ana = dse._scale_analysis(SPLIT, 256, dse.Candidate("tpu-v5e", 64,
                                                        (8, 8), 1600.0))
    p_d, p_m = costmodel.collective_payload(ana, 64, 0.5)
    payload = ana["coll_payload_bytes"]
    assert (p_d, p_m) == (payload * (1.0 - 0.708), payload * 0.708)
    flat = dict(ana)
    del flat["coll_model_share"]
    assert costmodel.collective_payload(flat, 64, 0.25) == (
        payload * (1.0 - 0.25), payload * 0.25)


@pytest.mark.parametrize("frac", [0.5, 0.25])
def test_a_shareless_workload_prices_as_before(frac):
    """Without a share every path prices with ``coll_model_frac``, bitwise
    the same as a workload that packs the fraction as its share, and the
    fused packing fills the column with it."""
    sim = costmodel.SimConfig(coll_model_frac=frac)
    spec = small_spec()
    batch = spec.slice(0, len(spec))
    packed = dataclasses.replace(FLAT, base_analysis=dict(
        BASE, coll_model_share=frac))
    a, fa = dse.evaluate_workload_tile(FLAT, batch, CONS, sim=sim)
    b, fb = dse.evaluate_workload_tile(packed, batch, CONS, sim=sim)
    for f in ("t_collective", "latency_s", "energy_j", "power_w"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    np.testing.assert_array_equal(fa, fb)
    ev = TileEvaluator([FLAT, WL], CampaignConfig(space=spec, sim=sim,
                                                  evaluator="pallas"))
    assert ev.wl_cols.shape == (2, len(costmodel.WL_COLS))
    assert list(ev.wl_cols[:, -1]) == [frac, 0.708]


def test_pareto_search_prices_each_workloads_share():
    spec = small_spec()
    both = dse.pareto_search([WL, FLAT], spec.slice(0, len(spec)), CONS)
    for wl in (WL, FLAT):
        one = dse.pareto_search(wl, spec.slice(0, len(spec)), CONS)
        key = (wl.arch, wl.shape)
        np.testing.assert_array_equal(both[key].indices, one[key].indices)
        np.testing.assert_array_equal(both[key].energy_j, one[key].energy_j)


@pytest.mark.parametrize("evaluator, rtol", [("pallas", 1e-12),
                                             ("jit", 1e-5)])
def test_fused_sweeps_price_the_share_within_tolerance(evaluator, rtol):
    """The Pallas sweep (interpret mode, float64) and the fused jit sweep
    (float32) land on the numpy evaluator's frontier candidates for a
    share-carrying workload, values within their tiers' tolerances."""
    spec = small_spec()
    wls = [WL, FLAT]
    a = Campaign(wls, CampaignConfig(space=spec, constraint=CONS,
                                     evaluator="numpy")).run()
    b = Campaign(wls, CampaignConfig(space=spec, constraint=CONS,
                                     evaluator=evaluator)).run()
    for key, fa in a.frontiers.items():
        fb = b.frontiers[key]
        assert len(fa) > 1
        np.testing.assert_array_equal(np.sort(fa.indices),
                                      np.sort(fb.indices))
        oa, ob = np.argsort(fa.indices), np.argsort(fb.indices)
        np.testing.assert_allclose(fb.energy_j[ob], fa.energy_j[oa],
                                   rtol=rtol)
        np.testing.assert_allclose(fb.latency_s[ob], fa.latency_s[oa],
                                   rtol=rtol)


def test_legacy_jit_engine_prices_the_share():
    spec = small_spec()
    batch = spec.slice(0, len(spec))
    ref, _ = dse.evaluate_workload_tile(WL, batch, CONS)
    jit, _ = dse.evaluate_workload_tile(WL, batch, CONS, engine="jit")
    np.testing.assert_allclose(np.asarray(jit.t_collective),
                               np.asarray(ref.t_collective), rtol=1e-5)
    flat, _ = dse.evaluate_workload_tile(
        dataclasses.replace(WL, base_analysis=BASE), batch, CONS)
    assert not np.allclose(np.asarray(flat.t_collective),
                           np.asarray(ref.t_collective), rtol=1e-3)


def test_split_evaluations_are_counted():
    spec = small_spec()
    tel = Telemetry()
    Campaign([WL, FLAT], CampaignConfig(space=spec, constraint=CONS,
                                        evaluator="pallas"),
             telemetry=tel).run()
    assert tel.counter("evaluator_candidates_total").value == 2 * len(spec)
    assert tel.counter("evaluator_split_evals_total").value == len(spec)


def test_census_analysis_carries_the_artifacts_share():
    hxa_block = dict(BASE, wire_bytes_by_axis={"data": 3.0, "model": 1.0,
                                               "both_axes": 0.0})
    assert dse.census_analysis(hxa_block) == dict(BASE,
                                                  coll_model_share=0.25)
    assert dse.census_analysis(BASE) == BASE
    silent = dict(BASE, wire_bytes_by_axis={"data": 0.0, "model": 0.0,
                                            "both_axes": 0.0})
    assert dse.census_analysis(silent) == BASE


def test_sim_model_version_is_4():
    assert costmodel.SIM_MODEL_VERSION == 4


# --- the serving index keys a share only where a family has one --------------


def _index(workloads):
    spec = small_spec(chips=("tpu-v5e",), chip_counts=(16, 64))
    camp = Campaign(workloads, CampaignConfig(space=spec, constraint=CONS,
                                              evaluator="numpy"))
    camp.run()
    return FrontierIndex.from_campaign(camp)


def test_family_key_carries_a_share_only_where_there_is_one():
    np.testing.assert_array_equal(
        family_key(FLAT), [BASE["flops"], BASE["hbm_bytes"],
                           BASE["collective_bytes"], BASE["wire_bytes"],
                           256, 0.5])
    assert family_key(WL).shape == (7,) and family_key(WL)[-1] == 0.708


def test_shareless_lookups_are_unchanged_beside_share_families():
    """Share-less keys project and look up as before; a key with a share
    matches only its own family, never the share-less twin."""
    twin = dataclasses.replace(WL, shape="prefill_32k", base_analysis=BASE)
    flat_only = _index([FLAT, twin])
    mixed = _index([FLAT, twin, WL])
    for wl in (FLAT, twin):
        assert mixed.lookup(wl).shape == flat_only.lookup(wl).shape \
            == wl.shape
        np.testing.assert_array_equal(
            mixed._normalize(family_key(wl)),
            (np.log1p(family_key(wl)) - mixed._feat_lo) / mixed._feat_span)
    assert mixed.lookup(WL).shape == "train_4k"
    assert flat_only.lookup(WL) is None
    other = dataclasses.replace(WL, base_analysis=dict(SPLIT,
                                                       coll_model_share=0.6))
    assert mixed.lookup(other) is None
    again = FrontierIndex.from_dict(mixed.to_dict())
    assert again.lookup(WL).shape == "train_4k"
