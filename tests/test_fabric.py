"""Distributed campaign fabric tests: lease ledger invariants, deterministic
lease-timeout expiry under a fake clock, and the headline identity — the
distributed frontier is bitwise-equal to the single-process frontier for any
worker count, interleaving (seeded), injected worker death, duplicated
payload delivery, or hang recovered by lease timeout."""

import numpy as np
import pytest

from repro.core import costmodel, dse
from repro.dse_campaign import (Campaign, FabricCoordinator, FakeClock,
                                FaultInjection, LeaseBoard, LocalFabric,
                                MultiprocessFabric, SliceVariant, SpaceSpec,
                                campaign_config, evaluator_from_config,
                                frontiers_identical, store, tile_span)
from repro.dse_campaign.fabric import _expand_intervals, _tile_intervals
from repro.runtime.fault_tolerance import HeartbeatMonitor

BASE = {"flops": 3.2e14, "hbm_bytes": 4.5e13, "collective_bytes": 5e11,
        "wire_bytes": 7e11}
WLS = [dse.Workload("qwen3_14b", "train_4k", BASE, 256, 0.5),
      dse.Workload("stablelm_1_6b", "serve_2k",
                   {k: v * 0.3 for k, v in BASE.items()}, 64, 0.2)]
CONS = dse.Constraint(max_power_w=50_000)


def small_spec(**kw):
    kw.setdefault("chips", ("tpu-v5e", "tpu-v4", "tpu-edge"))
    kw.setdefault("chip_counts", (16, 64))
    kw.setdefault("freq_points", 7)
    kw.setdefault("variants", (SliceVariant(), SliceVariant("bin85", 0.85)))
    kw.setdefault("chunk_size", 32)
    return SpaceSpec(**kw)


def campaign(**kw):
    kw.setdefault("evaluator", "numpy")
    spec = kw.pop("spec", None) or small_spec()
    return Campaign(WLS, spec, constraint=CONS, **kw)


def assert_identical_frontiers(a, b):
    assert set(a) == set(b)
    for key in a:
        assert frontiers_identical(a[key], b[key]), key


@pytest.fixture(scope="module")
def single_process_result():
    """The reference frontier every fabric variant must reproduce bitwise."""
    return campaign().run()


# --- LeaseBoard: the tile ownership ledger -----------------------------------


def test_lease_board_issues_smallest_pending_first():
    board = LeaseBoard(5)
    assert [board.next_tile("a"), board.next_tile("b")] == [0, 1]
    assert board.complete(0) is True
    assert board.next_tile("a") == 2
    assert board.n_done == 1 and not board.all_done


def test_lease_board_complete_is_first_write_wins():
    board = LeaseBoard(3)
    board.next_tile("a")
    assert board.complete(0) is True
    assert board.complete(0) is False  # duplicate delivery: stats-only no-op
    assert board.n_done == 1


def test_lease_board_revoke_repends_and_reissues():
    board = LeaseBoard(4)
    assert board.next_tile("a") == 0
    assert board.next_tile("b") == 1
    assert board.revoke_worker("a") == [0]
    # the revoked tile is the smallest pending again, for any worker
    assert board.next_tile("b") == 0
    assert board.revoke_worker("a") == []  # nothing left to revoke


def test_lease_board_never_reissues_done_tiles():
    board = LeaseBoard(3)
    t = board.next_tile("a")
    board.revoke_worker("a")          # tile 0 re-pends ...
    assert board.complete(t) is True  # ... but the "dead" worker delivers it
    # re-issue must skip it: the pending heap entry is stale
    assert board.next_tile("b") == 1
    assert board.next_tile("b") == 2
    assert board.next_tile("b") is None


def test_lease_board_contiguous_prefix_and_preseeded_done():
    board = LeaseBoard(6, done=[0, 1, 3])
    assert board.contiguous_done_prefix() == 2
    assert board.next_tile("a") == 2    # holes first, never 0/1/3
    board.complete(2)
    assert board.contiguous_done_prefix() == 4
    assert board.done_tiles == [0, 1, 2, 3]


def test_tile_interval_roundtrip():
    tiles = [0, 1, 2, 5, 7, 8]
    assert _tile_intervals(tiles) == [[0, 3], [5, 6], [7, 9]]
    assert _expand_intervals(_tile_intervals(tiles)) == tiles


def test_tile_span_matches_tiles_iteration():
    spec = small_spec()
    for t, lo, batch in spec.tiles():
        assert tile_span(spec, t) == (lo, lo + len(batch))
    with pytest.raises(IndexError):
        tile_span(spec, spec.n_tiles())


# --- HeartbeatMonitor + coordinator expiry: deterministic under FakeClock ----


def test_heartbeat_register_forget_and_fake_clock_expiry():
    clock = FakeClock()
    mon = HeartbeatMonitor([], timeout_s=10.0, clock=clock)
    mon.register("w0")
    clock.advance(6.0)
    mon.register("w1")
    clock.advance(5.0)            # w0 silent 11s > 10; w1 silent 5s
    assert mon.dead_hosts() == ["w0"]
    mon.beat("w0")
    assert mon.healthy()
    mon.forget("w0")
    clock.advance(100.0)
    assert mon.dead_hosts() == ["w1"]  # forgotten hosts never report dead


def test_coordinator_expires_only_lease_holders():
    clock = FakeClock()
    coord = FabricCoordinator(campaign(), lease_timeout_s=10.0, clock=clock)
    coord.register_worker("busy")
    coord.register_worker("idle")
    assert coord.lease("busy") == 0    # only "busy" holds a lease
    clock.advance(11.0)
    expired = coord.expire()
    # the hung lease holder is expelled and its tile re-pends; the idle
    # worker owes nothing and silence alone must not expel it
    assert expired == {"busy": [0]}
    assert coord.board.next_tile("idle") == 0


# --- worker config: serialization + version gates ----------------------------


def test_campaign_config_roundtrips_evaluator():
    camp = campaign(evaluator="numpy")
    ev = evaluator_from_config(campaign_config(camp))
    assert ev.evaluator == "numpy"
    assert ev.workload_keys == camp.engine.workload_keys
    assert len(ev.space) == len(camp.space)
    # the rebuilt evaluator reduces a tile identically to the original
    lo, hi = tile_span(camp.space, 1)
    batch = camp.space.slice(lo, hi)
    a = camp.engine.reduce_tile(batch, lo)
    b = ev.reduce_tile(batch, lo)
    for wi in range(a.n_workloads):
        np.testing.assert_array_equal(a.surv_gidx[wi], b.surv_gidx[wi])
        np.testing.assert_array_equal(a.surv_energy[wi], b.surv_energy[wi])
        np.testing.assert_array_equal(a.surv_latency[wi], b.surv_latency[wi])
    assert a.n_feasible == b.n_feasible
    assert a.ref_energy_j == b.ref_energy_j


def test_campaign_config_refuses_fast_evaluator():
    class Fitted:
        def predict(self, X):  # pragma: no cover - never called
            return np.zeros(len(X))

    camp = campaign(evaluator="fast", power_model=Fitted(),
                    cycles_model=Fitted())
    with pytest.raises(ValueError, match="fast"):
        campaign_config(camp)


def test_evaluator_from_config_refuses_mixed_cost_model_versions():
    cfg = campaign_config(campaign())
    cfg["sim_model_version"] = costmodel.SIM_MODEL_VERSION + 1
    with pytest.raises(ValueError, match="version"):
        evaluator_from_config(cfg)


# --- LocalFabric: interleaving + fault-injection identity --------------------


@pytest.mark.parametrize("n_workers,seed", [(1, 0), (2, 0), (3, 1), (5, 2)])
def test_local_fabric_identity_any_workers_any_interleaving(
        n_workers, seed, single_process_result):
    res = LocalFabric(campaign(), n_workers=n_workers, seed=seed).run()
    assert res.complete
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)
    # stats ledger is exact despite arbitrary completion order
    assert res.candidates_evaluated == len(small_spec()) * len(WLS)


def test_local_fabric_survives_worker_death_and_duplicate_delivery(
        single_process_result):
    fab = LocalFabric(
        campaign(), n_workers=3, seed=1,
        fault=FaultInjection(kill_worker=1, kill_after_tiles=1,
                             duplicate=True))
    res = fab.run()
    assert res.complete
    # the scripted faults actually fired (seeded interleaving is stable)
    assert fab.coord.stats["lost_workers"] == [1]
    assert fab.coord.stats["reissued_tiles"] >= 1
    assert fab.coord.stats["duplicates"] == 1
    # ... and neither the re-issued tile nor the duplicate fold perturbed
    # the frontier or the candidate accounting
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)
    assert res.candidates_evaluated == len(small_spec()) * len(WLS)


def test_local_fabric_recovers_hung_worker_via_lease_timeout(
        single_process_result):
    fab = LocalFabric(campaign(), n_workers=2, seed=3, lease_timeout_s=5.0,
                      fault=FaultInjection(hang_worker=0))
    res = fab.run()
    assert res.complete
    assert fab.coord.stats["lost_workers"] == [0]
    assert fab.coord.stats["reissued_tiles"] == 1
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)


def test_local_fabric_hang_requires_fake_clock():
    with pytest.raises(ValueError, match="FakeClock"):
        LocalFabric(campaign(), clock=__import__("time").monotonic,
                    fault=FaultInjection(hang_worker=0))


def test_local_fabric_fused_jit_identity():
    """The fused float32 sweep distributes bitwise too (same compiled fn,
    same padded tile shapes, order-independent merges)."""
    single = campaign(evaluator="jit").run()
    res = LocalFabric(campaign(evaluator="jit"), n_workers=3, seed=5,
                      fault=FaultInjection(kill_worker=2, kill_after_tiles=1,
                                           duplicate=True)).run()
    assert res.complete
    assert_identical_frontiers(single.frontiers, res.frontiers)


def test_local_fabric_overflow_normalization_identity():
    """A workload whose screened set overflows max_survivors ships the
    host-reduced exact skyline instead; the fold still matches the
    single-process overflow fallback bitwise."""
    single = campaign(evaluator="jit", max_survivors=1).run()
    res = LocalFabric(campaign(evaluator="jit", max_survivors=1),
                      n_workers=2, seed=0).run()
    assert res.complete
    assert_identical_frontiers(single.frontiers, res.frontiers)


# --- distributed checkpoints -------------------------------------------------


def test_fabric_checkpoint_resume_matches_fresh(tmp_path,
                                                single_process_result):
    ckpt = str(tmp_path / "fabric.ckpt.json")
    fab = LocalFabric(campaign(), n_workers=3, seed=2)
    partial = fab.run(max_completions=3, checkpoint_path=ckpt)
    assert not partial.complete

    state = store.load_checkpoint(ckpt)
    assert state["version"] == 1                  # schema unchanged
    done = _expand_intervals(state["fabric"]["done"])
    assert len(done) == 3
    prefix = 0
    while prefix in done:
        prefix += 1
    assert state["next_tile"] == prefix  # contiguous done prefix

    # resume on a DIFFERENT worker count; done tiles are not re-evaluated
    coord = FabricCoordinator.from_checkpoint(ckpt, lease_timeout_s=1e9,
                                              clock=FakeClock())
    assert coord.board.done_tiles == done
    res = LocalFabric(coord, n_workers=2, seed=9).run()
    assert res.complete
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)
    assert res.candidates_evaluated == len(small_spec()) * len(WLS)


def test_plain_campaign_resumes_fabric_checkpoint(tmp_path,
                                                  single_process_result):
    """A fabric checkpoint is a valid single-process checkpoint: next_tile
    is the contiguous done prefix and any out-of-prefix tiles the fabric
    already folded re-merge as exact no-ops."""
    ckpt = str(tmp_path / "fabric.ckpt.json")
    LocalFabric(campaign(), n_workers=3, seed=4).run(max_completions=4,
                                                     checkpoint_path=ckpt)
    resumed = Campaign.from_checkpoint(ckpt)
    res = resumed.run()
    assert res.complete
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)


# --- MultiprocessFabric: real spawn workers ----------------------------------


def test_multiprocess_fabric_death_duplicate_identity(tmp_path,
                                                      single_process_result):
    """One real-process run exercising the whole failure matrix: a worker
    crashes mid-tile (exits without delivering), the first payload is
    delivered twice, a checkpoint is written — and the frontier still
    equals the single-process run bitwise."""
    ckpt = str(tmp_path / "mp.ckpt.json")
    fab = MultiprocessFabric(
        campaign(), n_workers=2, checkpoint_every=2,
        fault=FaultInjection(kill_worker=1, kill_after_tiles=1,
                             duplicate=True))
    res = fab.run(checkpoint_path=ckpt)
    assert res.complete
    assert fab.stats["lost_workers"] == [1]
    assert fab.stats["duplicates"] == 1
    assert fab.stats["reissued_tiles"] >= 1
    assert_identical_frontiers(single_process_result.frontiers, res.frontiers)
    assert res.candidates_evaluated == len(small_spec()) * len(WLS)
    # the final checkpoint records every tile done
    state = store.load_checkpoint(ckpt)
    assert _expand_intervals(state["fabric"]["done"]) == list(
        range(small_spec().n_tiles()))


def test_multiprocess_fabric_refuses_tpu_parent(monkeypatch):
    """On a TPU host the parent holds the chip, and spawned workers that
    inherit its environment would reach for it.  The fabric refuses to start
    there with an error that says how to run it, and spawns no process.
    The parent's backend is steered here by replacing
    ``jax.default_backend``."""
    import multiprocessing as mp
    import jax
    before = set(mp.active_children())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        MultiprocessFabric(campaign(), n_workers=2)
    assert set(mp.active_children()) == before


def test_multiprocess_fabric_rejects_hang_injection():
    with pytest.raises(ValueError, match="LocalFabric"):
        MultiprocessFabric(campaign(), fault=FaultInjection(hang_worker=0))
