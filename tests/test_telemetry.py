"""Telemetry subsystem tests: metrics registry, span tracer, and the one
rule that keeps observability safe — instrumentation is a reading, never an
input.  The headline identity: a fully instrumented campaign's frontier is
BITWISE-equal to an uninstrumented one (``NullTelemetry`` default), so the
registry/tracer can ride every hot path without touching results."""

import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import dse
from repro.dse_campaign import (Campaign, CampaignConfig, FakeClock,
                                LocalFabric, MultiprocessFabric, SliceVariant,
                                SpaceSpec, frontiers_identical)
from repro.telemetry import (MetricsRegistry, NullTelemetry, SpanTracer,
                             Telemetry, coerce_telemetry, metric_value)
from repro.telemetry.trace import NULL_SPAN
from tools import trace_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


# ---------------------------------------------------------------- metrics --


class TestMetricsRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry(clock=FakeClock(5.0))
        c = reg.counter("tiles_total")
        c.inc()
        c.inc(3)
        assert c.value == 4
        assert c.updated_at == 5.0
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labeled_series_are_distinct(self):
        reg = MetricsRegistry()
        a = reg.counter("queries_total", path="index_exact")
        b = reg.counter("queries_total", path="mini_campaign")
        a.inc(2)
        b.inc(5)
        assert a is not b and a.value == 2 and b.value == 5
        # same (name, labels) -> the SAME series object (held-series idiom)
        assert reg.counter("queries_total", path="index_exact") is a

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("busy_s")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("busy_s")

    def test_gauge_set_and_add(self):
        reg = MetricsRegistry()
        g = reg.gauge("worker_busy_s", worker=0)
        assert g.value is None
        g.add(1.5)
        g.add(0.5)
        assert g.value == 2.0
        g.set(7.0)
        assert g.value == 7.0

    def test_histogram_quantile_matches_numpy(self):
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=-6.0, sigma=1.3, size=513)
        reg = MetricsRegistry()
        h = reg.histogram("latency_s")
        for s in samples:
            h.observe(float(s))
        for q in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0):
            np.testing.assert_allclose(
                h.quantile(q), np.percentile(samples, q * 100),
                rtol=1e-12, err_msg=f"q={q}")
        assert h.count == samples.size
        np.testing.assert_allclose(h.sum, samples.sum())
        assert h.min == samples.min() and h.max == samples.max()

    def test_histogram_ring_bounds_memory_but_totals_exact(self):
        reg = MetricsRegistry()
        h = reg.histogram("latency_s", max_samples=64)
        for i in range(1000):
            h.observe(float(i))
        assert len(h.samples) == 64
        assert h.samples == [float(i) for i in range(936, 1000)]
        assert h.count == 1000 and h.sum == sum(range(1000))
        assert h.min == 0.0 and h.max == 999.0

    def test_histogram_empty_and_bad_q(self):
        h = MetricsRegistry().histogram("x_s")
        assert h.quantile(0.5) is None
        h.observe(1.0)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_snapshot_roundtrips_through_json(self):
        tel = Telemetry(clock=FakeClock(1.0))
        tel.counter("tiles_total").inc(3)
        tel.gauge("ema_s").set(0.25)
        tel.histogram("lat_s", path="exact").observe(0.5)
        snap = json.loads(json.dumps(tel.snapshot()))
        assert metric_value(snap, "tiles_total") == 3
        assert metric_value(snap, "ema_s", kind="gauges") == 0.25
        row = metric_value(snap, "lat_s", kind="histograms", path="exact")
        assert row["count"] == 1 and row["p50"] == 0.5
        assert metric_value(snap, "absent_total", default=-1) == -1

    def test_fakeclock_snapshots_deterministic(self):
        def activity():
            tel = Telemetry(clock=FakeClock(10.0))
            c = tel.counter("tiles_total")
            for _ in range(5):
                tel.clock.advance(0.125)
                c.inc()
                tel.histogram("tile_wall_s").observe(0.125)
            return tel.snapshot()

        a, b = activity(), activity()
        assert a == b                       # identical activity, identical snap
        assert a["clock_s"] == 10.625
        assert metric_value(a, "tiles_total") == 5


# ----------------------------------------------------------------- tracer --


class TestSpanTracer:
    def test_nesting_parent_depth(self):
        tr = SpanTracer(clock=FakeClock(0.0))
        with tr.span("tile_eval", tile=3) as outer:
            tr.clock.advance(0.5)
            with tr.span("launch") as inner:
                tr.clock.advance(0.25)
        outer_r, inner_r = {r.name: r for r in tr.records}["tile_eval"], \
            {r.name: r for r in tr.records}["launch"]
        assert outer_r.parent == -1 and outer_r.depth == 0
        assert inner_r.parent == outer_r.sid and inner_r.depth == 1
        assert outer_r.dur == 0.75 and inner_r.dur == 0.25
        assert outer_r.attrs == {"tile": 3}
        assert inner_r.sid == outer.sid + 1 == inner.sid

    def test_ring_evicts_oldest(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            with tr.span("s", i=i):
                pass
        recs = tr.records
        assert len(recs) == 8
        assert [r.attrs["i"] for r in recs] == list(range(12, 20))

    def test_threads_nest_independently(self):
        tr = SpanTracer()
        done = threading.Event()

        def other():
            with tr.span("worker_root"):
                done.wait(5.0)

        t = threading.Thread(target=other)
        with tr.span("main_root"):
            t.start()
            done.set()
            t.join()
        by_name = {r.name: r for r in tr.records}
        # the worker's span is a root on ITS thread, not a child of main
        assert by_name["worker_root"].parent == -1
        assert by_name["worker_root"].depth == 0
        assert by_name["worker_root"].thread_id != \
            by_name["main_root"].thread_id

    def test_chrome_trace_schema(self, tmp_path):
        tel = Telemetry(clock=FakeClock(100.0))
        with tel.span("tile_eval", tile=0):
            tel.clock.advance(0.010)
            with tel.span("launch"):
                tel.clock.advance(0.002)
        path = tel.export_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"] == "repro-campaign"
        xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        te, la = xs["tile_eval"], xs["launch"]
        assert te["ts"] == 0.0 and te["dur"] == pytest.approx(12_000)
        assert la["ts"] == pytest.approx(10_000)
        assert la["dur"] == pytest.approx(2_000)
        assert la["args"]["parent"] == te["args"]["sid"]
        assert la["args"]["depth"] == te["args"]["depth"] + 1
        assert te["args"]["tile"] == 0

    def test_trace_report_check_passes_and_catches_violations(self, tmp_path):
        tel = Telemetry()
        with tel.span("tile_eval"):
            with tel.span("launch"):
                pass
        path = tel.export_trace(str(tmp_path / "t.json"))
        events = trace_report.load_events(path)
        assert trace_report.check(events, ["tile_eval"]) == []
        assert trace_report.check(events, ["lease"]) != []  # missing name
        bad = [dict(e) for e in events]
        for e in bad:
            if e["name"] == "launch":
                e["args"] = dict(e["args"], depth=5)
        assert any("depth" in err for err in trace_report.check(bad, []))

    def test_null_span_is_shared_noop(self):
        tel = NullTelemetry()
        assert tel.span("anything", tile=1) is NULL_SPAN
        with tel.span("x"):
            pass
        assert tel.tracer.records == []
        assert tel.tracer.chrome_trace()["traceEvents"] == []

    def test_coerce_telemetry_fresh_per_owner(self):
        a, b = coerce_telemetry(None), coerce_telemetry(None)
        assert a is not b                   # per-owner registries: no aliasing
        t = Telemetry()
        assert coerce_telemetry(t) is t


# --------------------------------------------------- instrumented == plain --


class TestInstrumentationIsAReading:
    def test_instrumented_frontier_bitwise_equals_uninstrumented(self):
        spec = small_spec()
        plain = Campaign(WLS, spec, constraint=CONS, evaluator="numpy").run()
        tel = Telemetry()
        traced = Campaign(WLS, spec, constraint=CONS, evaluator="numpy",
                          telemetry=tel).run()
        for key in plain.frontiers:
            assert frontiers_identical(plain.frontiers[key],
                                       traced.frontiers[key])
        # and the instrumented run actually observed itself
        assert metric_value(tel.snapshot(), "campaign_tiles_total") == \
            traced.tiles_done
        assert any(r.name == "tile_eval" for r in tel.tracer.records)

    def test_nulltelemetry_metrics_still_count(self):
        # the disabled path keeps REAL counters: fused_launches (back-compat
        # surface, tests/test_selection.py reads it) must count as before
        campaign = Campaign(WLS, small_spec(), constraint=CONS,
                            evaluator="numpy")
        campaign.run()
        ev = campaign.engine
        assert isinstance(ev.telemetry, NullTelemetry)
        assert metric_value(ev.telemetry.snapshot(),
                            "evaluator_candidates_total") == \
            len(WLS) * len(small_spec())

    def test_local_fabric_trace_has_fabric_spans(self, tmp_path):
        tel = Telemetry()
        campaign = Campaign(WLS, small_spec(), constraint=CONS,
                            evaluator="numpy", telemetry=tel)
        LocalFabric(campaign, n_workers=2, seed=0).run(
            checkpoint_path=str(tmp_path / "ckpt.json"))
        names = {r.name for r in tel.tracer.records}
        assert {"tile_eval", "lease", "deliver", "checkpoint_write"} <= names
        errors = trace_report.check(
            trace_report.load_events(
                tel.export_trace(str(tmp_path / "trace.json"))),
            trace_report.DEFAULT_REQUIRED)
        assert errors == []

    def test_multiprocess_workers_ship_metrics_snapshots(self, tmp_path):
        campaign = Campaign(WLS, small_spec(), constraint=CONS,
                            evaluator="numpy")
        fabric = MultiprocessFabric(campaign, n_workers=2)
        result = fabric.run(checkpoint_path=str(tmp_path / "ckpt.json"))
        assert result.complete
        wm = fabric.stats["worker_metrics"]
        assert set(wm) == {0, 1}
        total_tiles = sum(
            metric_value(snap, "worker_tiles_total", default=0)
            for snap in wm.values())
        assert total_tiles == campaign.space.n_tiles()
        for w, snap in wm.items():
            busy = metric_value(snap, "worker_busy_s_total")
            assert busy is not None and busy >= 0.0
            # stats' busy ledger uses the worker-shipped totals
            assert fabric.stats["worker_busy_s"][w] == busy


# ------------------------------------------ the stages between two launches --

# span -> the parent span it nests under (None: a root)
LAUNCH_STAGES = {"dispatch": "launch", "device_wait": "launch",
                 "fetch": "launch", "host_compact": "launch"}
PALLAS_STAGES = {"pack": "launch"}
CAMPAIGN_STAGES = {"overflow_reduce": "compact",
                   "fetch_full": "overflow_reduce", "materialize": "merge",
                   "fold": "merge", "snapshot": "merge", "tile_wait": None,
                   "tile_slice": None, "lower": None, "compile": None}
# a constraint of its own per evaluator, so that its sweep compiles afresh
# under the test's tracing telemetry and JAX reports lowering + compiling
FRESH_POWER_W = {"pallas": 50_101.0, "jit": 50_102.0}


def stage_config(evaluator, max_survivors=4):
    return CampaignConfig(
        space=small_spec(), evaluator=evaluator,
        constraint=dse.Constraint(max_power_w=FRESH_POWER_W[evaluator]),
        max_survivors=max_survivors)


def parents(records):
    by_sid = {r.sid: r for r in records}
    return {(r.name, by_sid[r.parent].name if r.parent in by_sid else None)
            for r in records}


class TestLaunchStageSpans:
    @pytest.mark.parametrize("evaluator", ["pallas", "jit"])
    def test_every_stage_span_under_its_parent(self, evaluator, tmp_path):
        tel = Telemetry()
        traced = Campaign(WLS, stage_config(evaluator), telemetry=tel).run()
        records = tel.tracer.records
        expected = {**LAUNCH_STAGES, **CAMPAIGN_STAGES}
        if evaluator == "pallas":
            expected.update(PALLAS_STAGES)
        got = parents(records)
        for name, parent in expected.items():
            assert (name, parent) in got, (name, parent, sorted(got))
        if evaluator == "jit":     # it packs nothing: jit takes the columns
            assert not {n for n, _ in got} & set(PALLAS_STAGES)
        # the request id: every root span of the run carries the same one
        seqs = {r.attrs["campaign"] for r in records
                if r.name in ("tile_eval", "tile_wait")}
        assert len(seqs) == 1
        n_tiles = traced.tiles_done
        counts = {n: sum(r.name == n for r in records) for n in expected}
        assert counts["dispatch"] == counts["fetch"] == n_tiles
        assert counts["materialize"] == counts["fold"] == n_tiles * len(WLS)
        # every overflowed workload has its span and its counter increment
        overflows = tel.counter("evaluator_overflows_total").value
        assert counts["overflow_reduce"] == overflows > 0
        # one copy of the full rows per launch that overflowed, at most
        assert (0 < counts["fetch_full"]
                == tel.counter("evaluator_full_fetches_total").value
                <= min(n_tiles, overflows))
        assert {r.attrs["fun_name"] for r in records if r.name == "lower"}
        slices = [r for r in records if r.name == "tile_slice"]
        main = {r.thread_id for r in records if r.name == "tile_eval"}
        assert {r.thread_id for r in slices}.isdisjoint(main)
        events = trace_report.load_events(
            tel.export_trace(str(tmp_path / "trace.json")))
        assert trace_report.check(events, list(expected)) == []

    @pytest.mark.parametrize("evaluator", ["pallas", "jit"])
    def test_frontiers_bitwise_equal_with_telemetry_on_off_and_null(
            self, evaluator):
        cfg = stage_config(evaluator, max_survivors=8)
        runs = [Campaign(WLS, cfg, telemetry=t).run()
                for t in (Telemetry(), None, NullTelemetry())]
        for other in runs[1:]:
            for key in runs[0].frontiers:
                assert frontiers_identical(runs[0].frontiers[key],
                                           other.frontiers[key])

    def test_launch_children_are_its_stages_and_untraced_adds_nothing(
            self, monkeypatch):
        """Traced, ``launch`` holds exactly its five stages (Pallas path);
        untraced, the launch makes no explicit copy and no separate wait:
        the inputs cross inside the jitted call, the first fetch waits."""
        import jax
        tel = Telemetry()
        Campaign(WLS, stage_config("pallas"), telemetry=tel).run()
        records = tel.tracer.records
        launches = {r.sid for r in records if r.name == "launch"}
        assert {r.name for r in records if r.parent in launches} == {
            "pack", "dispatch", "device_wait", "fetch", "host_compact"}

        def refuse(name):
            def call(*args, **kwargs):
                raise AssertionError(f"an untraced launch called jax.{name}")
            return call
        for name in ("device_put", "block_until_ready"):
            monkeypatch.setattr(jax, name, refuse(name))
        assert Campaign(WLS, stage_config("pallas")).run().complete

    def test_null_telemetry_records_nothing_and_registers_no_listener(self):
        """In a fresh process: a campaign under ``NullTelemetry`` leaves
        no span and no ``jax.monitoring`` listener; the first tracing
        ``Telemetry`` registers exactly one."""
        code = """
from jax._src import monitoring
import repro.telemetry as telemetry
from repro.core import dse
from repro.dse_campaign import Campaign, CampaignConfig, SpaceSpec
wl = dse.Workload("qwen3_14b", "train_4k", {"flops": 3.2e14,
    "hbm_bytes": 4.5e13, "collective_bytes": 5e11, "wire_bytes": 7e11},
    256, 0.5)
listeners = lambda: [f for f in monitoring.get_event_time_span_listeners()
                     if f is telemetry._on_jax_time_span]
null = telemetry.NullTelemetry()
cfg = CampaignConfig(space=SpaceSpec(chips=("tpu-v5e",), chip_counts=(16,),
                     freq_points=3, chunk_size=16), evaluator="pallas")
Campaign([wl], cfg, telemetry=null).run()
assert null.tracer.records == [] and not telemetry._listening
assert listeners() == []
telemetry.Telemetry(); telemetry.Telemetry()
assert telemetry._listening and len(listeners()) == 1
print("ok")
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.join(REPO, "src"),
                        os.environ.get("PYTHONPATH", "")]))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr

    def test_dead_telemetry_leaves_the_listener_audience(self):
        import gc
        import repro.telemetry as telemetry
        tel = Telemetry()
        assert tel in telemetry._live
        n = len(telemetry._live)
        del tel
        gc.collect()
        assert len(telemetry._live) == n - 1

    def test_record_puts_a_finished_span_in_as_a_root(self):
        tr = SpanTracer(clock=FakeClock(10.0), wall_clock=FakeClock(1000.0))
        with tr.span("outer"):
            tr.record("queue_wait", 9.5, 10.0, qid=3)
        rec = {r.name: r for r in tr.records}["queue_wait"]
        assert rec.parent == -1 and rec.depth == 0 and rec.dur == 0.5
        assert rec.attrs == {"qid": 3}

    def test_a_compile_is_recorded_only_where_a_span_is_open(self):
        """JAX's lowering and compiling land in the tracing ``Telemetry``
        whose span is open on the compiling thread, and in no other."""
        import jax
        import jax.numpy as jnp
        busy, idle = Telemetry(), Telemetry()
        x = jnp.arange(11.0)
        with busy.span("request"):
            jax.jit(lambda v: v * 3.0 + 1.375)(x).block_until_ready()
        recs = {r.name: r for r in busy.tracer.records}
        assert {"request", "lower", "compile"} <= set(recs)
        req = recs["request"]
        for name in ("lower", "compile"):
            r = recs[name]
            assert r.parent == -1 and r.attrs["fun_name"]
            assert 0 <= r.dur and req.t0 <= r.t1 <= req.t1
        assert idle.tracer.records == []

    def test_a_compile_on_another_thread_is_not_recorded(self):
        """A span open on this thread does not claim a compile that another
        thread runs."""
        import threading

        import jax
        import jax.numpy as jnp
        tel = Telemetry()
        x = jnp.arange(13.0)
        with tel.span("request"):
            t = threading.Thread(target=lambda: jax.jit(
                lambda v: v * 5.0 - 0.625)(x).block_until_ready())
            t.start()
            t.join()
        assert {r.name for r in tel.tracer.records} == {"request"}

    def test_span_shows_in_a_cpu_profile_as_repro_name(self, tmp_path):
        import jax
        tel = Telemetry()
        with jax.profiler.trace(str(tmp_path)):
            with tel.span("launch"):
                with tel.span("device_wait"):
                    pass
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        names = {e.name for plane in
                 jax.profiler.ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events}
        assert {"repro.launch", "repro.device_wait"} <= names


def test_trace_report_self_time_subtracts_children_coverage():
    tel = Telemetry(clock=FakeClock(0.0))
    clock = tel.clock
    with tel.span("tile_eval"):
        clock.advance(1.0)
        with tel.span("launch"):
            with tel.span("pack"):
                clock.advance(2.0)
            clock.advance(0.5)
            with tel.span("fetch"):
                clock.advance(1.5)
        with tel.span("merge"):
            clock.advance(3.0)
    events = tel.chrome_trace()["traceEvents"]
    events = [e for e in events if e["ph"] == "X"]
    agg = trace_report.summarize(events)
    assert agg["tile_eval"]["self_us"] == pytest.approx(1.0e6)
    assert agg["launch"]["self_us"] == pytest.approx(0.5e6)
    assert agg["pack"]["self_us"] == pytest.approx(2.0e6)
    tree = trace_report.stage_tree(events)
    assert tree[("tile_eval", "launch", "fetch")]["total_us"] == \
        pytest.approx(1.5e6)
    assert sum(row["self_us"] for p, row in tree.items()
               if p[0] == "tile_eval") == pytest.approx(8.0e6)
