"""The reduction from a profiler trace to device readings: on synthetic
events, and the reading of short traces recorded on the CPU
(``data/cpu-small.xplane.pb``) and on one TPU v5e
(``data/chip-small.xplane.pb``)."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_busy_is_the_union_of_intervals():
    assert trace.merged([]) == []
    assert trace.merged([(0, 10), (5, 15), (20, 30)]) == [(0, 15), (20, 30)]
    assert trace.merged([(20, 30), (0, 40)]) == [(0, 40)]
    assert trace.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_gaps_go_to_the_innermost_span():
    gaps = [(10, 20), (30, 40), (50, 60)]
    spans = [("bench.campaign", 0, 100), ("launch", 25, 45),
             ("merge", 48, 70)]
    got = trace.attribute(gaps, spans)
    assert got == pytest.approx({"bench.campaign": 10e-9, "launch": 10e-9,
                                 "merge": 10e-9})
    assert trace.attribute(gaps, []) == pytest.approx({"host, no span":
                                                       30e-9})


def test_reduce_on_synthetic_events():
    events = {"devices": {"/device:TPU:0": {
        "modules": [("m", 100, 200), ("m", 150, 300), ("m", 500, 600)],
        "ops": [("dse_sweep", 100, 180), ("fusion", 180, 200),
                ("dse_sweep", 500, 580)]}},
        "host": [("bench.anchor", 0, 1), ("bench.campaign", 0, 1000)]}
    red = trace.reduce(events, window=(0, 1000),
                       extra_spans=[("merge", 300, 500)])
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["device_ops"][0] == ["dse_sweep", pytest.approx(160e-9)]
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"bench.campaign": 500e-9,
                                  "merge": 200e-9})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


def test_reading_a_trace_recorded_on_the_cpu():
    """``data/cpu-small.xplane.pb``: four tiny campaigns traced on the CPU,
    under the profiler options of a traced run.  The host annotations and
    the anchor are read from the file; with no TPU plane the reduction
    refuses it."""
    events = trace.read(str(DATA / "cpu-small.xplane.pb"))
    assert events["devices"] == {}
    names = [n for n, _, _ in events["host"]]
    assert names == [trace.ANCHOR] + ["bench.campaign"] * 4
    anchor = trace.anchor_ns(events)
    campaigns = sorted(s for s in events["host"] if s[0] == "bench.campaign")
    assert all(anchor < s < e for _, s, e in campaigns)
    assert all(e1 <= s2 for (_, _, e1), (_, s2, _) in zip(campaigns,
                                                         campaigns[1:]))
    with pytest.raises(ValueError):
        trace.reduce(events)


def test_reading_a_trace_recorded_on_the_chip():
    """``data/chip-small.xplane.pb``: half a second of select-novel traced
    on one TPU v5e by ``bench/readings.py --record-trace``; the run's own
    reduction of it is ``data/chip-small.expect.json``.  Reading the file
    again, with the recorded host spans moved onto the trace's clock by
    the anchor, gives the same busy time, device ops and idle gaps."""
    import json
    expect = json.loads((DATA / "chip-small.expect.json").read_text())
    events = trace.read(str(DATA / "chip-small.xplane.pb"))
    assert list(events["devices"]) == ["/device:TPU:0"]
    a = trace.anchor_ns(events)
    assert a is not None

    def to_ns(t):
        return int(a + (t - expect["t_anchor"]) * 1e9)

    spans = [(n, to_ns(s), to_ns(e)) for n, s, e in expect["host_spans"]]
    red = trace.reduce(events, (to_ns(expect["window_start"]),
                                to_ns(expect["window_end"])), spans)
    assert 0 < red["busy_s"] < expect["device"]["window_s"]
    assert red["busy_s"] == pytest.approx(expect["device"]["busy_s"])
    assert red["device_ops"] == [[n, pytest.approx(s)] for n, s in
                                 expect["breakdown"]["device_ops"]]
    assert "dse_sweep" in red["device_ops"][0][0]
    assert red["idle_gaps"] == [[n, pytest.approx(s)] for n, s in
                                expect["breakdown"]["idle_gaps"]]
