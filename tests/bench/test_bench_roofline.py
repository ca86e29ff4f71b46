"""The byte count behind ``sweep_hbm_roofline`` and the readers that turn
a window's observations into metrics."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

roofline = harness.load_module("metrics", "sweep_hbm_roofline")
V5E = harness.load_peaks("TPU v5 lite")


def test_sweep_bytes_of_one_default_campaign():
    n, w = 125_440, 5
    # 18 float32 columns read per candidate, 3 float32 written per
    # candidate and workload
    assert roofline.sweep_bytes(n, n * w) == 4 * (18 * n + 3 * n * w) \
        == 16_558_080


def test_roofline_share_of_a_window():
    obs = {"sweep": {"candidates": 125_440, "candidate_workloads": 627_200},
           "peaks": V5E, "busy_s": 141e-6}
    least = 16_558_080 / 819e9
    assert roofline.read(obs) == pytest.approx(least / 141e-6 * 100)
    assert roofline.read({**obs, "busy_s": None}) is None
    assert roofline.read({k: v for k, v in obs.items() if k != "peaks"}) \
        is None


def test_end_to_end_readers():
    obs = {"kind": "campaign", "window_start": 10.0,
           "completions": [10.5, 11.0, 12.0],
           "candidate_evals": [100, 100, 100], "setup_s": 7.5}
    assert harness.load_module("metrics", "campaign_cand_per_s").read(obs) \
        == pytest.approx(150.0)
    assert harness.load_module("metrics", "setup_s").read(obs) == 7.5
    q = {"kind": "selection", "latencies_s": [0.001] * 19 + [0.5]}
    assert harness.load_module("metrics", "query_p50_ms").read(q) == \
        pytest.approx(1.0)
    assert harness.load_module("metrics", "query_p95_ms").read(q) == \
        pytest.approx(1.0 + 0.05 * 499.0)
    assert harness.load_module("metrics", "campaign_cand_per_s").read(q) \
        is None


def test_span_and_idle_readers():
    obs = {"spans": {"launch": [0.036, 4], "merge": [0.024, 4],
                     "index_lookup": [0.002, 20]},
           "busy_s": 0.25, "window_s": 10.0}
    read = lambda n: harness.load_module("metrics", n).read(obs)
    assert read("launch_ms_per_tile") == pytest.approx(9.0)
    assert read("merge_ms_per_tile") == pytest.approx(6.0)
    assert read("index_lookup_ms") == pytest.approx(0.1)
    assert read("mini_campaign_ms") is None
    assert read("device_idle.campaign") == pytest.approx(97.5)
    assert read("device_idle.novel") == pytest.approx(97.5)
    assert harness.load_module("metrics", "device_idle.novel").read({}) is None
