"""The census cache: keyed by the program's code and the cell; a hit gives
what the lowering wrote."""

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import census  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _artifact(rec):
    return {"hxa": {k: rec[k] for k in ("flops", "hbm_bytes",
                                        "collective_bytes", "wire_bytes")},
            "roofline": {"n_chips": rec["base_chips"]},
            "memory": {"state_gb_per_device": rec["state_gb_per_device"]},
            "wall_s": 1.0}


def test_cache_lowers_once_and_a_hit_equals_the_lowering(tmp_path,
                                                        monkeypatch):
    recs = {(r["arch"], r["shape"]): r
            for r in json.loads((DATA / "census.json").read_text())}
    cells = list(recs)[:3]
    lowered = []

    def lower(arch, shape, out):
        lowered.append((arch, shape))
        (pathlib.Path(out) / census.artifact_name(arch, shape)).write_text(
            json.dumps(_artifact(recs[(arch, shape)])))

    monkeypatch.setattr(census, "CACHE", str(tmp_path))
    first = census.ensure(cells, lower=lower)
    assert (first["hit"], first["lowered"]) == (0, 3)
    fresh = census.load(cells)
    again = census.ensure(cells, lower=lower)
    assert (again["hit"], again["lowered"]) == (3, 0)
    assert lowered == cells
    assert census.load(cells) == fresh
    assert fresh == [{k: recs[c][k] for k in fresh[0]} for c in cells]
    # nothing but the artifacts is left in the cache directory
    assert sorted(p.name for p in pathlib.Path(first["dir"]).iterdir()) == \
        sorted(census.artifact_name(*c) for c in cells)


def test_code_hash_follows_the_code(tmp_path):
    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "a.py").write_text("x = 1\n")
    (src / "notes.txt").write_text("not code\n")
    h = census.code_hash(str(src))
    assert census.code_hash(str(src)) == h
    (src / "notes.txt").write_text("changed\n")
    assert census.code_hash(str(src)) == h
    (src / "pkg" / "a.py").write_text("x = 2\n")
    assert census.code_hash(str(src)) != h


def test_scaled_census_scales_the_work_only():
    rec = json.loads((DATA / "census.json").read_text())[0]
    s = census.scaled(rec, 1.05)
    for k in ("flops", "hbm_bytes", "collective_bytes", "wire_bytes"):
        assert s[k] == rec[k] * 1.05
    for k in ("base_chips", "state_gb_per_device", "arch", "shape"):
        assert s[k] == rec[k]
