"""The workloads' compile census, kept in a cache inside the checkout.

The census lowers each (arch, shape) cell at its published widths on 512
host placeholder devices, which costs tens of seconds of host time.  It
depends only on the program's code and the cell, so the first run of a cell
in a checkout lowers it and writes the artifact under
``bench/.cache/census/<code hash>/``; every later run reads it back.  The
code hash covers every file under ``src/repro``, so a change to the program
lowers afresh.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Sequence

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


def code_hash(src: str = os.path.join(ROOT, "src", "repro")) -> str:
    """sha256 over the relative paths and bytes of every ``.py`` file of
    the program, in sorted order."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(src):
        dirnames.sort()
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            h.update(os.path.relpath(path, src).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def artifact_name(arch: str, shape: str) -> str:
    return f"{arch}__{shape}__pod1.json"


def _lower(arch: str, shape: str, out_dir: str) -> None:
    """Lower one cell with the program's dry-run into ``out_dir``."""
    os.environ.setdefault("REPRO_SAVE_HLO", "0")
    from repro.launch import dryrun
    dryrun.run_cell(arch, shape, multi_pod=False, out_dir=out_dir)


def census_dir(key: str) -> str:
    return os.path.join(CACHE, "census", key)


def ensure(cells: Sequence[Sequence[str]], lower=_lower) -> Dict[str, int]:
    """Make sure every cell's artifact is in the cache; returns how many
    were found (``hit``) and lowered (``lowered``).  An artifact is written
    under a temporary name and renamed, so a run cut short leaves none."""
    out = census_dir(code_hash())
    os.makedirs(out, exist_ok=True)
    hit = lowered = 0
    for arch, shape in cells:
        path = os.path.join(out, artifact_name(arch, shape))
        if os.path.exists(path):
            hit += 1
            continue
        tmp = os.path.join(out, f".lowering-{arch}__{shape}")
        os.makedirs(tmp, exist_ok=True)
        lower(arch, shape, tmp)
        os.replace(os.path.join(tmp, artifact_name(arch, shape)), path)
        os.rmdir(tmp)
        lowered += 1
    return {"hit": hit, "lowered": lowered, "dir": out}


def load(cells: Sequence[Sequence[str]]) -> List[Dict]:
    """The census of each cell, in order: the artifact's fields that the
    sweep reads, as plain numbers (the reference's input too)."""
    out = census_dir(code_hash())
    records = []
    for arch, shape in cells:
        with open(os.path.join(out, artifact_name(arch, shape))) as f:
            art = json.load(f)
        records.append(census_record(arch, shape, art))
    return records


def census_record(arch: str, shape: str, art: Dict) -> Dict:
    """The sweep's inputs from one dry-run artifact."""
    return {"arch": arch, "shape": shape,
            "flops": float(art["hxa"]["flops"]),
            "hbm_bytes": float(art["hxa"]["hbm_bytes"]),
            "collective_bytes": float(art["hxa"]["collective_bytes"]),
            "wire_bytes": float(art["hxa"]["wire_bytes"]),
            "base_chips": int(art["roofline"]["n_chips"]),
            "state_gb_per_device": float(
                art["memory"]["state_gb_per_device"])}


def scaled(record: Dict, scale: float) -> Dict:
    """A novel family: the census's work scaled by ``scale``, as the
    serving benchmark's perturbation does (slice and state unchanged)."""
    out = dict(record)
    for k in ("flops", "hbm_bytes", "collective_bytes", "wire_bytes"):
        out[k] = record[k] * scale
    return out
