"""Plain reference of the design-space sweep for censuses that split their
collective wire bytes by mesh axis; importing nothing of the program.

``bench/reference.py`` prices every workload's collective payload with one
model-axis fraction, the configuration's ``sim.coll_model_frac``.  Here each
workload brings its own: ``coll_model_share``, the model-axis share of the
wire bytes its dry-run census attributes to the mesh axes, model / (data +
model).  The model-axis part of the payload rides the model ring and the
rest the data (then pod) ring, as in ``reference.evaluate``, whose float64
arithmetic this module runs with the workload's share in the fraction's
place.

The census reader takes the split straight from the dry-run artifact
(``hxa.wire_bytes_by_axis``); an artifact without one is an error.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import numpy as np

from bench import census as census_cache
from bench import reference
from bench.reference import Space, pareto  # noqa: F401  (the reference's own)


def census_record(arch: str, shape: str, art: Dict) -> Dict:
    """``census.census_record`` of one artifact, with its per-axis wire
    bytes (``data``, ``model`` and the ``both_axes`` part of ``data``) and
    the model-axis share of them."""
    hxa = art["hxa"]
    if "wire_bytes_by_axis" not in hxa:
        raise KeyError(f"the census of {arch} x {shape} has no "
                       "hxa.wire_bytes_by_axis: the program that lowered it "
                       "does not split collectives by mesh axis")
    split = {k: float(v) for k, v in hxa["wire_bytes_by_axis"].items()}
    out = census_cache.census_record(arch, shape, art)
    out["wire_bytes_by_axis"] = split
    out["coll_model_share"] = split["model"] / (split["data"]
                                                + split["model"])
    return out


def load(cells: Sequence[Sequence[str]]) -> List[Dict]:
    """``census_record`` of each cell's artifact in the census cache (the
    artifacts ``census.ensure`` lowered), in order."""
    out = census_cache.census_dir(census_cache.code_hash())
    records = []
    for arch, shape in cells:
        with open(os.path.join(out, census_cache.artifact_name(arch,
                                                               shape))) as f:
            records.append(census_record(arch, shape, json.load(f)))
    return records


def split_config(cfg: Dict, census: Dict) -> Dict:
    """``cfg`` with its model-axis fraction replaced by the census's own
    share."""
    return dict(cfg, sim=dict(cfg["sim"],
                              coll_model_frac=census["coll_model_share"]))


def evaluate(cols: Dict[str, np.ndarray], census: Dict, cfg: Dict,
             dtype=np.float64):
    """(energy_j, latency_s, feasible) of one workload census over the
    candidate columns ``cols``, its collectives split by its own
    ``coll_model_share``; every intermediate is rounded to ``dtype``."""
    return reference.evaluate(cols, census, split_config(cfg, census), dtype)
