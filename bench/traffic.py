"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with the run's seed, into an
endless sequence of requests.

Every seed gets the same work in another order: requests come in blocks,
and each block holds every family once (selection) or scales every workload
(campaign), in an order and with census scales drawn from the seed.

A mix is one of two kinds.

``campaign``: each request is one exact campaign over every workload of the
configuration, each workload's census scaled by a factor drawn uniformly
from ``census_scale``.

``selection``: each request is one query about one of the ``families``
(``"indexed"`` or ``"all"`` of the configuration's cells), each once per
block, with its census scaled by a factor from ``census_scale`` (``null``
asks for the family's own census, an index hit) and a ``deadline_s``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterator

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(abs(int(seed)))


def _scale(rng: np.random.Generator, rng_spec) -> float:
    """A census scale from ``[low, high)``, never exactly 1 (that would be
    the family's own census)."""
    while True:
        s = float(rng.uniform(rng_spec["low"], rng_spec["high"]))
        if s != 1.0:
            return s


def campaign_requests(mix: Dict, seed: int,
                      n_workloads: int) -> Iterator[Dict]:
    rng = rng_for(seed)
    while True:
        yield {"scales": [_scale(rng, mix["census_scale"])
                          for _ in range(n_workloads)]}


def selection_requests(mix: Dict, seed: int, n_indexed: int,
                       n_all: int) -> Iterator[Dict]:
    rng = rng_for(seed)
    n = n_indexed if mix["families"] == "indexed" else n_all
    while True:
        for fam in rng.permutation(n):
            scale = (None if mix["census_scale"] is None
                     else _scale(rng, mix["census_scale"]))
            yield {"family": int(fam), "scale": scale,
                   "deadline_s": mix["deadline_s"]}


def requests(mix: Dict, seed: int, **sizes) -> Iterator[Dict]:
    """The request stream of ``mix`` (dispatch on its ``kind``).  The
    drivers run one client in a closed loop; a mix that asks for another
    loop or more clients is refused."""
    if (mix.get("loop", "closed"), mix.get("clients", 1)) != ("closed", 1):
        raise ValueError(f"the drivers run one closed-loop client, not "
                         f"{mix.get('loop')!r} with {mix.get('clients')} clients")
    if mix["kind"] == "campaign":
        return campaign_requests(mix, seed, sizes["n_workloads"])
    if mix["kind"] == "selection":
        return selection_requests(mix, seed, sizes["n_indexed"],
                                  sizes["n_all"])
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")
