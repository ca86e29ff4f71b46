"""Driver of a configuration that runs exact campaigns back to back over
censuses that split their collective wire bytes by mesh axis.

The campaign driver's ``Cell`` (set-up, window, traffic, sample), with each
workload carrying its census's ``coll_model_share`` into the program and the
check made against ``bench/reference_split.py``.  A program whose cost model
takes no such share, or a census without the split, is refused at set-up,
before anything is lowered or run.  A traced window also reads the
program's evaluator counters into ``obs["counters"]``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

from bench import census as census_mod
from bench import check, reference_split
from bench.drivers import campaign

# the TileEvaluator counters a traced window sums over its campaigns
COUNTERS = ("evaluator_candidates_total", "evaluator_split_evals_total")


def with_share(wl, record: Dict):
    """``wl`` with the census's model-axis share in its base analysis."""
    return dataclasses.replace(wl, base_analysis=dict(
        wl.base_analysis, coll_model_share=record["coll_model_share"]))


class Cell(campaign.Cell):
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool,
                 census):
        from repro.core import costmodel
        if "coll_model_share" not in costmodel.WL_COLS:
            raise RuntimeError("the program's cost model takes no census "
                               "split (costmodel.WL_COLS has no "
                               "coll_model_share)")

        def split_census(cells):
            records = census(cells)
            if all("coll_model_share" in r for r in records):
                return records
            return reference_split.load(cells)

        super().__init__(cfg, mix, seed, traced, split_census)
        self.telemetries: List = []
        # the campaign driver's window builds each campaign and its
        # telemetry through these two; the campaigns get the census shares
        self._Campaign = self._campaign
        self._Telemetry = self._telemetry

    def _campaign(self, wls, config, telemetry=None):
        from repro.dse_campaign import Campaign
        return Campaign([with_share(wl, r)
                         for wl, r in zip(wls, self.records)], config,
                        telemetry=telemetry)

    def _telemetry(self):
        from repro.telemetry import Telemetry
        tel = Telemetry()
        self.telemetries.append(tel)
        return tel

    def window(self, t0: float, seconds: float) -> Dict:
        self.telemetries = []
        obs = super().window(t0, seconds)
        if self.telemetries:
            obs["counters"] = {n: sum(t.counter(n).value
                                      for t in self.telemetries)
                               for n in COUNTERS}
        return obs

    def check(self, served_by: str = "program") -> Dict:
        """The comparison numbers over the sampled campaigns' frontiers,
        against the split reference; ``served_by="control"`` puts the
        reference, in bfloat16, in the program's place."""
        import ml_dtypes
        space = reference_split.Space(self.cfg)
        cols = space.arrays()
        readings = []
        self.n_checked = 0
        for i in self.sample():
            d = self.done[i]
            for rec, s in zip(self.records, d["scales"]):
                rec_s = census_mod.scaled(rec, s)
                cfg = reference_split.split_config(self.cfg, rec_s)
                ref = campaign.reference_question(space, cols, rec_s, cfg)
                if served_by == "control":
                    ctl = campaign.reference_question(
                        space, cols, rec_s, cfg, dtype=ml_dtypes.bfloat16)
                    f = ctl["front"]
                    served = (ctl["index"][f], ctl["energy"][f],
                              ctl["latency"][f])
                else:
                    served = d["frontiers"][(rec["arch"], rec["shape"])]
                readings.append(check.compare_frontier(*served, ref))
                self.n_checked += 1
        if not readings:
            return {n: float("inf") for n in check.NUMBERS}
        return check.worst(readings)


def setup(cfg: Dict, mix: Dict, seed: int, traced: bool, census) -> Cell:
    return Cell(cfg, mix, seed, traced, census)
