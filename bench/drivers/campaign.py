"""Driver of a configuration that runs exact campaigns back to back.

Set-up: the census of the configuration's cells, the ``CampaignConfig``
the configuration states, and one whole campaign as warm-up (it compiles or
loads every program the window runs).  Window: a closed loop with one
client; each request is one ``Campaign.run`` over every workload, each
census scaled by the request's factor, to its final frontiers.  Campaigns
started before the window's end all complete.  Check: the final frontiers
of a sample of the window's campaigns, drawn from the seed with the last
one always in it, against the plain reference over the whole space.
"""

from __future__ import annotations

import contextlib
import sys
import time
from typing import Dict, List

import numpy as np

from bench import census as census_mod
from bench import check, reference, traffic

CHECKED_CAMPAIGNS = 4


def program_config(cfg: Dict):
    """The program's ``CampaignConfig`` as the configuration states it."""
    from repro.core import costmodel, dse
    from repro.dse_campaign import CampaignConfig
    from repro.dse_campaign.space import SliceVariant, SpaceSpec
    sp = cfg["space"]
    space = SpaceSpec(chips=tuple(sp["chips"]),
                      chip_counts=tuple(sp["chip_counts"]),
                      freq_points=int(sp["freq_points"]),
                      mesh_dims=int(sp["mesh_dims"]),
                      variants=tuple(SliceVariant(n, float(s))
                                     for n, s in sp["variants"]),
                      chunk_size=int(sp["chunk_size"]))
    return CampaignConfig(space=space, evaluator=cfg["evaluator"],
                          constraint=dse.Constraint(**cfg["constraint"]),
                          sim=costmodel.SimConfig(**cfg["sim"]))


def workload(record: Dict, tag: str = ""):
    from repro.core import dse
    return dse.Workload(
        arch=record["arch"], shape=record["shape"] + tag,
        base_analysis={k: record[k] for k in ("flops", "hbm_bytes",
                                              "collective_bytes",
                                              "wire_bytes")},
        base_chips=record["base_chips"],
        state_gb_per_device=record["state_gb_per_device"])


def reference_question(space: reference.Space, cols: Dict, record: Dict,
                       cfg: Dict, idx=None, dtype=np.float64) -> Dict:
    """The reference's answer over ``cols`` (the candidates at global
    indices ``idx``, all when None)."""
    e, l, f = reference.evaluate(cols, record, cfg, dtype)
    index = (np.arange(len(space)) if idx is None
             else np.asarray(idx, np.int64))
    return {"index": index, "energy": e, "latency": l,
            "excess": reference.constraint_excess(cols, record, cfg, e, l),
            "front": reference.pareto(e, l, f)}


class Cell:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool,
                 census):
        from repro.dse_campaign import Campaign
        from repro.telemetry import Telemetry
        self._Campaign, self._Telemetry = Campaign, Telemetry
        parts = {}
        t = time.perf_counter()
        self.records = census([tuple(c) for c in cfg["census"]["cells"]])
        parts["census"] = time.perf_counter() - t
        self.cfg, self.mix, self.seed, self.traced = cfg, mix, seed, traced
        self.config = program_config(cfg)
        t = time.perf_counter()
        warm = Campaign([workload(r) for r in self.records], self.config)
        res = warm.run()
        if not res.complete:
            raise RuntimeError("warm-up campaign did not complete")
        parts["warm_up"] = time.perf_counter() - t
        self.setup_parts = parts
        self.n_checked = 0
        self.reset(seed)

    def window(self, t0: float, seconds: float) -> Dict:
        import jax
        Campaign, Telemetry = self._Campaign, self._Telemetry
        space_n = len(self.config.resolved_space)
        n_wl = len(self.records)
        completions, host_spans = [], []
        spans: Dict[str, List[float]] = {}
        failed = attempted = 0
        end = t0 + seconds
        while time.perf_counter() < end:
            req = next(self.requests)
            wls = [workload(census_mod.scaled(r, s))
                   for r, s in zip(self.records, req["scales"])]
            tel = Telemetry() if self.traced else None
            attempted += 1
            ts = time.perf_counter()
            try:
                with (jax.profiler.TraceAnnotation("bench.campaign")
                      if self.traced else contextlib.nullcontext()):
                    res = Campaign(wls, self.config, telemetry=tel).run()
            except Exception as exc:  # a failed request is counted
                failed += 1
                print(f"[bench] campaign failed: {exc!r}", file=sys.stderr)
                continue
            te = time.perf_counter()
            completions.append(te)
            if not res.complete:
                failed += 1
            self.done.append({"scales": req["scales"], "frontiers": {
                k: (f.indices, f.energy_j, f.latency_s)
                for k, f in res.frontiers.items()}})
            if tel is not None:
                host_spans.append(("bench.campaign", ts, te))
                for r in tel.tracer.records:
                    s = spans.setdefault(r.name, [0.0, 0])
                    s[0] += r.dur
                    s[1] += 1
                    host_spans.append((r.name, r.t0, r.t1))
        last = completions[-1] if completions else time.perf_counter()
        return {"kind": "campaign", "window_start": t0, "window_end": last,
                "attempted": attempted, "failed": failed,
                "completions": completions,
                "candidate_evals": [space_n * n_wl] * len(completions),
                "sweep": {"candidates": space_n * len(completions),
                          "candidate_workloads": space_n * n_wl
                          * len(completions)},
                "spans": spans, "host_spans": host_spans}

    def release(self) -> None:
        """Nothing of the program outlives a campaign."""

    def reset(self, seed: int) -> None:
        """A fresh request stream and record for another seed."""
        self.seed = seed
        self.requests = traffic.requests(self.mix, seed,
                                         n_workloads=len(self.records))
        self.done: List[Dict] = []

    def sample(self) -> List[int]:
        n = len(self.done)
        if n == 0:
            return []
        rng = traffic.rng_for(self.seed)
        rest = rng.permutation(n - 1)[:CHECKED_CAMPAIGNS - 1].tolist()
        return sorted(rest + [n - 1])

    def check(self, served_by: str = "program") -> Dict:
        """The comparison numbers over the sampled campaigns' frontiers;
        ``served_by="control"`` puts the reference, in bfloat16, in the
        program's place."""
        import ml_dtypes
        space = reference.Space(self.cfg)
        cols = space.arrays()
        readings = []
        self.n_checked = 0
        for i in self.sample():
            d = self.done[i]
            for rec, s in zip(self.records, d["scales"]):
                rec_s = census_mod.scaled(rec, s)
                ref = reference_question(space, cols, rec_s, self.cfg)
                if served_by == "control":
                    ctl = reference_question(space, cols, rec_s, self.cfg,
                                             dtype=ml_dtypes.bfloat16)
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
