"""Driver of a configuration that serves accelerator-selection queries.

Set-up: the census of every cell; a ``FrontierIndex`` built from an exact
campaign over the configuration's indexed cells; one ``SelectionEngine``
over it, with no predictors, so that every answer is exact over the whole
space; and a warm-up of each family the mix asks about, on the path it
will take.  Window: a closed loop with one client, each query a
``SelectionEngine.select`` call timed from the client's side.  Check: a
sample of the window's distinct answers, drawn from the seed with the
slowest query's answer always in it, against the plain reference's
frontier over the whole space, whatever the answer says it verified.
"""

from __future__ import annotations

import contextlib
import hashlib
import sys
import time
from typing import Dict, List

import numpy as np

from bench import census as census_mod
from bench import check, reference, traffic
from bench.drivers.campaign import (program_config, reference_question,
                                    workload)

CHECKED_ANSWERS = 12


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Cell:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool,
                 census):
        from repro.dse_campaign import Campaign
        from repro.serving.engine import SelectionEngine
        from repro.serving.frontier_index import FrontierIndex
        from repro.telemetry import Telemetry
        if cfg["engine"].get("predictors"):
            raise ValueError("this driver serves exact answers only; a "
                             "configuration with predictors needs a "
                             "reference of the predictors first")
        parts = {}
        t = time.perf_counter()
        self.records = census([tuple(c) for c in cfg["census"]["cells"]])
        parts["census"] = time.perf_counter() - t
        self.cfg, self.mix, self.seed, self.traced = cfg, mix, seed, traced
        n_idx = int(cfg["index"]["cells"])
        self.indexed = self.records[:n_idx]
        self.base = [workload(r) for r in self.indexed]

        t = time.perf_counter()
        camp = Campaign(self.base, program_config(cfg))
        if not camp.run().complete:
            raise RuntimeError("index campaign did not complete")
        index = FrontierIndex.from_campaign(camp)
        parts["index_build"] = time.perf_counter() - t

        self.tel = Telemetry() if traced else None
        self.engine = SelectionEngine(
            index, top_k=int(cfg["engine"]["top_k"]), telemetry=self.tel)

        t = time.perf_counter()
        # every family the mix asks about, once, on the path it will take
        families = (self.indexed if mix["families"] == "indexed"
                    else self.records)
        for rec in families:
            if mix["census_scale"] is None:
                self.engine.select(workload(rec))
                continue
            ans = self.engine.select(workload(census_mod.scaled(rec, 1.05)))
            if ans.provenance != "mini_campaign":
                raise RuntimeError(f"warm-up novel query answered by "
                                   f"{ans.provenance}")
        if self.tel is not None:
            self.tel.tracer.clear()
        parts["warm_up"] = time.perf_counter() - t
        self.setup_parts = parts
        self.n_checked = 0
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """A fresh request stream and record for another seed."""
        self.seed = seed
        self.requests = traffic.requests(
            self.mix, seed, n_indexed=len(self.indexed),
            n_all=len(self.records))
        self.answers: Dict[str, Dict] = {}

    def window(self, t0: float, seconds: float) -> Dict:
        import jax
        latencies, host_spans = [], []
        spans: Dict[str, List[float]] = {}
        failed = attempted = 0
        end = t0 + seconds
        last = t0
        for req in self.requests:
            if time.perf_counter() >= end:
                break
            fam, scale = req["family"], req["scale"]
            wl = (self.base[fam] if scale is None else
                  workload(census_mod.scaled(self.records[fam], scale)))
            kind = "hit" if scale is None else "novel"
            attempted += 1
            ts = time.perf_counter()
            try:
                with (jax.profiler.TraceAnnotation("bench.query." + kind)
                      if self.traced else contextlib.nullcontext()):
                    ans = self.engine.select(wl, deadline_s=req["deadline_s"])
            except Exception as exc:  # a failed request is counted
                failed += 1
                print(f"[bench] query failed: {exc!r}", file=sys.stderr)
                continue
            te = last = time.perf_counter()
            latencies.append(te - ts)
            if ans.degraded_reason is not None:
                failed += 1
            self._keep(fam, scale, ans, te - ts)
            if self.tel is not None:
                host_spans.append(("bench.query." + kind, ts, te))
                for r in self.tel.tracer.records:
                    s = spans.setdefault(r.name, [0.0, 0])
                    s[0] += r.dur
                    s[1] += 1
                    host_spans.append((r.name, r.t0, r.t1))
                self.tel.tracer.clear()
        return {"kind": "selection", "window_start": t0, "window_end": last,
                "attempted": attempted, "failed": failed,
                "latencies_s": latencies, "spans": spans,
                "host_spans": host_spans}

    def _keep(self, fam: int, scale, ans, latency: float) -> None:
        """One record per distinct (question, answer), with the longest
        latency it was served in: every hit on a family serves the same
        frontier, and is compared once."""
        served = (np.asarray(ans.frontier_indices, np.int64),
                  np.asarray(ans.frontier_energy_j, np.float64),
                  np.asarray(ans.frontier_latency_s, np.float64))
        key = _digest(np.asarray([fam, -1.0 if scale is None else scale]),
                      *served)
        a = self.answers.setdefault(key, {"family": fam, "scale": scale,
                                          "served": served,
                                          "latency": latency})
        a["latency"] = max(a["latency"], latency)

    def release(self) -> None:
        """Drop the engine (the index and its device arrays)."""
        self.engine = None

    def sample(self) -> List[Dict]:
        """``CHECKED_ANSWERS`` distinct answers drawn from the seed, the
        slowest query's among them."""
        answers = sorted(self.answers.values(), key=lambda a: -a["latency"])
        if not answers:
            return []
        rng = traffic.rng_for(self.seed)
        rest = (1 + rng.permutation(len(answers) - 1))[:CHECKED_ANSWERS - 1]
        return [answers[0]] + [answers[i] for i in sorted(rest)]

    def check(self, served_by: str = "program") -> Dict:
        """The comparison numbers over the sampled answers, each against
        the reference over the whole space; ``served_by="control"`` puts
        the reference, in bfloat16, in the program's place."""
        import ml_dtypes
        space = reference.Space(self.cfg)
        cols = space.arrays()
        readings = []
        self.n_checked = 0
        for a in self.sample():
            rec = self.records[a["family"]]
            if a["scale"] is not None:
                rec = census_mod.scaled(rec, a["scale"])
            ref = reference_question(space, cols, rec, self.cfg)
            served = a["served"]
            if served_by == "control":
                ctl = reference_question(space, cols, rec, self.cfg,
                                         dtype=ml_dtypes.bfloat16)
                f = ctl["front"]
                served = (ctl["index"][f], ctl["energy"][f],
                          ctl["latency"][f])
            readings.append(check.compare_frontier(*served, ref))
            self.n_checked += 1
        if not readings:
            return {n: float("inf") for n in check.NUMBERS}
        return check.worst(readings)


def setup(cfg: Dict, mix: Dict, seed: int, traced: bool, census) -> Cell:
    return Cell(cfg, mix, seed, traced, census)
