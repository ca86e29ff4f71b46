"""Campaign orchestrator: resumable, bounded-memory DSE over mega-spaces.

A ``Campaign`` sweeps every workload in a cached dry-run artifact set across
a ``SpaceSpec``, tile by tile.  Two tile engines exist:

* the per-workload loop (``"numpy"`` float64 — bitwise-identical to one-shot
  ``pareto_search`` — and ``"fast"`` predictors): each tile is materialized,
  evaluated per workload, constraint-masked and raw-merged into that
  workload's ``StreamingFrontier``.

* the fused zero-copy pipeline (``"jit"`` and ``"pallas"``): tiles stream as
  array-only batches (no per-candidate Python objects), padded to
  ``chunk_size`` with a validity mask so the device function compiles ONCE
  for the whole sweep, and ALL workloads are evaluated in a single launch
  per tile (``costmodel.sweep_workloads_reduced_jit`` or the Pallas
  DSE-sweep kernel).  The launch also reduces each workload's tile to its
  feasible Pareto survivors, compacted on device, so the host transfers
  O(survivors) instead of O(tile) (the full rows follow only for a
  workload that overflowed) and merges via ``StreamingFrontier.merge_reduced``
  (proven identical to the raw merge); ``Candidate`` objects are
  materialized lazily for survivors only.  A prefetch thread stages the
  next tile's arrays while the device evaluates the current one
  (double-buffering), so candidate generation overlaps execution.

The tile engine itself lives in ``TileEvaluator``, and a reduced tile is a
``TileReduction`` — a pure function of (campaign config, tile span) that is
cheap to serialize.  That split is what the distributed fabric
(``repro.dse_campaign.fabric``) exploits: remote workers run the same
``TileEvaluator`` and ship ``TileReduction`` payloads to one coordinator,
whose frontier is bitwise-identical to this module's single-process sweep.

Peak candidate memory is one tile regardless of space size.  Tiles carry
their mesh axes (pod/data/model) into the simulators, so the factorization
axis of the space differentiates the frontier on every evaluator.

Checkpointing is by tile index: the campaign state (spec, workloads,
frontiers, trajectory, next tile) round-trips through JSON, so an
interrupted sweep resumes exactly where it stopped and converges to the
same frontier a fresh run produces — on the fused engines too, because the
reduced merge reproduces the raw merge's accounting exactly.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import queue
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import SHAPES, get_config
from repro.core import costmodel, dataset, dse
from repro.dse_campaign import store
from repro.dse_campaign.config import (EVALUATORS, CampaignConfig,
                                       _CAMPAIGN_LEGACY, _EVALUATOR_LEGACY,
                                       coerce_config)
from repro.dse_campaign.frontier import StreamingFrontier
from repro.dse_campaign.space import SpaceSpec
from repro.telemetry import coerce_telemetry
from repro.telemetry.trace import NULL_TRACER

WorkloadKey = Tuple[str, str]

# per-process campaign sequence number: the request id on the root spans
# of one ``Campaign.run`` (``tile_eval``, ``tile_wait``)
_CAMPAIGN_SEQ = itertools.count(1)


def workload_to_dict(wl: dse.Workload) -> Dict:
    """The JSON/pickle shape of a ``Workload`` used by checkpoints and the
    fabric's worker config — one definition so the two cannot drift."""
    return {"arch": wl.arch, "shape": wl.shape,
            "base_analysis": dict(wl.base_analysis),
            "base_chips": wl.base_chips,
            "state_gb_per_device": wl.state_gb_per_device}


def workload_from_dict(d: Dict) -> dse.Workload:
    """Inverse of ``workload_to_dict``."""
    return dse.Workload(arch=d["arch"], shape=d["shape"],
                        base_analysis=d["base_analysis"],
                        base_chips=d["base_chips"],
                        state_gb_per_device=d["state_gb_per_device"])


@dataclasses.dataclass
class TileStat:
    """Wall-clock accounting for one evaluated tile (all workloads).

    ``candidates`` counts per-workload candidate evaluations
    (``len(tile) * n_workloads``); ``wall_s`` is the tile's evaluation wall
    on whichever process evaluated it.  Stats survive checkpoint/resume, so
    summing them stays consistent with the campaign's evaluated counters.
    """

    tile: int
    candidates: int
    wall_s: float

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CampaignResult:
    """Final (or interrupted) campaign state returned by ``Campaign.run``
    and by the distributed fabric runners.

    ``frontiers`` / ``trajectories`` are per-(arch, shape) workload;
    ``tiles_done`` counts completed tiles (on a distributed run these may
    have completed out of order — completion, not order, is the invariant).
    """

    frontiers: Dict[WorkloadKey, dse.ParetoFrontier]
    trajectories: Dict[WorkloadKey, List]
    tile_stats: List[TileStat]
    space_size: int
    tiles_done: int
    n_tiles: int
    wall_s: float

    @property
    def complete(self) -> bool:
        """True once every tile of the space has folded into the frontiers."""
        return self.tiles_done >= self.n_tiles

    @property
    def candidates_evaluated(self) -> int:
        """Per-workload candidate evaluations across all runs (tile_stats
        survives resume), including any re-issued tiles on a fabric run."""
        return sum(s.candidates for s in self.tile_stats)

    @property
    def sweep_wall_s(self) -> float:
        """Total tile-evaluation wall across ALL runs of this campaign —
        ``tile_stats`` survives checkpoint/resume, so unlike ``wall_s`` (this
        ``run`` call only) it stays consistent with ``candidates_evaluated``
        on a resumed campaign."""
        return sum(s.wall_s for s in self.tile_stats)

    @property
    def candidates_per_sec(self) -> float:
        """Per-workload candidate evaluations per second of sweep wall."""
        return self.candidates_evaluated / max(self.sweep_wall_s, 1e-9)


@dataclasses.dataclass(frozen=True)
class TileReduction:
    """One evaluated tile reduced to exactly what a frontier merge needs.

    Per workload ``w``: ``surv_gidx[w]`` (global candidate indices into the
    space), ``surv_energy[w]`` / ``surv_latency[w]`` (float64 scores), the
    tile's exact feasible count ``n_feasible[w]``, and the tile's feasible
    maxima ``ref_energy_j[w]`` / ``ref_latency_s[w]`` (``None`` when the
    tile has no feasible point).

    Invariants the fabric and the fused single-process path both rely on:

    * ``surv_gidx[w] ⊆ [lo, hi)`` and holds a FEASIBLE SUPERSET of the
      tile's per-workload Pareto skyline, so
      ``StreamingFrontier.merge_reduced`` recovers the exact skyline and
      reproduces the raw merge's accounting bitwise;
    * the payload is O(survivors), not O(tile) — cheap to pickle across a
      process (or host) boundary;
    * it is a pure function of (space, workloads, constraint, sim,
      evaluator) and the tile span — no cross-tile state — which is what
      makes a lost tile safely re-issuable to any other worker.

    Adaptive campaigns additionally carry a seeded training subsample:
    ``sample_lidx`` (LOCAL indices into the tile, shared by all workloads —
    candidate features are workload-independent) plus per-workload
    ``sample_energy`` / ``sample_latency`` rows the surrogates train on.
    The subsample is seeded by ``(adaptive.seed, lo)``, so it is a pure
    function of config x span like everything else here — a re-issued or
    replayed tile yields bitwise-identical training rows on any worker.
    ``None`` (exact campaigns) keeps the payload unchanged.
    """

    lo: int
    hi: int
    surv_gidx: Tuple[np.ndarray, ...]
    surv_energy: Tuple[np.ndarray, ...]
    surv_latency: Tuple[np.ndarray, ...]
    n_feasible: Tuple[int, ...]
    ref_energy_j: Tuple[Optional[float], ...]
    ref_latency_s: Tuple[Optional[float], ...]
    sample_lidx: Optional[np.ndarray] = None
    sample_energy: Optional[Tuple[np.ndarray, ...]] = None
    sample_latency: Optional[Tuple[np.ndarray, ...]] = None

    @property
    def n_workloads(self) -> int:
        """Workload count W (every per-workload tuple has this length)."""
        return len(self.surv_gidx)

    @property
    def n_survivors(self) -> int:
        """Total survivors across workloads — the payload's wire size is
        O(this), never O(tile)."""
        return int(sum(g.size for g in self.surv_gidx))


class _TilePrefetcher:
    """Double-buffered tile staging: a worker thread materializes the next
    tile(s) of a ``SpaceSpec.tiles`` generator while the main thread drives
    the device on the current one.  The worker does numpy-only work (no JAX
    dispatch), so it is safe alongside the evaluating thread; ``close()``
    unblocks and retires it when iteration stops early (max_tiles).  Each
    step of the generator is a ``tile_slice`` root span of ``tracer`` on
    the worker's thread."""

    _END = object()

    def __init__(self, it, depth: int = 1, tracer=NULL_TRACER):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._tracer = tracer
        self._thread = threading.Thread(target=self._work, args=(it,),
                                        daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _work(self, it):
        try:
            while True:
                with self._tracer.span("tile_slice"):
                    item = next(it, self._END)
                if item is self._END or not self._put(item):
                    break
        except BaseException as exc:  # re-raised on the consuming thread
            self._err = exc
        self._put(self._END)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._END:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        self._stop.set()


class TileEvaluator:
    """The one-tile engine shared by ``Campaign`` and the fabric workers.

    Holds everything needed to turn a tile span of a ``SpaceSpec`` into a
    ``TileReduction``: the workload set, constraint, ``SimConfig`` and the
    evaluator tier.  ``reduce_tile`` is side-effect free with respect to
    the campaign (no frontier state lives here), so any number of
    evaluators — across threads, processes or hosts — can work on disjoint
    (or even overlapping) tiles and their reductions fold into one frontier
    without coordination beyond the merge itself.

    Constructed from a ``CampaignConfig`` (``config.evaluator`` selects the
    engine: ``"numpy"`` — float64 per-workload simulator, bitwise-identical
    to one-shot ``pareto_search`` —, ``"jit"`` — fused float32
    multi-workload sweep; ``pipeline=False`` falls back to the legacy
    per-workload jit loop —, ``"pallas"`` — the fused Pallas DSE-sweep
    kernel — or ``"fast"`` — trained predictors; requires fitted
    ``power_model``/``cycles_model`` and, being unpicklable, is refused by
    the distributed fabric).  The pre-config keyword form
    ``TileEvaluator(workloads, space, evaluator=..., ...)`` still works but
    emits a ``DeprecationWarning``.

    ``fused_launches`` counts fused multi-workload sweep launches
    (``sweep_reduced`` calls) over this evaluator's lifetime — the serving
    layer's "batched concurrent queries ride ONE launch" assertion reads
    it, so the claim is measured rather than assumed.  It is now a view
    over the evaluator's telemetry counter
    (``evaluator_fused_launches_total``); pass ``telemetry=`` to share a
    registry/tracer with the caller, or omit it for a private
    ``NullTelemetry`` (counters still count, tracing is free).
    ``evaluator_overflows_total`` counts workloads whose screened survivors
    overflowed ``max_survivors`` and were reduced on the host instead;
    ``evaluator_split_evals_total`` counts the candidate x workload
    evaluations priced with a census split (``coll_model_share``), beside
    ``evaluator_candidates_total``, which counts them all.
    """

    def __init__(self, workloads: Sequence[dse.Workload], config=None,
                 telemetry=None, **legacy):
        cfg = coerce_config("TileEvaluator", config, legacy,
                            _EVALUATOR_LEGACY)
        keys = [(wl.arch, wl.shape) for wl in workloads]
        if len(set(keys)) != len(keys):
            raise ValueError(f"duplicate (arch, shape) workload keys: {keys}")
        self.config = cfg
        self.workloads = list(workloads)
        self.space = cfg.resolved_space
        self.constraint = cfg.resolved_constraint
        self.evaluator = cfg.evaluator
        self.sim = cfg.sim
        self.power_model = cfg.power_model
        self.cycles_model = cfg.cycles_model
        self.pipeline = bool(cfg.pipeline)
        self.max_survivors = int(cfg.max_survivors)
        self.adaptive = cfg.adaptive
        self.train_sample = 0 if cfg.adaptive is None \
            else int(cfg.adaptive.train_sample)
        self.telemetry = coerce_telemetry(telemetry)
        # held series: the hot path pays one attribute read, not a dict hit
        self._c_fused = self.telemetry.counter("evaluator_fused_launches_total")
        self._c_candidates = self.telemetry.counter(
            "evaluator_candidates_total")
        self._c_split = self.telemetry.counter("evaluator_split_evals_total")
        self._n_split = sum("coll_model_share" in wl.base_analysis
                            for wl in self.workloads)
        self._c_overflows = self.telemetry.counter(
            "evaluator_overflows_total")
        self._c_full_fetches = self.telemetry.counter(
            "evaluator_full_fetches_total")

    @property
    def fused_launches(self) -> int:
        """Fused sweep launches so far — a view over the telemetry counter
        (kept as the historical public reading surface)."""
        return int(self._c_fused.value)

    @property
    def fused(self) -> bool:
        """Whether tiles go through the fused multi-workload reduced path."""
        return (self.evaluator == "pallas"
                or (self.evaluator == "jit" and self.pipeline))

    @property
    def workload_keys(self) -> List[WorkloadKey]:
        """(arch, shape) keys in workload order — the order every
        ``TileReduction`` tuple and frontier dict is indexed by."""
        return [(wl.arch, wl.shape) for wl in self.workloads]

    # -- per-workload evaluation (numpy / fast / legacy jit) ----------------

    def evaluate_workload(self, wl: dse.Workload, batch: dse.CandidateBatch
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(energy_j, latency_s, feasible) for one workload on one tile."""
        if self.evaluator == "fast":
            return self._evaluate_fast(wl, batch)
        res, feasible = dse.evaluate_workload_tile(
            wl, batch, self.constraint, sim=self.sim, engine=self.evaluator)
        return np.asarray(res.energy_j), np.asarray(res.latency_s), feasible

    def _evaluate_fast(self, wl: dse.Workload, batch: dse.CandidateBatch):
        """Predictor fast path via ``dse.predict_space`` (same scoring as
        ``fast_path_search``).  Workload shapes suffixed with a pod tag
        resolve to their base shape."""
        cfg = get_config(wl.arch)
        shape = SHAPES[wl.shape.split(":", 1)[0]]
        energy, latency, feasible, _, _ = dse.predict_space(
            cfg, shape, self.power_model, self.cycles_model, batch,
            self.constraint)
        return energy, latency, feasible

    # -- fused zero-copy sweep (jit / pallas) -------------------------------

    @functools.cached_property
    def wl_cols(self) -> np.ndarray:
        """Packed [W, len(WL_COLS)] per-workload scalar matrix (cached); a
        workload without a census split packs ``sim.coll_model_frac``."""
        return np.asarray(
            [costmodel.wl_row(wl.base_analysis, wl.base_chips,
                              wl.state_gb_per_device,
                              self.sim.coll_model_frac)
             for wl in self.workloads], np.float64)

    def padded_tile_arrays(self, batch: dse.CandidateBatch) -> Dict:
        """The tile's packed columns padded to ``chunk_size`` with a validity
        mask — every tile presents the SAME shapes to the device function,
        so jit/Pallas trace exactly once for the whole sweep (the partial
        final tile no longer retriggers a retrace)."""
        n = len(batch)
        target = max(self.space.chunk_size, n)
        pad = target - n

        def padarr(a):
            a = np.asarray(a)
            return a if pad == 0 else np.concatenate(
                [a, np.repeat(a[:1], pad, axis=0)])

        valid = np.ones(target, np.float64)
        valid[n:] = 0.0
        arrays = {
            "n_chips": padarr(batch.n_chips),
            "freq_mhz": padarr(batch.freq_mhz),
            "mesh_pod": padarr(batch.pod_axis()),
            "mesh_data": padarr(batch.mesh_data),
            "mesh_model": padarr(batch.mesh_model),
            "valid": valid,
        }
        arrays.update({k: padarr(batch.chip_cols[k])
                       for k in costmodel.SWEEP_GATHER_FIELDS})
        return arrays

    def launch_lanes(self, n: int) -> int:
        """The lane count a fused launch of an ``n``-candidate batch runs
        on: the batch padded to ``chunk_size``, then to the kernel's
        blocks for this evaluator's workload count."""
        from repro.kernels.dse_sweep import padded_lanes
        return padded_lanes(max(self.space.chunk_size, n),
                            len(self.workloads))

    def stage_tile(self, batch: dse.CandidateBatch):
        """``batch`` staged once for many Pallas launches of this
        evaluator's workload count (``ops.stage_dse_sweep``): the matrix
        each ``sweep_reduced(batch)`` packs, on the device when
        compiled.  Pass it back through ``sweep_reduced``'s ``stage``."""
        from repro.kernels import ops
        return ops.stage_dse_sweep(self.padded_tile_arrays(batch),
                                   len(self.workloads), n_valid=len(batch))

    def sweep_reduced(self, batch: dse.CandidateBatch,
                      stage: Optional[Callable[[], Any]] = None
                      ) -> costmodel.SweepReduced:
        """ONE fused launch: all workloads x one padded tile, skyline-reduced
        on device.  Spans wrap the host-side stages only — ``pad`` (array
        staging) and ``launch``, whose children split it: ``pack``
        (Pallas path), then ``dispatch``, ``device_wait``, ``fetch`` and
        ``host_compact`` (both paths, ``costmodel.run_reduced_launch``);
        tracing never enters the jitted/Pallas code itself.

        ``stage`` (Pallas only) returns ``batch`` as ``stage_tile`` staged
        it; it is called inside ``pack``, and ``pad`` and the packing are
        skipped."""
        if stage is not None and self.evaluator != "pallas":
            raise ValueError(f"a staged tile needs the pallas evaluator, "
                             f"not {self.evaluator!r}")
        self._c_fused.inc()
        tel = self.telemetry
        cons = self.constraint
        if stage is None:
            with tel.span("pad", n=len(batch)):
                arrays = self.padded_tile_arrays(batch)
        with tel.span("launch", evaluator=self.evaluator, n=len(batch)):
            if self.evaluator == "pallas":
                from repro.kernels import ops
                kw = dict(sim=self.sim, constraint=cons,
                          max_survivors=self.max_survivors, tracer=tel)
                if stage is not None:
                    return ops.dse_sweep_staged(stage, self.wl_cols, **kw)
                return ops.dse_sweep(arrays, self.wl_cols,
                                     n_valid=len(batch), **kw)
            return costmodel.sweep_workloads_reduced_jit(
                self.wl_cols,
                {k: arrays[k] for k in costmodel.SWEEP_GATHER_FIELDS},
                arrays["n_chips"], arrays["freq_mhz"], arrays["mesh_pod"],
                arrays["mesh_data"], arrays["mesh_model"], arrays["valid"],
                sim=self.sim, max_power_w=cons.max_power_w,
                max_latency_s=cons.max_latency_s,
                min_hbm_fit=cons.min_hbm_fit,
                max_survivors=self.max_survivors, tracer=tel)

    # -- the normalized reduction -------------------------------------------

    def _tile_sample_lidx(self, n: int, lo: int) -> Optional[np.ndarray]:
        """Seeded training-subsample indices for the tile at ``lo`` (local,
        sorted, without replacement), or ``None`` when the campaign is not
        adaptive.  Seeded by ``(adaptive.seed, lo)`` so the draw depends
        only on config x span — never on which worker or in which round the
        tile was evaluated."""
        if self.train_sample <= 0:
            return None
        k = min(self.train_sample, n)
        rng = np.random.default_rng((self.adaptive.seed, lo))
        return np.sort(rng.choice(n, size=k, replace=False)).astype(np.int64)

    @staticmethod
    def _reduce_rows(energy: np.ndarray, latency: np.ndarray,
                     feasible: np.ndarray, lo: int):
        """Host-side reduction of one workload's raw tile rows: exact
        feasible Pareto survivors + the aggregates ``merge_reduced`` needs to
        reproduce the raw merge's accounting (proven identical by the
        ``merge_reduced``-vs-raw hypothesis property)."""
        e = np.asarray(energy, np.float64)
        l = np.asarray(latency, np.float64)
        feas = np.asarray(feasible, bool)
        loc = np.flatnonzero(dse.pareto_mask(e, l, feas))
        n_feas = int(feas.sum())
        ref_e = float(e[feas].max()) if n_feas else None
        ref_l = float(l[feas].max()) if n_feas else None
        return (lo + loc.astype(np.int64), e[loc], l[loc], n_feas,
                ref_e, ref_l)

    def _fetch_full(self, red: costmodel.SweepReduced) -> tuple:
        """``red``'s full rows ``(energy, latency, feasible)`` copied to the
        host in one ``jax.device_get`` (span ``fetch_full``, counter
        ``evaluator_full_fetches_total``)."""
        import jax
        self._c_full_fetches.inc()
        with self.telemetry.span("fetch_full"):
            return jax.device_get(red.full)

    def reduce_tile(self, batch: dse.CandidateBatch, lo: int,
                    stage: Optional[Callable[[], Any]] = None
                    ) -> TileReduction:
        """Evaluate one tile for ALL workloads and reduce it to a
        ``TileReduction`` — the single entry point both the in-process fused
        sweep and the fabric workers call, so the two paths cannot diverge.

        Fused evaluators keep the on-device screen survivors (a feasible
        superset of the tile skyline, float-cast to float64 exactly); a
        workload whose screened set overflowed ``max_survivors`` — and every
        non-fused evaluator — is reduced host-side to the exact feasible
        Pareto set instead.  Either way the fold through
        ``StreamingFrontier.merge_reduced`` equals the raw full-tile merge.

        With ``config.adaptive`` set, the reduction additionally carries a
        seeded per-tile training subsample (see ``TileReduction``); the
        fused path reads it off the launch's full rows, the per-workload
        path off each workload's evaluation — zero extra launches either
        way.  The fused path copies the full rows to the host (span
        ``fetch_full``, counter ``evaluator_full_fetches_total``) at most
        once per launch, and only for an overflow or a sample.

        ``stage`` hands ``sweep_reduced`` the tile as ``stage_tile``
        staged it (Pallas only).
        """
        n = len(batch)
        cols = {"gidx": [], "e": [], "l": [], "nf": [], "re": [], "rl": []}
        lidx = self._tile_sample_lidx(n, lo)
        samp_e: List[np.ndarray] = []
        samp_l: List[np.ndarray] = []

        def add(gidx, e, l, nf, re, rl):
            cols["gidx"].append(gidx)
            cols["e"].append(e)
            cols["l"].append(l)
            cols["nf"].append(nf)
            cols["re"].append(re)
            cols["rl"].append(rl)

        if self.fused:
            red = self.sweep_reduced(batch, stage)
            full = None            # red.full on the host, once it is read
            with self.telemetry.span("compact", n=n):
                for wi in range(len(self.workloads)):
                    if lidx is not None:
                        full = full or self._fetch_full(red)
                        samp_e.append(full[0][wi][lidx].astype(np.float64))
                        samp_l.append(full[1][wi][lidx].astype(np.float64))
                    if red.overflowed(wi):
                        self._c_overflows.inc()
                        with self.telemetry.span("overflow_reduce", wl=wi):
                            full = full or self._fetch_full(red)
                            add(*self._reduce_rows(
                                *(r[wi][:n] for r in full), lo))
                        continue
                    k = int(red.n_survivors[wi])
                    nf = int(red.n_feasible[wi])
                    add(lo + red.surv_idx[wi][:k].astype(np.int64),
                        red.surv_energy[wi][:k].astype(np.float64),
                        red.surv_latency[wi][:k].astype(np.float64), nf,
                        float(red.ref_energy[wi]) if nf else None,
                        float(red.ref_latency[wi]) if nf else None)
        else:
            for wl in self.workloads:
                with self.telemetry.span("launch", evaluator=self.evaluator,
                                         workload=f"{wl.arch}|{wl.shape}"):
                    energy, latency, feasible = \
                        self.evaluate_workload(wl, batch)
                if lidx is not None:
                    samp_e.append(np.asarray(energy, np.float64)[lidx])
                    samp_l.append(np.asarray(latency, np.float64)[lidx])
                with self.telemetry.span("compact", n=n):
                    add(*self._reduce_rows(energy, latency, feasible, lo))
        tr = TileReduction(
            lo=lo, hi=lo + n,
            surv_gidx=tuple(cols["gidx"]), surv_energy=tuple(cols["e"]),
            surv_latency=tuple(cols["l"]), n_feasible=tuple(cols["nf"]),
            ref_energy_j=tuple(cols["re"]), ref_latency_s=tuple(cols["rl"]),
            sample_lidx=lidx,
            sample_energy=tuple(samp_e) if lidx is not None else None,
            sample_latency=tuple(samp_l) if lidx is not None else None)
        self._c_candidates.inc(n * len(self.workloads))
        self._c_split.inc(n * self._n_split)
        return tr


class Campaign:
    """Streaming multi-workload DSE campaign over a ``SpaceSpec``.

    Constructed from a ``CampaignConfig`` (``Campaign(workloads, config)``);
    the pre-config keyword form ``Campaign(workloads, space,
    evaluator=..., ...)`` still works but emits a ``DeprecationWarning``.

    ``config.evaluator`` selects the tile engine: ``"numpy"`` (float64 simulator,
    bitwise-identical to one-shot ``pareto_search``), ``"jit"``
    (float32 fused multi-workload sweep, ``costmodel.sweep_workloads_
    reduced_jit``), ``"pallas"`` (the fused Pallas DSE-sweep kernel —
    float64 in interpret mode on CPU, where its frontier holds the numpy
    evaluator's exact candidate set, float32 compiled on an accelerator),
    or ``"fast"``
    (trained predictors; pass fitted ``power_model``/``cycles_model``).

    ``pipeline=False`` disables the fused path for ``"jit"`` and falls back
    to the original per-workload loop on unpadded tiles (one launch per
    workload per tile, full-tile host transfer, raw merges) — kept as the
    measured baseline for the evaluator-speedup benchmark.

    Invariant: the final frontier depends only on (space, workloads,
    constraint, sim, evaluator) — never on tile size, tile order,
    interruption points, or (via ``repro.dse_campaign.fabric``) how many
    workers evaluated the tiles.
    """

    def __init__(self, workloads: Sequence[dse.Workload], config=None,
                 telemetry=None, **legacy):
        cfg = coerce_config("Campaign", config, legacy, _CAMPAIGN_LEGACY)
        self.telemetry = coerce_telemetry(telemetry)
        self.engine = TileEvaluator(workloads, cfg,
                                    telemetry=self.telemetry)
        self.checkpoint_every = int(cfg.checkpoint_every)
        self.frontiers: Dict[WorkloadKey, StreamingFrontier] = {
            k: StreamingFrontier(telemetry=self.telemetry)
            for k in self.engine.workload_keys}
        self.tile_stats: List[TileStat] = []
        self.next_tile = 0

    # -- config views (the engine owns the config; Campaign owns the state) -

    @property
    def config(self) -> CampaignConfig:
        return self.engine.config

    @property
    def workloads(self) -> List[dse.Workload]:
        return self.engine.workloads

    @property
    def space(self) -> SpaceSpec:
        return self.engine.space

    @property
    def constraint(self) -> dse.Constraint:
        return self.engine.constraint

    @property
    def evaluator(self) -> str:
        return self.engine.evaluator

    @property
    def sim(self) -> costmodel.SimConfig:
        return self.engine.sim

    @property
    def pipeline(self) -> bool:
        return self.engine.pipeline

    @property
    def max_survivors(self) -> int:
        return self.engine.max_survivors

    @property
    def fused(self) -> bool:
        """Whether tiles go through the fused multi-workload reduced path."""
        return self.engine.fused

    def _sweep_tile_reduced(self, batch: dse.CandidateBatch
                            ) -> costmodel.SweepReduced:
        """One fused launch on one tile (kernel-test entry point)."""
        return self.engine.sweep_reduced(batch)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_artifacts(cls, art_dir: str, config=None,
                       **kwargs) -> "Campaign":
        """Sweep ALL cached dry-run workloads under ``art_dir``.

        ``config`` is a ``CampaignConfig`` (or, deprecated, a ``SpaceSpec``
        plus the old keyword set — forwarded to the constructor shim).
        Each artifact's compiled census (``base_analysis``) is loaded ONCE
        per (arch, shape) cell and reused across every tile of the sweep.
        Colliding (arch, shape) cells from different pods are disambiguated
        by suffixing the shape with the pod tag.
        """
        arts = dataset.load_dryrun_artifacts(art_dir)
        if not arts:
            raise FileNotFoundError(f"no dry-run artifacts in {art_dir}")
        seen = {}
        for (arch, shape, pod), art in sorted(arts.items()):
            key = (arch, shape) if (arch, shape) not in seen else (
                arch, f"{shape}:{pod}")
            seen[key] = dse.Workload(
                arch=key[0], shape=key[1],
                base_analysis=dse.census_analysis(art["hxa"]),
                base_chips=art["roofline"]["n_chips"],
                state_gb_per_device=art["memory"]["state_gb_per_device"])
        return cls(list(seen.values()), config, **kwargs)

    @classmethod
    def from_checkpoint(cls, path: str, **kwargs) -> "Campaign":
        """Rebuild an interrupted campaign from its checkpoint file; the
        next ``run`` continues at the first unevaluated tile.

        Space, workloads, constraint, ``SimConfig``, evaluator and pipeline
        mode are all restored from the checkpoint into a ``CampaignConfig``;
        extra keyword arguments override config fields on the rebuilt
        config.  Fitted predictor models
        cannot be serialized, so resuming an ``evaluator="fast"`` campaign
        requires re-passing the SAME ``power_model``/``cycles_model`` via
        kwargs (``__init__`` refuses to resume without them); supplying
        retrained models would splice two predictors into one frontier
        undetected.  A checkpoint written under a different
        ``costmodel.SIM_MODEL_VERSION`` is refused for the same reason: its
        folded-in tiles and the tiles a resume would evaluate come from
        incomparable cost models.

        A checkpoint written by the distributed fabric also loads here:
        ``next_tile`` is the contiguous done prefix, and any out-of-order
        tiles the fabric already folded re-merge as exact no-ops (span
        idempotence), so a single-process resume still converges to the
        same frontier.

        Corrupt checkpoints do not crash the resume: ``store.load_checkpoint``
        verifies the integrity CRC, quarantines a bad file to ``*.corrupt``
        and falls back to the newest valid generation (see
        ``docs/resilience.md``); only when no copy on disk verifies does a
        ``CheckpointCorruptionError`` surface.
        """
        state = store.load_checkpoint(path)
        return cls.from_state(state, source=path, **kwargs)

    @classmethod
    def from_state(cls, state: Dict, source: str = "<state>",
                   **kwargs) -> "Campaign":
        """Rebuild a campaign from an already-loaded ``state_dict`` (the
        verified-load half of ``from_checkpoint`` — callers that need the
        corruption-recovery report use ``store.load_checkpoint_recovering``
        and hand the state here)."""
        ckpt_model = state.get("sim_model_version")
        if ckpt_model != costmodel.SIM_MODEL_VERSION:
            raise ValueError(
                f"checkpoint {source} was written under cost-model version "
                f"{ckpt_model!r} but this build is "
                f"{costmodel.SIM_MODEL_VERSION}; resuming would splice two "
                "incomparable cost models into one frontier.  To upgrade, "
                "re-run the campaign from scratch under the current model "
                "(and rebuild any FrontierIndex derived from this "
                "checkpoint)")
        workloads = [workload_from_dict(w) for w in state["workloads"]]
        cfg = CampaignConfig(
            space=SpaceSpec.from_dict(state["space"]),
            evaluator=state["evaluator"],
            constraint=dse.Constraint(**state["constraint"]),
            sim=costmodel.SimConfig(**state["sim"]),
            # checkpoints written before the fused pipeline carry no key:
            # they ran the legacy per-workload engine, so resume must stay
            # on it — splicing the fused float32 sweep into a half-done
            # legacy "jit" campaign could flip float32 near-ties
            # mid-frontier
            pipeline=state.get("pipeline", False))
        telemetry = kwargs.pop("telemetry", None)
        if kwargs:
            unknown = set(kwargs) - {f.name for f in
                                     dataclasses.fields(CampaignConfig)}
            if unknown:
                raise TypeError(f"from_state: unexpected keyword "
                                f"arguments {sorted(unknown)}")
            cfg = cfg.replace(**kwargs)
        camp = cls(workloads, cfg, telemetry=telemetry)
        camp.next_tile = state["next_tile"]
        camp.tile_stats = [TileStat(**s) for s in state["tile_stats"]]
        for key_str, fr_state in state["frontiers"].items():
            arch, shape = key_str.split("|", 1)
            camp.frontiers[(arch, shape)] = StreamingFrontier.from_state(
                fr_state, telemetry=camp.telemetry)
        return camp

    # -- folding ------------------------------------------------------------

    def merge_reduction(self, tr: TileReduction, tile_no: int = -1) -> None:
        """Fold one ``TileReduction`` into every workload's frontier, with
        survivor ``Candidate`` objects materialized lazily from the space.

        Idempotent at tile granularity: re-folding an already-folded tile —
        a duplicate delivery on the fabric, or a replayed tile after a
        resume — changes neither the frontier nor its accounting."""
        tel = self.telemetry
        for wi, wl in enumerate(self.workloads):
            gidx = tr.surv_gidx[wi]
            with tel.span("materialize", wl=wi):
                cands = self.space.candidates_at(gidx)
            self.frontiers[(wl.arch, wl.shape)].merge_reduced(
                cands, tr.surv_energy[wi],
                tr.surv_latency[wi], gidx, span=(tr.lo, tr.hi),
                n_feasible=tr.n_feasible[wi],
                ref_energy_j=tr.ref_energy_j[wi],
                ref_latency_s=tr.ref_latency_s[wi], tile=tile_no)

    # -- the sweep ----------------------------------------------------------

    def run(self, checkpoint_path: Optional[str] = None,
            max_tiles: Optional[int] = None) -> CampaignResult:
        """Sweep tiles from ``next_tile`` on; returns the (possibly partial)
        campaign result.  ``max_tiles`` bounds THIS call (interruption point
        for resume demos/tests); with a ``checkpoint_path`` (defaulting to
        ``config.checkpoint_path``) the state is persisted every
        ``checkpoint_every`` tiles and at the end.  The root spans of the
        call carry its campaign sequence number (``campaign``):
        ``tile_eval`` per tile, and ``tile_wait`` for each wait on the
        prefetcher for the next tile."""
        if checkpoint_path is None:
            checkpoint_path = self.config.checkpoint_path
        tel = self.telemetry
        clock = tel.clock
        c_tiles = tel.counter("campaign_tiles_total")
        c_ckpt = tel.counter("campaign_checkpoint_writes_total")
        t_start = clock()
        done_this_call = 0
        fused = self.fused
        engine = self.engine
        seq = next(_CAMPAIGN_SEQ)
        tiles = _TilePrefetcher(self.space.tiles(
            start_tile=self.next_tile, with_candidates=not fused), tracer=tel)
        try:
            while True:
                with tel.span("tile_wait", campaign=seq):
                    item = next(tiles, None)
                if item is None:
                    break
                tile_no, lo, batch = item
                if max_tiles is not None and done_this_call >= max_tiles:
                    break
                t0 = clock()
                with tel.span("tile_eval", tile=tile_no, n=len(batch),
                              campaign=seq):
                    if fused:
                        tr = engine.reduce_tile(batch, lo)
                        with tel.span("merge", tile=tile_no):
                            self.merge_reduction(tr, tile_no)
                    else:
                        indices = np.arange(lo, lo + len(batch),
                                            dtype=np.int64)
                        for wl in self.workloads:
                            with tel.span(
                                    "launch", evaluator=engine.evaluator,
                                    workload=f"{wl.arch}|{wl.shape}"):
                                energy, latency, feasible = \
                                    engine.evaluate_workload(wl, batch)
                            with tel.span("merge", tile=tile_no):
                                self.frontiers[(wl.arch, wl.shape)].merge(
                                    batch.candidates, energy, latency,
                                    feasible, indices=indices, tile=tile_no)
                        engine._c_candidates.inc(
                            len(batch) * len(self.workloads))
                c_tiles.inc()
                self.tile_stats.append(TileStat(
                    tile=tile_no,
                    candidates=len(batch) * len(self.workloads),
                    wall_s=clock() - t0))
                self.next_tile = tile_no + 1
                done_this_call += 1
                if checkpoint_path and (self.next_tile % self.checkpoint_every == 0):
                    with tel.span("checkpoint_write", tile=tile_no):
                        store.save_checkpoint(self.state_dict(),
                                              checkpoint_path)
                    c_ckpt.inc()
        finally:
            tiles.close()
        if checkpoint_path:
            with tel.span("checkpoint_write", tile=self.next_tile - 1):
                store.save_checkpoint(self.state_dict(), checkpoint_path)
            c_ckpt.inc()
        return self._result(clock() - t_start)

    def _result(self, wall_s: float, tiles_done: Optional[int] = None
                ) -> CampaignResult:
        wl_by_key = {(wl.arch, wl.shape): wl for wl in self.workloads}
        return CampaignResult(
            frontiers={k: fr.as_pareto_frontier(wl_by_key[k])
                       for k, fr in self.frontiers.items()},
            trajectories={k: list(fr.trajectory)
                          for k, fr in self.frontiers.items()},
            tile_stats=list(self.tile_stats),
            space_size=len(self.space),
            tiles_done=self.next_tile if tiles_done is None else tiles_done,
            n_tiles=self.space.n_tiles(),
            wall_s=wall_s)

    # -- persistence --------------------------------------------------------

    def state_dict(self) -> Dict:
        """Full JSON-serializable campaign state (schema version 1), stamped
        with ``SIM_MODEL_VERSION`` so ``from_checkpoint`` can refuse to splice
        two cost models into one frontier."""
        return {
            "version": 1,
            "sim_model_version": costmodel.SIM_MODEL_VERSION,
            "space": self.space.to_dict(),
            "workloads": [workload_to_dict(wl) for wl in self.workloads],
            "constraint": dataclasses.asdict(self.constraint),
            "sim": dataclasses.asdict(self.sim),
            "evaluator": self.evaluator,
            "pipeline": self.pipeline,
            "next_tile": self.next_tile,
            "tile_stats": [s.as_dict() for s in self.tile_stats],
            "frontiers": {f"{arch}|{shape}": fr.state_dict()
                          for (arch, shape), fr in self.frontiers.items()},
        }
