"""Analytical ground-truth simulator: latency (cycles) + power + energy.

Role in the reproduction: the paper trains its predictors against POWER and
CYCLES measured on a real V100S.  This container is CPU-only, so the measured
target is replaced by a deterministic, calibrated analytical model over the
compiled artifact (the "slow-accurate path"): HxA census -> three roofline
terms -> partial-overlap latency -> CMOS power.  The ML predictors (fast path)
never see any of this — they predict from static early-design features only,
exactly like the paper.

Latency model:
  t_comp = flops / (peak * mxu_derate)        t_mem = hbm_bytes / hbm_bw
  t_coll -- topology-aware when the candidate's mesh is known: the collective
  payload splits into a data-parallel share (hierarchical ring all-reduce over
  the pod x data axes) and a model-parallel share (all-gather/reduce-scatter
  on the model axis) -- by the census's own per-axis split where the
  workload carries one (``coll_model_share``), else by
  ``SimConfig.coll_model_frac`` -- each axis costing

      t_axis = bytes_axis * (k - 1)/k / (ici_bw * links_axis)
               + 2 * (k - 1) * hop_s

  with per-axis link counts from ``hw.axis_link_counts`` (ring vs. torus
  wraparound, chip link-budget degradation).  Without a mesh the fixed
  mesh-less approximation ``wire_bytes / (ici_bw * MESHLESS_LINKS)``
  applies (the former ``SimConfig.links_used`` knob is gone; see
  ``SIM_MODEL_VERSION``).
  latency = max(t) + (1 - overlap) * (sum(t) - max(t))
    -- overlap=0.8: XLA latency-hiding overlaps most, not all, of the
       non-dominant terms.

Power model (per chip):
  P = P_idle + (TDP - P_idle) * (w_mxu*u_mxu + w_hbm*u_hbm + w_ici*u_ici)
      * (f/f_max)^3            [DVFS cubic, paper ref [5]]
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import numpy as np

from repro.hw import (CHIP_TABLE, ChipSpec, ChipTable, axis_link_counts,
                      get_chip, normalize_mesh)
from repro.telemetry.trace import NULL_TRACER

# default fraction of the collective payload attributed to model-parallel
# collectives (activation all-gather/reduce-scatter on the model axis); the
# remainder is the data-parallel all-reduce share.  The split happens in ONE
# place (``collective_payload``): by the analysis's ``coll_model_share`` --
# the census's model-axis share of its wire bytes -- where it carries one,
# else by the simulating ``SimConfig``'s ``coll_model_frac``.
COLL_MODEL_FRAC = 0.5

# bump when the cost model's arithmetic changes on purpose: the CI frontier
# compare (benchmarks/compare_campaign.py) only gates hypervolume regressions
# between artifacts produced by the SAME model version.  Checkpoints,
# fabric worker configs and FrontierIndex artifacts all stamp this number
# and refuse to load across a mismatch.
# 1 = mesh-agnostic links_used; 2 = topology-aware collectives;
# 3 = SimConfig.links_used removed (mesh-less simulation is the fixed
#     MESHLESS_LINKS approximation, no longer a config knob)
# 4 = a workload's census may carry its model-axis share
#     (``coll_model_share``), which replaces ``coll_model_frac`` for it
SIM_MODEL_VERSION = 4

# link count of the fixed mesh-less approximation: censuses simulated
# without a candidate mesh (dry-run base pods, offload slices, rooflines)
# price collectives as ``wire_bytes / (ici_bw * MESHLESS_LINKS)``.  This is
# the old ``links_used`` default frozen in place — candidate sweeps always
# carry a mesh and never touch it.
MESHLESS_LINKS = 2


@dataclasses.dataclass(frozen=True)
class SimConfig:
    overlap: float = 0.8
    w_mxu: float = 0.55
    w_hbm: float = 0.30
    w_ici: float = 0.15
    coll_model_frac: float = COLL_MODEL_FRAC


@dataclasses.dataclass(frozen=True)
class SimResult:
    t_compute: float
    t_memory: float
    t_collective: float
    latency_s: float
    cycles: float
    utilization: float
    power_w: float               # per chip
    energy_j: float              # whole slice
    bottleneck: str

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def wire_bytes(analysis: Dict):
    """Collective wire-bytes of a census, with the documented fallback chain
    (wire_bytes -> collective_bytes -> 0) shared by every simulate variant."""
    return analysis.get("wire_bytes", analysis.get("collective_bytes", 0.0))


def _raw_payload(analysis: Dict, n_chips, xp):
    """Un-ring-factored collective payload bytes per device.

    Prefers the ``coll_payload_bytes`` key that ``dse._scale_analysis``
    emits; otherwise derives it from ``wire_bytes`` by un-applying the
    whole-slice ring factor (n-1)/n that first-order scaling applied."""
    if "coll_payload_bytes" in analysis:
        return xp.asarray(analysis["coll_payload_bytes"])
    wire = xp.asarray(wire_bytes(analysis))
    n = xp.asarray(n_chips) * 1.0
    ring = xp.where(n > 1, (n - 1.0) / xp.maximum(n, 1.0), 1.0)
    return wire / ring


def model_share(wire_bytes_by_axis: Dict) -> Optional[float]:
    """A census's model-axis share of its wire bytes, model / (data +
    model), from the dry-run's ``wire_bytes_by_axis``; None for a census
    that moves no bytes."""
    data, model = wire_bytes_by_axis["data"], wire_bytes_by_axis["model"]
    return model / (data + model) if data + model > 0 else None


def collective_payload(analysis: Dict, n_chips, frac: float, xp=np):
    """(data_bytes, model_bytes) collective payload split for a candidate.

    The ONLY place the data/model split happens: by the analysis's
    ``coll_model_share`` where it carries one, else by the simulating
    ``SimConfig.coll_model_frac``.  Identical IEEE expressions in scalar
    and array form, so every simulate variant splits bitwise the same."""
    payload = _raw_payload(analysis, n_chips, xp)
    frac = analysis.get("coll_model_share", frac)
    return payload * (1.0 - frac), payload * frac


def _axis_collective_time(payload, extent, links, ici_bw, hop_s, xp):
    """Ring time of one mesh axis: bandwidth term + per-step hop latency.

    t = payload * (k-1)/k / (ici_bw * links) + 2*(k-1)*hop_s
    (reduce-scatter + all-gather, k-1 ring steps each).  Inactive axes
    (k <= 1), axes moving zero bytes, linkless chips, and zero-bandwidth
    chips contribute 0."""
    k = xp.asarray(extent) * 1.0
    links = xp.asarray(links) * 1.0
    bw = xp.asarray(ici_bw) * 1.0
    live = (k > 1) & (links > 0) & (bw > 0) & (xp.asarray(payload) > 0)
    denom = xp.where(live, bw * xp.where(links > 0, links, 1.0), 1.0)
    t_bw = payload * (k - 1.0) / xp.maximum(k, 1.0) / denom
    t_hop = 2.0 * (k - 1.0) * hop_s
    return xp.where(live, t_bw + t_hop, 0.0)


def topology_collective_time(p_data, p_model, mesh_pod, mesh_data, mesh_model,
                             ici_bw, ici_links, links_per_axis, hop_s, xp=np):
    """Topology-aware collective time over the (pod, data, model) mesh axes.

    The model-parallel payload rides the model axis; the data-parallel
    payload does a hierarchical ring all-reduce: a full ring over the data
    axis, then the pod axis on the 1/k_data shard that survives the first
    reduce-scatter stage.  Per-axis link counts come from
    ``hw.axis_link_counts`` (torus wraparound, link-budget degradation)."""
    lp, ld, lm = axis_link_counts(mesh_pod, mesh_data, mesh_model,
                                  ici_links, links_per_axis, xp=xp)
    kd = xp.asarray(mesh_data) * 1.0
    return (_axis_collective_time(p_data, mesh_data, ld, ici_bw, hop_s, xp)
            + _axis_collective_time(p_data / xp.maximum(kd, 1.0), mesh_pod,
                                    lp, ici_bw, hop_s, xp)
            + _axis_collective_time(p_model, mesh_model, lm, ici_bw, hop_s,
                                    xp))


def roofline_terms(analysis: Dict, chip: ChipSpec, n_chips: int) -> Dict:
    """The §Roofline contract.  ``analysis`` holds PER-DEVICE HxA numbers, so
    term = per_device_quantity / per_chip_rate == global / (chips * rate)."""
    t_comp = analysis["flops"] / chip.peak_flops_bf16
    t_mem = analysis["hbm_bytes"] / chip.hbm_bw
    t_coll = (analysis["collective_bytes"] / chip.ici_bw
              if chip.ici_bw else 0.0)
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    return {**terms, "dominant": dom,
            "hlo_flops_per_device": analysis["flops"],
            "hlo_bytes_per_device": analysis["hbm_bytes"],
            "collective_bytes_per_device": analysis["collective_bytes"],
            "n_chips": n_chips}


def simulate(analysis: Dict, chip: ChipSpec, n_chips: int,
             freq_mhz: Optional[float] = None,
             sim: SimConfig = SimConfig(), mesh=None) -> SimResult:
    """Slow-accurate path: deterministic latency/power from a compiled cell.

    With ``mesh`` (the candidate's mesh tuple) the collective term is the
    topology-aware per-axis model; without it the fixed mesh-less
    ``MESHLESS_LINKS`` approximation applies.  The topology arithmetic runs
    through the same xp-generic helpers as ``simulate_batch``, so scalar
    and batch agree bitwise."""
    if freq_mhz is None:
        freq_mhz = chip.nominal_freq_mhz
    chip_f = chip.at_frequency(freq_mhz)
    t_comp = analysis["flops"] / chip_f.peak_flops_bf16
    t_mem = analysis["hbm_bytes"] / chip_f.hbm_bw
    wire = wire_bytes(analysis)
    if mesh is not None:
        pod, data, model = normalize_mesh(mesh)
        p_d, p_m = collective_payload(analysis, n_chips, sim.coll_model_frac)
        t_coll = float(topology_collective_time(
            p_d, p_m, pod, data, model, chip_f.ici_bw, chip_f.ici_links,
            chip_f.ici_links_per_axis, chip_f.ici_hop_s))
    else:
        t_coll = (wire / (chip_f.ici_bw * MESHLESS_LINKS)
                  if chip_f.ici_bw else 0.0)

    ts = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(ts, key=ts.get)
    t_max = ts[dom]
    latency = t_max + (1.0 - sim.overlap) * (sum(ts.values()) - t_max)
    latency = max(latency, 1e-9)

    u_mxu = t_comp / latency
    u_hbm = t_mem / latency
    u_ici = t_coll / latency
    util = sim.w_mxu * u_mxu + sim.w_hbm * u_hbm + sim.w_ici * u_ici
    power = chip.dynamic_power(freq_mhz, util)
    cycles = latency * freq_mhz * 1e6
    return SimResult(
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        latency_s=latency, cycles=cycles, utilization=u_mxu,
        power_w=power, energy_j=power * latency * n_chips,
        bottleneck=dom)


def simulate_by_name(analysis: Dict, chip_name: str, n_chips: int,
                     freq_mhz: Optional[float] = None, mesh=None) -> SimResult:
    return simulate(analysis, get_chip(chip_name), n_chips, freq_mhz,
                    mesh=mesh)


# --- Batched (struct-of-arrays) path ------------------------------------------
# Same arithmetic as ``simulate`` applied to whole candidate arrays at once:
# chip properties are gathered from CHIP_TABLE by index, every step is an
# elementwise array op, so a full DSE space is one pass of vector code instead
# of a Python loop.  numpy float64 by default (bitwise-matches the scalar
# path); pass ``xp=jax.numpy`` for a jit-able accelerator variant.

BOTTLENECKS = ("compute", "memory", "collective")

# the chip-table columns simulate_batch actually gathers; pre-gathered
# ``gathered`` dicts only need (and multi-workload tiling only tiles) these
SIM_GATHER_FIELDS = ("nominal_freq_mhz", "min_freq_mhz", "max_freq_mhz",
                     "peak_flops_bf16", "hbm_bw", "ici_bw", "tdp_watts",
                     "idle_watts", "ici_links", "ici_links_per_axis",
                     "ici_hop_s")


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: ndarray fields
class SimBatch:
    """``SimResult`` over N candidates, field-per-array."""

    t_compute: np.ndarray
    t_memory: np.ndarray
    t_collective: np.ndarray
    latency_s: np.ndarray
    cycles: np.ndarray
    utilization: np.ndarray
    power_w: np.ndarray              # per chip
    energy_j: np.ndarray             # whole slice
    bottleneck_idx: np.ndarray       # index into BOTTLENECKS

    def __len__(self) -> int:
        return int(np.shape(self.latency_s)[0])

    def bottleneck(self, i: int) -> str:
        return BOTTLENECKS[int(self.bottleneck_idx[i])]

    def result(self, i: int) -> SimResult:
        """Materialize one row as the scalar dataclass."""
        return SimResult(
            t_compute=float(self.t_compute[i]),
            t_memory=float(self.t_memory[i]),
            t_collective=float(self.t_collective[i]),
            latency_s=float(self.latency_s[i]),
            cycles=float(self.cycles[i]),
            utilization=float(self.utilization[i]),
            power_w=float(self.power_w[i]),
            energy_j=float(self.energy_j[i]),
            bottleneck=self.bottleneck(i))


def simulate_batch(analysis: Dict, chip_idx, n_chips,
                   freq_mhz=None, sim: SimConfig = SimConfig(),
                   table: ChipTable = CHIP_TABLE, xp=np,
                   gathered: Optional[Dict] = None,
                   mesh_pod=None, mesh_data=None, mesh_model=None) -> SimBatch:
    """Vectorized ``simulate`` over arrays of candidates.

    ``analysis`` holds per-device arrays (or scalars, broadcast) of flops /
    hbm_bytes / collective_bytes / wire_bytes (plus the optional
    ``coll_payload_bytes`` un-split collective payload); ``chip_idx``
    indexes ``table``; ``n_chips`` / ``freq_mhz`` are per-candidate arrays.
    With ``mesh_data``/``mesh_model`` (and optionally ``mesh_pod``) the
    collective term is the topology-aware per-axis model; without them the
    fixed mesh-less ``MESHLESS_LINKS`` approximation applies.  With the
    default ``xp=np``
    the arithmetic is float64 and agrees with the scalar path to machine
    precision; any array namespace with the numpy API (e.g. ``jax.numpy``)
    works, making the body jit-able.  ``gathered`` (from
    ``table.gather(chip_idx)``) skips the per-call column gathers when the
    same candidate batch is swept repeatedly.
    """
    n_chips = xp.asarray(n_chips)
    if gathered is None:
        gathered = {f: xp.asarray(getattr(table, f))[xp.asarray(chip_idx)]
                    for f in SIM_GATHER_FIELDS}
    nominal = gathered["nominal_freq_mhz"]
    f_min = gathered["min_freq_mhz"]
    f_max = gathered["max_freq_mhz"]
    if freq_mhz is None:
        freq_mhz = nominal
    freq = xp.clip(xp.asarray(freq_mhz), f_min, f_max)

    peak = gathered["peak_flops_bf16"] * (freq / nominal)
    hbm_bw = gathered["hbm_bw"]
    ici_bw = gathered["ici_bw"]

    flops = xp.asarray(analysis["flops"])
    hbm_bytes = xp.asarray(analysis["hbm_bytes"])
    wire = xp.asarray(wire_bytes(analysis))

    t_comp = flops / peak
    t_mem = hbm_bytes / hbm_bw
    if mesh_model is not None:
        if mesh_data is None:
            raise ValueError("mesh_model without mesh_data; pass both "
                             "trailing mesh axes (mesh_pod is optional)")
        if mesh_pod is None:
            mesh_pod = xp.ones(xp.shape(xp.asarray(mesh_model)), xp.asarray(
                mesh_model).dtype)
        p_d, p_m = collective_payload(analysis, n_chips,
                                      sim.coll_model_frac, xp=xp)
        t_coll = topology_collective_time(
            p_d, p_m, mesh_pod, mesh_data, mesh_model, ici_bw,
            gathered["ici_links"], gathered["ici_links_per_axis"],
            gathered["ici_hop_s"], xp=xp)
    else:
        has_ici = ici_bw > 0
        t_coll = xp.where(
            has_ici,
            wire / (xp.where(has_ici, ici_bw, 1.0) * MESHLESS_LINKS),
            0.0)

    ts = xp.stack([t_comp, t_mem, t_coll])         # BOTTLENECKS order
    dom = xp.argmax(ts, axis=0)
    t_max = xp.max(ts, axis=0)
    latency = t_max + (1.0 - sim.overlap) * (xp.sum(ts, axis=0) - t_max)
    latency = xp.maximum(latency, 1e-9)

    # same association as the scalar path (w * (t/latency), summed in the
    # same order); residual disagreement is 1 ulp from pow() vs array **3
    util = (sim.w_mxu * (t_comp / latency) + sim.w_hbm * (t_mem / latency)
            + sim.w_ici * (t_coll / latency))
    util = xp.clip(util, 0.0, 1.0)
    tdp = gathered["tdp_watts"]
    idle = gathered["idle_watts"]
    power = idle + (tdp - idle) * util * (freq / f_max) ** 3
    power = xp.minimum(power, tdp)

    # cycles use the caller's (unclamped) frequency, matching ``simulate``;
    # freq_mhz was defaulted to nominal above if the caller passed None
    cycles = latency * xp.asarray(freq_mhz) * 1e6
    return SimBatch(
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        latency_s=latency, cycles=cycles, utilization=t_comp / latency,
        power_w=power, energy_j=power * latency * n_chips,
        bottleneck_idx=dom)


def scale_census(base_analysis: Dict, base_chips, n_chips, xp=np) -> Dict:
    """First-order rescale of a compiled census to other slice sizes, xp-generic.

    The single home of the scaling arithmetic shared by
    ``dse._scale_analysis_batch`` (numpy float64), the fused jit sweep below,
    and the Pallas DSE-sweep kernel — identical IEEE expressions in every
    path, so the float64 variants agree bitwise with the scalar oracle.
    flops/bytes scale ~1/chips; collective bytes ride the ring factor; the
    emitted ``coll_payload_bytes`` un-applies the base census's global ring
    factor so the topology-aware simulator can split it per mesh axis; a
    ``coll_model_share`` passes through unscaled.
    """
    bc = xp.asarray(base_chips) * 1.0
    nc = xp.asarray(n_chips) * 1.0
    r = bc / nc
    ring_base = xp.maximum((bc - 1.0) / bc, 1e-9)
    ring = xp.where(nc > 1, ((nc - 1.0) / nc) / ring_base, 0.0)
    out = {
        "flops": xp.asarray(base_analysis["flops"]) * r,
        "hbm_bytes": xp.asarray(base_analysis["hbm_bytes"]) * r,
        "collective_bytes":
            xp.asarray(base_analysis["collective_bytes"]) * r * ring,
        "wire_bytes": xp.asarray(base_analysis["wire_bytes"]) * r * ring,
        "coll_payload_bytes":
            xp.asarray(base_analysis["wire_bytes"]) * r / ring_base,
    }
    if "coll_model_share" in base_analysis:
        out["coll_model_share"] = xp.asarray(base_analysis["coll_model_share"])
    return out


# --- Fused sweep reduction (per-tile skyline pre-reduction) -------------------
# A campaign tile's full energy/latency arrays exist only so the streaming
# frontier can discard >99% of them.  The helpers below move that discard on
# device: the constraint-feasible Pareto survivors of the tile plus the scalar
# aggregates the frontier accounting needs (feasible count, feasible maxima
# for the hypervolume reference point) are everything the host has to see —
# O(survivors) transfer instead of O(tile).  ``skyline_reduce`` is xp-generic
# so the numpy reference, the jit reference path and the Pallas kernel all
# reduce with the same arithmetic, and the surviving mask provably equals
# ``dse.pareto_mask`` on the feasible subset (same sort keys, same strict /
# group-minimum survival rule, infeasible rows pushed to +inf keys).

# chip-table columns the fused sweep gathers: the simulate set plus the HBM
# capacity the feasibility check reads
SWEEP_GATHER_FIELDS = SIM_GATHER_FIELDS + ("hbm_bytes",)

# per-workload scalar column order of the packed [W, 7] workload matrix; a
# workload without a census split packs ``SimConfig.coll_model_frac`` as its
# ``coll_model_share`` (``wl_row``)
WL_COLS = ("flops", "hbm_bytes", "collective_bytes", "wire_bytes",
           "base_chips", "state_gb_per_device", "coll_model_share")


def wl_row(base_analysis: Dict, base_chips, state_gb_per_device,
           frac: float) -> list:
    """One workload's ``WL_COLS`` row: its census scalars, and its
    ``coll_model_share`` or ``frac`` where it carries none."""
    return [base_analysis["flops"], base_analysis["hbm_bytes"],
            base_analysis["collective_bytes"], base_analysis["wire_bytes"],
            base_chips, state_gb_per_device,
            base_analysis.get("coll_model_share", frac)]


def _cummin(x, xp):
    if xp is np:
        return np.minimum.accumulate(x)
    import jax.lax
    return jax.lax.cummin(x)


def skyline_reduce(energy, latency, feasible, xp=np):
    """(keep, n_feasible, ref_energy, ref_latency) of one evaluated tile.

    ``keep`` marks the feasible Pareto survivors of the (energy, latency)
    minimization — the same set ``dse.pareto_mask`` selects, computed with
    static shapes so it jits: infeasible rows are mapped to +inf sort keys
    instead of being compacted away.  ``ref_*`` are the feasible maxima
    (-inf when the tile has no feasible point) that pin the streaming
    frontier's hypervolume reference point.
    """
    e = xp.asarray(energy)
    l = xp.asarray(latency)
    feas = xp.asarray(feasible, bool)
    e_key = xp.where(feas, e, xp.inf)
    l_key = xp.where(feas, l, xp.inf)
    order = xp.lexsort((e_key, l_key))
    es, ls = e_key[order], l_key[order]
    first = xp.searchsorted(ls, ls, side="left")
    prefix = _cummin(es, xp)
    best_before = xp.where(first > 0, prefix[xp.maximum(first - 1, 0)], xp.inf)
    # survive: strictly faster points all cost more energy, and tied-latency
    # points only if they hold the group's energy minimum (equal duplicates
    # never dominate each other — both stay, matching dse.pareto_mask)
    nondom = (es < best_before) & (es <= es[first]) & feas[order]
    if xp is np:
        keep = np.zeros(e.shape, bool)
        keep[order] = nondom
    else:
        keep = xp.zeros(e.shape, bool).at[order].set(nondom)
    n_feasible = xp.sum(feas)
    ref_e = xp.max(xp.where(feas, e, -xp.inf))
    ref_l = xp.max(xp.where(feas, l, -xp.inf))
    return keep, n_feasible, ref_e, ref_l


def sweep_feasibility(power_w, latency_s, n_chips, hbm_bytes, base_chips,
                      state_gb_per_device, valid, max_power_w, max_latency_s,
                      min_hbm_fit: bool, xp=np):
    """``dse.feasibility_mask`` arithmetic in xp-generic, padding-aware form.

    ``valid`` masks tile padding lanes (always infeasible); ``max_power_w`` /
    ``max_latency_s`` of ``None`` skip their comparison exactly like the
    numpy constraint path, so the float64 variants agree bitwise."""
    ok = xp.asarray(valid) > 0
    nc = xp.asarray(n_chips) * 1.0
    if min_hbm_fit:
        state_pd = state_gb_per_device * (xp.asarray(base_chips) * 1.0) / nc
        ok = ok & (state_pd * 1e9 <= hbm_bytes * 0.9)
    if max_power_w is not None:
        ok = ok & (power_w * nc <= max_power_w)
    if max_latency_s is not None:
        ok = ok & (latency_s <= max_latency_s)
    return ok


# convex-weight probe spread of the on-device dominance screen: each weight
# w picks the feasible argmin of w*(e/e_min) + (l/l_min) — a point ON the
# tile skyline — and everything strictly dominated by a probe is screened
# out.  Geometric spread covers frontier slopes across four decades.
_PROBE_WEIGHTS = np.geomspace(1e-2, 1e2, 8)


def _screen_rows(energy, latency, feasible):
    """jnp screen shared by the jit reference path and the Pallas wrapper:
    per-workload-row conservative dominance screen of [W, N] sweeps.
    Returns (keep, n_surv, n_feas, ref_e, ref_l) with ``keep`` the [W, N]
    survivor mask.

    The screen is CONSERVATIVE: probes are real feasible points (argmins of
    convex (energy, latency) weightings, i.e. skyline members), and a
    skyline point is dominated by nothing — so the surviving set is always
    a superset of the exact ``skyline_reduce`` set, and the frontier fold
    (``StreamingFrontier.merge_reduced`` -> ``dse.pareto_mask``) recovers
    the exact skyline from it.  Everything here is elementwise / reduction
    work — no sort, no prefix scan: XLA's comparator sort costs more than
    the whole simulation on [W, 32k] tiles, while the probe screen leaves
    only a few percent of slack over the exact skyline on real campaign
    tiles.  All dominance comparisons run in the sweep dtype against probe
    values gathered from the same arrays, so screening decisions are exact
    in any precision."""
    import jax
    import jax.numpy as jnp
    wts = jnp.asarray(_PROBE_WEIGHTS, energy.dtype)

    def row(e, l, feas):
        e_lo = jnp.min(jnp.where(feas, e, jnp.inf))
        l_lo = jnp.min(jnp.where(feas, l, jnp.inf))
        score = wts[:, None] * (e / e_lo)[None, :] + (l / l_lo)[None, :]
        pi = jnp.argmin(jnp.where(feas[None, :], score, jnp.inf), axis=1)
        ep, lp = e[pi][:, None], l[pi][:, None]             # [P, 1] probes
        dom = ((e[None, :] >= ep) & (l[None, :] >= lp)
               & ((e[None, :] > ep) | (l[None, :] > lp)))
        keep = feas & ~jnp.any(dom, axis=0)
        return (keep, jnp.sum(keep), jnp.sum(feas),
                jnp.max(jnp.where(feas, e, -jnp.inf)),
                jnp.max(jnp.where(feas, l, -jnp.inf)))

    return jax.vmap(row)(energy, latency, feasible)


# lanes per block of the survivor compaction (``_compact_rows_device``); a
# v5e compacts faster with 128, a CPU in float64 several times slower
_COMPACT_BLOCK = 64


def _compact_rows_device(keep, energy, latency, max_survivors: int):
    """jnp survivor compaction of screened [W, N] rows: (surv_idx, surv_e,
    surv_l) as [W, K], ``K = min(max_survivors, N)``, with the survivors'
    lanes in ascending order and energy / latency taken bitwise from the
    same arrays; entries past a row's survivor count are zero.  A row that
    overflows keeps its first ``K`` survivors.

    The lanes fall into blocks of ``_COMPACT_BLOCK``.  Survivor slot r
    takes its block (the number of blocks that end with fewer than r
    survivors) as one row gather of the block's running survivor count
    and the bits of its energy and latency, and picks the lane whose count
    is r by a compare: one gather of ``W x K`` rows, where a search and
    element gathers of energy and latency would make many, and a TPU pays
    for a gather by its count of indices."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    w, n = keep.shape
    k = min(int(max_survivors), n)
    b = _COMPACT_BLOCK
    nb = -(-n // b)
    pad = ((0, 0), (0, nb * b - n))
    bits = jnp.dtype(f"int{8 * energy.dtype.itemsize}")
    kp = jnp.pad(keep, pad)
    count = jnp.cumsum(kp, axis=1, dtype=jnp.int32)   # survivors up to a lane
    rank = jnp.arange(1, k + 1, dtype=jnp.int32)
    blk = jnp.sum(count[:, b - 1::b][:, None, :] < rank[None, :, None],
                  axis=2, dtype=jnp.int32)                          # [W, K]
    data = jnp.stack([jnp.where(kp, count, 0).astype(bits)] + [
        lax.bitcast_convert_type(jnp.pad(x, pad), bits)
        for x in (energy, latency)], axis=1)                  # [W, 3, nb * b]
    data = data.reshape(w, 3, nb, b).transpose(0, 2, 1, 3)    # [W, nb, 3, b]
    rows = jax.vmap(lambda d, i: jnp.take(d, i, axis=0, mode="clip"))(
        data, blk)                                            # [W, K, 3, b]
    hit = rows[:, :, 0, :] == rank[None, :, None]
    lane = jnp.arange(b, dtype=jnp.int32)
    pos = blk * b + jnp.sum(jnp.where(hit, lane, 0), axis=2, dtype=jnp.int32)
    e_bits, l_bits = (jnp.sum(jnp.where(hit, rows[:, :, c, :], 0), axis=2,
                              dtype=bits) for c in (1, 2))
    filled = rank[None, :] <= count[:, -1:]
    zero = jnp.zeros((), energy.dtype)
    return (jnp.where(filled, pos, 0),
            jnp.where(filled, lax.bitcast_convert_type(e_bits, energy.dtype),
                      zero),
            jnp.where(filled, lax.bitcast_convert_type(l_bits, latency.dtype),
                      zero))


def _reduce_launch(energy, latency, feasible, max_survivors: int):
    """The in-trace tail both fused launches share: the screen
    (``_screen_rows``), the survivors compacted on the device
    (``_compact_rows_device``), and the outputs packed for the host.
    Returns ``(ints, floats, full)``: int32 ``[W, K + 2]`` (survivor lanes,
    ``n_surv``, ``n_feas``), ``[W, 2K + 2]`` in the sweep dtype (survivor
    energy, survivor latency, ``ref_e``, ``ref_l``), and the full rows
    ``(energy, latency, feasible)``, each ``[W, N]`` as the launch computed
    them, which stay on the device (``SweepReduced.full``)."""
    import jax.numpy as jnp
    keep, n_surv, n_feas, ref_e, ref_l = _screen_rows(energy, latency,
                                                       feasible)
    idx, surv_e, surv_l = _compact_rows_device(keep, energy, latency,
                                               max_survivors)
    ints = jnp.concatenate(
        [idx, n_surv[:, None].astype(jnp.int32),
         n_feas[:, None].astype(jnp.int32)], axis=1)
    floats = jnp.concatenate(
        [surv_e, surv_l, ref_e[:, None], ref_l[:, None]], axis=1)
    return ints, floats, (energy, latency, feasible)


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: ndarray fields
class SweepReduced:
    """Reduced result of one fused (all-workloads x tile) sweep launch.

    ``surv_*`` are the screened tile survivors (a feasible superset of the
    tile's Pareto skyline), compacted on the device: all a frontier merge
    needs.  ``full`` holds the launch's full rows, left on the device
    when compiled; ``TileEvaluator.reduce_tile`` copies them to the host,
    once, only for a workload whose screened set exceeds
    ``max_survivors`` or for an adaptive campaign's training sample."""

    surv_idx: np.ndarray         # int32 [W, K] lane indices into the tile
    surv_energy: np.ndarray      # [W, K], rows past n_survivors are zero
    surv_latency: np.ndarray     # [W, K]
    n_survivors: np.ndarray      # int32 [W] (may exceed K: overflow)
    n_feasible: np.ndarray       # int32 [W]
    ref_energy: np.ndarray       # [W] feasible max (-inf if none)
    ref_latency: np.ndarray      # [W]
    max_survivors: int
    full: tuple                  # (energy, latency, feasible) [W, N] each

    def overflowed(self, w: int) -> bool:
        return int(self.n_survivors[w]) > self.max_survivors


@functools.lru_cache(maxsize=None)
def _jit_sweep_reduced(sim: SimConfig, max_power_w, max_latency_s,
                       min_hbm_fit: bool, max_survivors: int):
    import jax
    import jax.numpy as jnp

    def run(wl_cols, chip_cols, n_chips, freq_mhz, mesh_pod, mesh_data,
            mesh_model, valid):
        # workloads broadcast as a leading DATA axis ([W, 1] x [1, N] ->
        # [W, N]) rather than a Python loop, so the traced graph — and the
        # compile time — is independent of the workload count
        row = lambda a: jnp.asarray(a)[None, :]
        wl = {k: wl_cols[:, i:i + 1] for i, k in enumerate(WL_COLS)}
        cols = {k: row(v) for k, v in chip_cols.items()}
        ana = scale_census(wl, wl["base_chips"], row(n_chips), xp=jnp)
        b = simulate_batch(ana, None, row(n_chips), row(freq_mhz), sim=sim,
                           xp=jnp, gathered=cols,
                           mesh_pod=row(mesh_pod), mesh_data=row(mesh_data),
                           mesh_model=row(mesh_model))
        feas = sweep_feasibility(
            b.power_w, b.latency_s, row(n_chips), cols["hbm_bytes"],
            wl["base_chips"], wl["state_gb_per_device"], row(valid),
            max_power_w, max_latency_s, min_hbm_fit, xp=jnp)
        e = jnp.broadcast_to(b.energy_j, feas.shape)
        l = jnp.broadcast_to(b.latency_s, feas.shape)
        return _reduce_launch(e, l, feas, max_survivors)

    return jax.jit(run)


def sweep_workloads_reduced_jit(wl_cols, chip_cols: Dict, n_chips, freq_mhz,
                                mesh_pod, mesh_data, mesh_model, valid,
                                sim: SimConfig = SimConfig(),
                                max_power_w=None, max_latency_s=None,
                                min_hbm_fit: bool = True,
                                max_survivors: int = 2048,
                                tracer=NULL_TRACER) -> SweepReduced:
    """The jit reference path of the fused on-device campaign evaluator.

    One launch evaluates ALL ``W`` workloads on one (padded) candidate tile —
    census scaling, topology-aware simulation, constraint masking and the
    per-tile skyline pre-reduction (a conservative dominance screen whose
    survivors are a guaranteed superset of the tile's feasible Pareto set)
    and the survivors' compaction all happen in-trace — so the host copies
    and handles O(survivors) per tile.
    float32 under the repo's default x64-disabled config (the ``"jit"``
    precision tier); the Pallas kernel path (``repro.kernels.dse_sweep``)
    shares every helper and runs float64 in interpret mode.  ``chip_cols``
    needs the ``SWEEP_GATHER_FIELDS`` columns; ``wl_cols`` is the packed
    [W, 7] ``WL_COLS`` matrix; the inputs cross to the device inside the
    jitted call.  ``tracer`` (a ``SpanTracer`` or ``Telemetry``) times the
    host stages (``run_reduced_launch``).
    """
    w_count, n_wl_cols = np.shape(wl_cols)
    if n_wl_cols != len(WL_COLS):
        raise ValueError(f"wl_cols must be [W, {len(WL_COLS)}] ({WL_COLS})")
    cols = {k: chip_cols[k] for k in SWEEP_GATHER_FIELDS}
    fn = _jit_sweep_reduced(sim, max_power_w, max_latency_s,
                            bool(min_hbm_fit), int(max_survivors))
    return run_reduced_launch(
        fn, (np.asarray(wl_cols, np.float64), cols, n_chips, freq_mhz,
             mesh_pod, mesh_data, mesh_model, valid),
        int(max_survivors), tracer)


def run_reduced_launch(fn, args, max_survivors: int,
                       tracer=NULL_TRACER) -> SweepReduced:
    """The host stages of one fused launch, shared by both fused evaluators:
    ``dispatch`` (``fn(*args)`` returns its futures), ``device_wait`` (the
    host waits for the chip), ``fetch`` (the two small outputs of
    ``_reduce_launch`` to the host in one ``jax.device_get``) and
    ``host_compact`` (``build_sweep_reduced``: slicing), each a span of
    ``tracer``.  The full rows stay on the device.  The wait is the one a
    fetch makes anyway: a tracing ``tracer`` takes it first, so that it is
    timed apart from the copies; untraced, the copy waits."""
    import jax
    with tracer.span("dispatch"):
        ints, floats, full = fn(*args)
    if tracer.tracing:
        with tracer.span("device_wait"):
            jax.block_until_ready((ints, floats, full))
    with tracer.span("fetch"):
        ints, floats = jax.device_get((ints, floats))
    with tracer.span("host_compact"):
        return build_sweep_reduced((ints, floats, full), max_survivors)


def build_sweep_reduced(out, max_survivors: int) -> SweepReduced:
    """Assemble the ``SweepReduced`` of a fused launch from its outputs
    ``(ints, floats, full)`` (``_reduce_launch``): the first two fetched
    to the host and sliced here, ``full`` the device rows, kept unread."""
    ints, floats, full = out
    k = ints.shape[1] - 2
    return SweepReduced(
        surv_idx=ints[:, :k], surv_energy=floats[:, :k],
        surv_latency=floats[:, k:2 * k],
        n_survivors=ints[:, k], n_feasible=ints[:, k + 1],
        ref_energy=floats[:, 2 * k], ref_latency=floats[:, 2 * k + 1],
        max_survivors=int(max_survivors), full=full)


@functools.lru_cache(maxsize=None)
def _jit_simulate_batch(sim: SimConfig, with_mesh: bool):
    import jax
    import jax.numpy as jnp

    if with_mesh:
        def run(flops, hbm_bytes, payload, share, chip_idx, n_chips,
                freq_mhz, mesh_pod, mesh_data, mesh_model):
            batch = simulate_batch(
                {"flops": flops, "hbm_bytes": hbm_bytes,
                 "coll_payload_bytes": payload, "wire_bytes": payload,
                 "coll_model_share": share},
                chip_idx, n_chips, freq_mhz, sim=sim, xp=jnp,
                mesh_pod=mesh_pod, mesh_data=mesh_data, mesh_model=mesh_model)
            return dataclasses.asdict(batch)
    else:
        def run(flops, hbm_bytes, wire_bytes, chip_idx, n_chips, freq_mhz):
            batch = simulate_batch(
                {"flops": flops, "hbm_bytes": hbm_bytes,
                 "wire_bytes": wire_bytes},
                chip_idx, n_chips, freq_mhz, sim=sim, xp=jnp)
            return dataclasses.asdict(batch)

    return jax.jit(run)


def simulate_batch_jit(analysis: Dict, chip_idx, n_chips, freq_mhz,
                       sim: SimConfig = SimConfig(),
                       mesh_pod=None, mesh_data=None,
                       mesh_model=None) -> SimBatch:
    """jit-compiled ``simulate_batch`` on the default JAX backend.

    Accelerator path for very large spaces; float32 under the repo's default
    x64-disabled config, so expect ~1e-6 relative agreement rather than the
    numpy path's exact match.  Passing ``mesh_data``/``mesh_model`` (and
    optionally ``mesh_pod``) selects the topology-aware collective model;
    the un-split payload is derived in float64 numpy BEFORE entering the
    jit, then split in-trace by the analysis's ``coll_model_share`` (else
    ``sim.coll_model_frac``) like every other path.
    """
    if mesh_model is not None:
        mesh_model = np.asarray(mesh_model, np.int32)
        mesh_data = np.asarray(mesh_data, np.int32)
        mesh_pod = (np.ones_like(mesh_model) if mesh_pod is None
                    else np.asarray(mesh_pod, np.int32))
        payload = _raw_payload(analysis, n_chips, np)
        share = analysis.get("coll_model_share", sim.coll_model_frac)
        out = _jit_simulate_batch(sim, True)(
            analysis["flops"], analysis["hbm_bytes"], payload, share,
            np.asarray(chip_idx, np.int32), n_chips, freq_mhz,
            mesh_pod, mesh_data, mesh_model)
    else:
        out = _jit_simulate_batch(sim, False)(
            analysis["flops"], analysis["hbm_bytes"], wire_bytes(analysis),
            np.asarray(chip_idx, np.int32), n_chips, freq_mhz)
    return SimBatch(**{k: np.asarray(v) for k, v in out.items()})
