"""Plain reference of the design-space sweep: the same semantics as the
system under test, written out in numpy and importing nothing of it.

A configuration file states the deployment: the chip table, the design
space (chips x slice sizes x mesh factorizations x DVFS lattice x slice
variants), the cost-model constants and the constraint.  From that and a
workload's census this module computes, for every candidate of the space,
its energy, latency and feasibility, and the feasible Pareto frontier of the
(energy, latency) minimization.

``dtype`` selects the arithmetic: ``np.float64`` is the reference;
``ml_dtypes.bfloat16`` (every intermediate rounded) is the lower-precision
control that the comparison has to refuse.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

CHIP_FIELDS = ("peak_flops_bf16", "hbm_bw", "hbm_bytes", "ici_bw", "ici_links",
               "nominal_freq_mhz", "min_freq_mhz", "max_freq_mhz",
               "tdp_watts", "idle_watts", "ici_links_per_axis", "ici_hop_s")


def mesh_factorizations(n: int, dims: int) -> List[Tuple[int, ...]]:
    """Nondecreasing 2-axis factorizations of ``n`` and, for ``dims`` 3,
    the 3-axis ones with a leading factor of at least 2; sorted by
    (length, extents)."""
    out = set()
    for a in range(1, int(n ** 0.5) + 1):
        if n % a == 0:
            out.add((a, n // a))
    if dims >= 3:
        for a in range(2, int(n ** (1 / 3)) + 2):
            if n % a:
                continue
            rem = n // a
            for b in range(a, int(rem ** 0.5) + 1):
                if rem % b == 0:
                    out.add((a, b, rem // b))
    return sorted(out, key=lambda m: (len(m), m))


class Space:
    """The configuration's design space, enumerated in the system's order:
    chip, then slice variant, then mesh (by slice size, then
    factorization), then DVFS lattice point.  Flat index
    ``row * freq_points + k``."""

    def __init__(self, cfg: Dict):
        sp = cfg["space"]
        self.chip_names = list(sp["chips"])
        self.chips = {c: cfg["chip_table"][c] for c in self.chip_names}
        self.freq_points = int(sp["freq_points"])
        rows = []
        for chip in self.chip_names:
            spec = self.chips[chip]
            if spec["ici_bw"] == 0:
                meshes = [(1, 1)]
            else:
                meshes = [m for n in sp["chip_counts"]
                          for m in mesh_factorizations(n, sp["mesh_dims"])]
            for _, scale in sp["variants"]:
                for mesh in meshes:
                    rows.append((chip, float(scale), mesh))
        self.rows = rows
        self.size = len(rows) * self.freq_points

    def __len__(self) -> int:
        return self.size

    def arrays(self, idx=None) -> Dict[str, np.ndarray]:
        """Per-candidate float64 columns at flat indices ``idx`` (all when
        None): chip fields, n_chips, mesh axes and frequency."""
        idx = (np.arange(self.size) if idx is None
               else np.asarray(idx, np.int64))
        row, k = np.divmod(idx, self.freq_points)
        per_row: Dict[str, list] = {f: [] for f in CHIP_FIELDS}
        for extra in ("n_chips", "mesh_pod", "mesh_data", "mesh_model",
                      "f_lo", "f_hi"):
            per_row[extra] = []
        for chip, scale, mesh in self.rows:
            spec = self.chips[chip]
            for f in CHIP_FIELDS:
                per_row[f].append(float(spec[f]))
            pod = 1
            for m in mesh[:-2]:
                pod *= m
            per_row["n_chips"].append(float(np.prod(mesh)))
            per_row["mesh_pod"].append(float(pod))
            per_row["mesh_data"].append(float(mesh[-2]))
            per_row["mesh_model"].append(float(mesh[-1]))
            lo, hi = spec["min_freq_mhz"], spec["max_freq_mhz"]
            per_row["f_lo"].append(float(lo))
            per_row["f_hi"].append(float(min(max(hi * scale, lo), hi)))
        cols = {name: np.asarray(v, np.float64)[row]
                for name, v in per_row.items()}
        lo, hi = cols.pop("f_lo"), cols.pop("f_hi")
        p = self.freq_points
        if p == 1:
            freq = hi.copy()
        else:
            freq = lo + k * (hi - lo) / (p - 1)
            freq = np.where(k == 0, lo, np.where(k == p - 1, hi, freq))
        cols["freq_mhz"] = freq
        return cols


def evaluate(cols: Dict[str, np.ndarray], census: Dict, cfg: Dict,
             dtype=np.float64) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(energy_j, latency_s, feasible) of one workload census over the
    candidate columns ``cols``; every intermediate is rounded to ``dtype``.

    ``census`` holds the per-device ``flops``, ``hbm_bytes`` and
    ``wire_bytes`` of the base slice, ``base_chips`` and
    ``state_gb_per_device``."""
    sim, cons = cfg["sim"], cfg["constraint"]

    def q(x):
        return np.asarray(x, dtype)

    def c(name):
        return q(cols[name])

    one = q(1.0)
    nc, freq = c("n_chips"), c("freq_mhz")
    bc = q(census["base_chips"])
    # first-order rescale of the base census to the candidate's slice
    r = q(bc / nc)
    ring_base = q(np.maximum(q(q(bc - one) / bc), q(1e-9)))
    flops = q(q(census["flops"]) * r)
    hbm = q(q(census["hbm_bytes"]) * r)
    payload = q(q(q(census["wire_bytes"]) * r) / ring_base)
    p_model = q(payload * q(sim["coll_model_frac"]))
    p_data = q(payload * q(one - q(sim["coll_model_frac"])))
    # roofline terms at the candidate's clock
    f = q(np.clip(freq, c("min_freq_mhz"), c("max_freq_mhz")))
    peak = q(c("peak_flops_bf16") * q(f / c("nominal_freq_mhz")))
    t_comp = q(flops / peak)
    t_mem = q(hbm / c("hbm_bw"))
    # topology: a ring per mesh axis; links per axis under the chip budget
    kp, kd, km = c("mesh_pod"), c("mesh_data"), c("mesh_model")
    n_active = q(q(kp > 1) + q(kd > 1) + q(km > 1))
    budget = q(np.maximum(np.floor(q(c("ici_links")
                                     / np.maximum(n_active, one))), one))

    def links(k):
        want = q(np.where(k >= 3, 2.0, np.where(k >= 2, 1.0, 0.0)))
        return q(np.minimum(np.minimum(want, c("ici_links_per_axis")),
                            budget))

    bw, hop = c("ici_bw"), c("ici_hop_s")

    def axis_time(p, k, lk):
        live = (k > 1) & (lk > 0) & (bw > 0) & (p > 0)
        denom = q(np.where(live, q(bw * np.where(lk > 0, lk, one)), one))
        t_bw = q(q(q(p * q(k - one)) / np.maximum(k, one)) / denom)
        t_hop = q(q(q(2.0) * q(k - one)) * hop)
        return q(np.where(live, q(t_bw + t_hop), q(0.0)))

    t_coll = q(q(axis_time(p_data, kd, links(kd))
                 + axis_time(q(p_data / np.maximum(kd, one)), kp, links(kp)))
               + axis_time(p_model, km, links(km)))
    t_max = q(np.maximum(np.maximum(t_comp, t_mem), t_coll))
    total = q(q(t_comp + t_mem) + t_coll)
    lat = q(t_max + q(q(one - q(sim["overlap"])) * q(total - t_max)))
    lat = q(np.maximum(lat, q(1e-9)))
    # CMOS power: idle + dynamic share x utilization x (f / f_max)^3
    util = q(q(q(q(sim["w_mxu"]) * q(t_comp / lat))
               + q(q(sim["w_hbm"]) * q(t_mem / lat)))
             + q(q(sim["w_ici"]) * q(t_coll / lat)))
    util = q(np.clip(util, q(0.0), one))
    tdp, idle = c("tdp_watts"), c("idle_watts")
    ratio = q(f / c("max_freq_mhz"))
    cube = q(q(ratio * ratio) * ratio)
    power = q(idle + q(q(q(tdp - idle) * util) * cube))
    power = q(np.minimum(power, tdp))
    energy = q(q(power * lat) * nc)
    feas = np.ones(nc.shape, bool)
    if cons["min_hbm_fit"]:
        state_pd = q(q(q(census["state_gb_per_device"]) * bc) / nc)
        feas &= q(state_pd * q(1e9)) <= q(c("hbm_bytes") * q(0.9))
    if cons["max_power_w"] is not None:
        feas &= q(power * nc) <= q(cons["max_power_w"])
    if cons["max_latency_s"] is not None:
        feas &= lat <= q(cons["max_latency_s"])
    return (np.asarray(energy, np.float64), np.asarray(lat, np.float64),
            feas)


def constraint_excess(cols: Dict[str, np.ndarray], census: Dict, cfg: Dict,
                      energy: np.ndarray, latency: np.ndarray) -> np.ndarray:
    """How far each candidate lies outside the constraint, as a share of
    the limit it breaks (0 where feasible), in float64."""
    cons = cfg["constraint"]
    nc = cols["n_chips"]
    out = np.zeros(nc.shape)
    if cons["min_hbm_fit"]:
        state = census["state_gb_per_device"] * census["base_chips"] / nc
        out = np.maximum(out, state * 1e9 / (cols["hbm_bytes"] * 0.9) - 1.0)
    if cons["max_power_w"] is not None:
        power = energy / latency / nc
        out = np.maximum(out, power * nc / cons["max_power_w"] - 1.0)
    if cons["max_latency_s"] is not None:
        out = np.maximum(out, latency / cons["max_latency_s"] - 1.0)
    return np.maximum(out, 0.0)


def pareto(energy: np.ndarray, latency: np.ndarray,
           feasible: np.ndarray) -> np.ndarray:
    """Positions of the feasible points that no feasible point dominates
    (<= on both axes, < on one); equal duplicates all stay."""
    idx = np.flatnonzero(feasible)
    if not idx.size:
        return idx
    e, l = energy[idx], latency[idx]
    order = np.lexsort((e, l))                  # latency, then energy
    es, ls = e[order], l[order]
    first = np.searchsorted(ls, ls, side="left")
    best = np.minimum.accumulate(es)
    before = np.where(first > 0, best[np.maximum(first - 1, 0)], np.inf)
    keep = (es < before) & (es <= es[first])
    return np.sort(idx[order[keep]])
