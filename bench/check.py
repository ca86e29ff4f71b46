"""The comparison that decides ``correct``: a served frontier against the
plain reference's frontier over the same candidates.

Every served frontier point is looked up by its global candidate index in
the reference's float64 evaluation.  Three numbers, each the worst over all
frontiers compared in a run:

* ``value_rel_err`` - relative error of a served energy or latency against
  the reference value of the same candidate;
* ``missed_rel``    - for each reference frontier point, the least relative
  slack ``x`` at which some served point (at its reference values) is within
  ``(1 + x)`` of it on both axes; a true frontier point that the served
  frontier neither holds nor nearly ties reads high;
* ``spurious_rel``  - for each served point (at its reference values), the
  margin by which a reference frontier point beats it on both axes, or by
  which it breaks the constraint; a served point that is not on the true
  frontier reads high.

A configuration's ``limits`` name the numbers that decide ``correct``.

The float32 device path reads at rounding level (float32 near-ties); a
sweep in a lower precision, or a wrong or missing answer, reads orders of
magnitude higher.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

NUMBERS = ("value_rel_err", "missed_rel", "spurious_rel")


def _cover_slack(target_e, target_l, by_e, by_l, chunk: int = 2048):
    """For each target point, min over ``by`` points of
    max(by_e/target_e - 1, by_l/target_l - 1, 0)."""
    out = np.full(target_e.shape, np.inf)
    if not by_e.size:
        return out
    for s in range(0, target_e.size, chunk):
        te, tl = target_e[s:s + chunk, None], target_l[s:s + chunk, None]
        slack = np.maximum(np.maximum(by_e[None, :] / te - 1.0,
                                      by_l[None, :] / tl - 1.0), 0.0)
        out[s:s + chunk] = slack.min(axis=1)
    return out


def _beaten_margin(target_e, target_l, by_e, by_l, chunk: int = 2048):
    """For each target point, max over ``by`` points of
    min(target_e/by_e - 1, target_l/by_l - 1), floored at 0."""
    out = np.zeros(target_e.shape)
    if not by_e.size:
        return out
    for s in range(0, target_e.size, chunk):
        te, tl = target_e[s:s + chunk, None], target_l[s:s + chunk, None]
        m = np.minimum(te / by_e[None, :] - 1.0, tl / by_l[None, :] - 1.0)
        out[s:s + chunk] = np.maximum(m.max(axis=1), 0.0)
    return out


def compare_frontier(served_idx, served_e, served_l, ref: Dict) -> Dict:
    """Numbers of one served frontier against ``ref``.

    ``ref`` holds the reference's float64 arrays over the answer's scope,
    keyed by global candidate index: ``index`` (sorted global indices),
    ``energy``, ``latency``, ``excess`` (constraint excess share) and
    ``front`` (positions into those arrays of the reference frontier)."""
    served_idx = np.asarray(served_idx, np.int64)
    pos = np.searchsorted(ref["index"], served_idx)
    pos = np.minimum(pos, ref["index"].size - 1)
    if served_idx.size and not np.array_equal(ref["index"][pos], served_idx):
        # a served candidate outside the question's scope cannot be right
        return {n: float("inf") for n in NUMBERS}
    se = np.asarray(served_e, np.float64)
    sl = np.asarray(served_l, np.float64)
    re, rl = ref["energy"][pos], ref["latency"][pos]
    value = 0.0
    if served_idx.size:
        value = float(max(np.max(np.abs(se / re - 1.0)),
                          np.max(np.abs(sl / rl - 1.0))))
    fe, fl = ref["energy"][ref["front"]], ref["latency"][ref["front"]]
    missed = _cover_slack(fe, fl, re, rl)
    spurious = np.maximum(_beaten_margin(re, rl, fe, fl), ref["excess"][pos])
    return {"value_rel_err": value,
            "missed_rel": float(missed.max()) if missed.size else 0.0,
            "spurious_rel": float(spurious.max()) if spurious.size else 0.0}


def worst(readings) -> Dict:
    """The worst of each number over several frontiers' readings."""
    out = {n: 0.0 for n in NUMBERS}
    for r in readings:
        for n in NUMBERS:
            out[n] = max(out[n], r[n])
    return out


def verdict(numbers: Dict, limits: Dict) -> bool:
    """True when every number that ``limits`` names is at or under its
    limit; a number not read fails."""
    return all(numbers.get(n, float("inf")) <= limits[n] for n in limits)
