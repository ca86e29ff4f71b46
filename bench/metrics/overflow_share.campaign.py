"""overflow_share.campaign: the share of (tile, workload) pairs whose
on-device screen overflowed ``max_survivors`` and were reduced on the host
(``overflow_reduce`` spans over tiles x workloads, the workloads being
``sweep.candidate_workloads / sweep.candidates``), in %."""

from bench.metrics import _per_request


def read(obs):
    got = _per_request.overflows(obs, _per_request.TILE)
    sweep = obs.get("sweep") or {}
    if got is None or not sweep.get("candidates"):
        return None
    _, n_overflow, n_tiles = got
    workloads = sweep["candidate_workloads"] / sweep["candidates"]
    return n_overflow / (n_tiles * workloads) * 100.0
