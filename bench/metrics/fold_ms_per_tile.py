"""fold_ms_per_tile: the program's ``fold`` span (the frontier fold: union,
dedup, Pareto mask and order, per workload) summed over the traced window,
per tile (``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "fold", _per_request.TILE)
