"""snapshot_ms_per_tile: the program's ``snapshot`` span (the trajectory point
and hypervolume after each fold) summed over the traced window, per tile
(``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "snapshot", _per_request.TILE)
