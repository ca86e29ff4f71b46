"""dispatch_ms_per_tile: the program's ``dispatch`` span (the jitted call
returning its futures) summed over the traced window, per tile
(``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "dispatch", _per_request.TILE)
