"""fetch_ms_per_tile: the program's ``fetch`` span (the copy of every output
of the launch to the host) summed over the traced window, per tile
(``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "fetch", _per_request.TILE)
