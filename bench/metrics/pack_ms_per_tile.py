"""pack_ms_per_tile: the program's ``pack`` span (column stack, lane padding
and float32 cast of the tile, on the Pallas path) summed over the traced
window, per tile (``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "pack", _per_request.TILE)
