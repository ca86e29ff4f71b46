"""tile_wait_ms_per_tile: the program's ``tile_wait`` span (the campaign
waiting on the prefetcher for the next tile) summed over the traced window,
per tile (``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "tile_wait", _per_request.TILE)
