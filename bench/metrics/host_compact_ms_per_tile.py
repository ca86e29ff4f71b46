"""host_compact_ms_per_tile: the program's ``host_compact`` span (the numpy
compaction of each workload's survivors) summed over the traced window, per
tile (``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "host_compact", _per_request.TILE)
