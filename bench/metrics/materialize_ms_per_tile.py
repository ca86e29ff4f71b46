"""materialize_ms_per_tile: the program's ``materialize`` span (``Candidate``
objects built for the survivors of each workload) summed over the traced
window, per tile (``tile_eval``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "materialize", _per_request.TILE)
