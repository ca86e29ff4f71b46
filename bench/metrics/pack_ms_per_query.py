"""pack_ms_per_query: the program's ``pack`` span (column stack, lane padding
and float32 cast of the whole space) summed over the traced window, per
novel query (``mini_campaign``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "pack", _per_request.QUERY)
