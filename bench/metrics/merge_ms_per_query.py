"""merge_ms_per_query: the program's ``merge`` span (the fold of the query's
survivors into its frontier and answer) summed over the traced window, per
novel query (``mini_campaign``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "merge", _per_request.QUERY)
