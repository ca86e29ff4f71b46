"""overflow_ms_per_query: the program's ``overflow_reduce`` span (the host
Pareto reduction of one workload whose on-device screen overflowed
``max_survivors``) summed over the traced window, per novel query
(``mini_campaign``), in ms; 0 when no workload overflowed."""

from bench.metrics import _per_request


def read(obs):
    got = _per_request.overflows(obs, _per_request.QUERY)
    return None if got is None else got[0]
