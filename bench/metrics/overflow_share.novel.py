"""overflow_share.novel: the share of novel queries (``mini_campaign``, one
workload each) whose on-device screen overflowed ``max_survivors`` and was
reduced on the host (``overflow_reduce``), in %."""

from bench.metrics import _per_request


def read(obs):
    got = _per_request.overflows(obs, _per_request.QUERY)
    if got is None:
        return None
    _, n_overflow, n_queries = got
    return n_overflow / n_queries * 100.0
