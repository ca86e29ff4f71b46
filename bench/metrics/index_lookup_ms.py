"""index_lookup_ms: mean of the program's ``index_lookup`` span per query,
in ms."""

from bench.metrics import _spans


def read(obs):
    return _spans.mean_ms(obs, "index_lookup")
