"""merge_ms_per_tile: mean of the program's ``merge`` span per tile over the
traced window, in ms."""

from bench.metrics import _spans


def read(obs):
    return _spans.mean_ms(obs, "merge")
