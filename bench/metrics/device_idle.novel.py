"""Share of the traced window in which no operation ran on the device, in %
(1 - busy / window; busy is the union of the device's module events)."""

from bench.metrics import _spans


def read(obs):
    return _spans.idle_pct(obs)
