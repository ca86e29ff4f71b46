"""mini_campaign_ms: mean of the program's ``mini_campaign`` span (the
exact path of a query the index cannot answer: candidate slice, one fused
launch, merge) per novel query, in ms."""

from bench.metrics import _spans


def read(obs):
    return _spans.mean_ms(obs, "mini_campaign")
