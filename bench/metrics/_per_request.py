"""Shared arithmetic of the per-request span readers: the total of one of
the program's spans over the traced window, divided by the count of the
span of one request (``tile_eval`` per tile of a campaign,
``mini_campaign`` per novel query), in ms.  A reader returns None when the
window holds no request span, or no span that a program with these stages
always records (so a program without them reports nothing)."""

TILE = "tile_eval"
QUERY = "mini_campaign"
OVERFLOW = "overflow_reduce"
# recorded on every launch of a program that splits the launch into its
# stages: tells "no workload overflowed" (0) from "no such span" (None)
STAGE = "device_wait"


def _span(obs, name):
    total, count = obs.get("spans", {}).get(name, (0.0, 0))
    return total, count


def ms_per(obs, name, request):
    """Total ms of span ``name`` per ``request`` span; None when either is
    absent from the window."""
    _, n_req = _span(obs, request)
    total, count = _span(obs, name)
    if not n_req or not count:
        return None
    return total / n_req * 1e3


def overflows(obs, request):
    """(ms of ``overflow_reduce`` per ``request`` span, count of
    ``overflow_reduce``, count of ``request``); None when the window holds
    no request span or no launch stage, zeros when nothing overflowed."""
    _, n_req = _span(obs, request)
    if not n_req or not _span(obs, STAGE)[1]:
        return None
    total, count = _span(obs, OVERFLOW)
    return total / n_req * 1e3, count, n_req
