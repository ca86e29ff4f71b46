"""device_wait_ms_per_query: the program's ``device_wait`` span (the host
waiting for the chip to finish the launch) summed over the traced window,
per novel query (``mini_campaign``), in ms."""

from bench.metrics import _per_request


def read(obs):
    return _per_request.ms_per(obs, "device_wait", _per_request.QUERY)
