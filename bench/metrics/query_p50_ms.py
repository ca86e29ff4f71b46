"""query_p50_ms: 50th percentile (numpy's linear rule) of the client-side
latency of every query answered in the window, in ms."""

import numpy as np


def read(obs):
    lat = obs.get("latencies_s") or []
    if obs.get("kind") != "selection" or not lat:
        return None
    return float(np.percentile(np.asarray(lat) * 1e3, 50))
