"""Shared arithmetic of the span readers: the mean of a program span."""


def mean_ms(obs, name):
    """Mean duration of the program's ``name`` span over the window, in
    ms; None when the window holds no such span."""
    total, count = obs.get("spans", {}).get(name, (0.0, 0))
    return total / count * 1e3 if count else None


def idle_pct(obs):
    """Share of the traced window in which no operation ran on the
    device, in %; None without a trace."""
    if "busy_s" not in obs or not obs.get("window_s"):
        return None
    return (1.0 - obs["busy_s"] / obs["window_s"]) * 100.0
