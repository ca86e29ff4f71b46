"""sweep_hbm_roofline: the fused sweep's least time at the HBM peak over the
device's busy time in the traced window, in %.

The bytes are the sweep's I/O contract, counted from the valid candidates N
and workloads W of every launch in the window, whatever implements the
sweep: 18 float32 candidate columns read per candidate, 3 float32 outputs
(energy, latency, feasibility) written per candidate and workload.  Busy
time covers everything the device ran (kernel, screen, compaction, copies),
so the share never exceeds what the sweep alone would read.
"""

CAND_COLUMNS = 18
OUTPUTS = 3
FLOAT32 = 4


def sweep_bytes(candidates: int, candidate_workloads: int) -> int:
    return FLOAT32 * (CAND_COLUMNS * candidates + OUTPUTS * candidate_workloads)


def read(obs):
    sweep, peaks = obs.get("sweep"), obs.get("peaks")
    if not sweep or not peaks or not obs.get("busy_s"):
        return None
    least_s = sweep_bytes(sweep["candidates"], sweep["candidate_workloads"]) \
        / peaks["hbm_bytes_per_s"]
    return least_s / obs["busy_s"] * 100.0
