"""Entry point of the benchmark: one cell, run once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Prints the numbers compared with the
reference as the last lines of standard error and one JSON object as the
last line of standard output; exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import allocator  # noqa: E402

allocator.fix()
os.environ.setdefault("REPRO_SAVE_HLO", "0")
# the TPU runtime logs under TMPDIR, not a fixed path shared between runs
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "tpu_logs"))

# the census lowers on 512 host placeholder devices: the dry-run module sets
# their count before JAX initializes a backend, so it is imported first
from repro.launch import dryrun  # noqa: E402,F401

from bench import harness  # noqa: E402

if __name__ == "__main__":
    try:
        harness.main(t_start=T_START)
    except harness.NoDevice as e:
        sys.exit(e.code)
