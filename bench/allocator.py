"""Fixed settings of glibc's allocator for a run, made before anything
large is allocated.

The program's hot path allocates arrays of several MB for every tile and
starts a staging thread for every campaign.  Left to its defaults, glibc
decides per process, and from the order of what was freed before, whether
such arrays come from the heap or from fresh pages (its mmap threshold
moves with the largest block freed so far), and it gives each new thread a
new arena until there are eight per core.  On one v5e host (13 cores) that
made a whole run of selection queries 3.4x slower than another and stepped
a campaign's time down by 14% after its 104th campaign.  Fixed here at what
the defaults reach in a long-lived process: blocks under 32 MiB from the
heap, no trimming below 512 MiB, and four arenas.
"""

import ctypes

M_TRIM_THRESHOLD = -1
M_TOP_PAD = -2
M_MMAP_THRESHOLD = -3
M_ARENA_MAX = -8

SETTINGS = ((M_MMAP_THRESHOLD, 32 << 20), (M_TRIM_THRESHOLD, 512 << 20),
            (M_TOP_PAD, 64 << 20), (M_ARENA_MAX, 4))


def fix() -> None:
    """Apply ``SETTINGS`` with ``mallopt``; raises if glibc refuses one."""
    libc = ctypes.CDLL("libc.so.6")
    for param, value in SETTINGS:
        if libc.mallopt(param, value) != 1:
            raise RuntimeError(f"mallopt({param}, {value}) was refused")
