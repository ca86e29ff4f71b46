"""The process settings the dry-run module makes before JAX starts a backend.

Each case runs in a fresh interpreter: the settings are read once, when the
first backend starts.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = ("import os; from repro.launch import dryrun; "
          "print(os.environ['TPU_PREMAPPED_BUFFER_SIZE']); "
          "print(os.environ['XLA_FLAGS'])")


@pytest.mark.parametrize("given", [None, "4294967296"])
def test_premapped_buffer_default_and_override(given):
    """Importing the dry-run module gives the TPU runtime a 256 MiB host
    premapped buffer unless the caller set one, and keeps the 512 host
    placeholder devices."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("TPU_PREMAPPED_BUFFER_SIZE", None)
    if given is not None:
        env["TPU_PREMAPPED_BUFFER_SIZE"] = given
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.splitlines()
    assert out[0] == (given or str(256 << 20))
    assert "--xla_force_host_platform_device_count=512" in out[1]
