"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache directory is fixed for a
process once JAX first uses it.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch import cache
    cache.REPO_CACHE_DIR = sys.argv[1]      # stands in for <repo>/.jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(cache.enable_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(8)).block_until_ready()
""")


def _entries(path):
    return [f for _, _, files in os.walk(path) for f in files] \
        if os.path.isdir(path) else []


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the cache is written there and
    nowhere else; without it, to the repository's fixed directory."""
    env_dir, repo_dir = str(tmp_path / "env_cache"), str(tmp_path / "repo")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", SCRIPT, repo_dir], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    used, unused = (env_dir, repo_dir) if env_set else (repo_dir, env_dir)
    assert r.stdout.strip().splitlines()[-1] == used
    assert _entries(used)
    assert not os.path.exists(unused)
