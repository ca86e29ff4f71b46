"""GPipe-style pipeline parallelism over a dedicated `stage` mesh axis.

shard_map + collective_permute microbatch rotation: tick t sends every
stage's activation to stage+1; stage s computes microbatch m at tick
t = s + m (the classic fill/steady/drain schedule, bubble fraction
(n_stage-1)/(n_micro+n_stage-1)).

The production 40-cell mesh uses DP x TP (+pod); this module provides the PP
axis for configurations that need it (very deep models / small batches) and
is validated for equivalence in tests/test_distributed.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh,
                   n_micro: int, stage_axis: str = "stage"):
    """stage_params: [n_stage, ...] (stacked per-stage weights);
    x: [B, ...] global batch.  Returns stage_{n-1}(...stage_0(x)) like a
    sequential stack, computed with pipeline rotation."""
    n_stage = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro
    xm = x.reshape(n_micro, mb, *x.shape[1:])
    perm = [(i, i + 1) for i in range(n_stage - 1)]

    def body(w_loc, xm_loc):
        stage = jax.lax.axis_index(stage_axis)
        w = jax.tree_util.tree_map(lambda a: a[0], w_loc)
        state = jnp.zeros_like(xm_loc[0])
        out = jnp.zeros_like(xm_loc)
        T = n_micro + n_stage - 1
        for t in range(T):
            inp = xm_loc[min(t, n_micro - 1)]
            cur = jnp.where(stage == 0, inp, state)
            # valid when this stage holds microbatch m = t - stage in range
            m = t - stage
            valid = (m >= 0) & (m < n_micro)
            y = stage_fn(w, cur)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage stores its finished microbatch
            is_last = stage == n_stage - 1
            idx = jnp.clip(m, 0, n_micro - 1)
            out = jnp.where(valid & is_last,
                            out.at[idx].set(y), out)
            # rotate activations to the next stage
            state = jax.lax.ppermute(y, stage_axis, perm)
        # only the last stage holds results; share them
        return jax.lax.psum(out, stage_axis)

    w_specs = jax.tree_util.tree_map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), stage_params)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(w_specs, P()), out_specs=P(),
                        check_vma=False)(stage_params, xm)
    return out.reshape(B, *x.shape[1:])
