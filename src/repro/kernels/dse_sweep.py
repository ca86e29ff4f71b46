"""Fused DSE-sweep Pallas kernel: the campaign evaluator as ONE launch.

The streaming campaign's hot loop is not a neural-net op — it is the cost
model itself, evaluated over millions of (workload x candidate) pairs.  This
kernel moves the whole per-tile pipeline on device: census scaling
(``costmodel.scale_census``), the topology-aware roofline simulation
(``costmodel.simulate_batch`` with ``xp=jnp`` — literally the same function
the numpy oracle path runs, so the arithmetic cannot diverge), and the
constraint mask (``costmodel.sweep_feasibility``), for every cached workload
in one ``pallas_call``.

Layout: candidates arrive as one packed [len(CAND_COLS), N] column matrix
(padding lanes carry ``valid=0``); per-workload scalars as the packed
[W, len(WL_COLS)] matrix, broadcast as a leading data axis
([W, 1] x [1, N] -> [W, N]) so the kernel body is W-independent — all
elementwise VPU math, no gathers, no host round-trips between workloads.
The launch is a 1-D grid over lane blocks (``block_lanes``): each step
streams one [len(CAND_COLS), B] candidate block and writes three [W, B]
output blocks, so VMEM holds one block whatever the tile size and the
HBM<->VMEM copies are pipelined across steps.

Precision tiers: in interpret mode (CPU CI / debugging) the whole sweep runs
float64 under a scoped ``jax.enable_x64(True)`` so the resulting
frontier holds the float64 numpy evaluator's exact candidate set (values
agree to ~1 ulp — XLA fusion noise only); compiled on an accelerator it
runs float32 (the same tier as ``simulate_batch_jit``, ~1e-6 relative).

The jitted wrapper fuses the per-tile skyline pre-reduction behind the
kernel (``costmodel._reduce_launch``: the screen ``_screen_rows`` — a
conservative dominance screen whose survivors are a guaranteed superset of
the tile's feasible Pareto set — then the survivors compacted on the
device), so one launch copies O(survivors) per tile to the host and the
full [W, N] rows stay on the device, read only where a workload overflows
``max_survivors`` — the same ``SweepReduced`` contract as the jit
reference path ``costmodel.sweep_workloads_reduced_jit``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import costmodel
from repro.telemetry.trace import NULL_TRACER

# packed candidate-column order of the [len(CAND_COLS), N] matrix the kernel
# consumes: batch axes first, then the gathered chip-table columns
CAND_COLS = ("n_chips", "freq_mhz", "mesh_pod", "mesh_data", "mesh_model",
             "valid") + costmodel.SWEEP_GATHER_FIELDS

LANE = 128   # TPU lane width; candidate tiles are padded to a multiple
# [W, B] elements one grid step computes: bounds the kernel's VMEM working set
# (the body keeps a few dozen [W, B] float32 temporaries) independently of W
BLOCK_ELEMS = 6 * 4096
MAX_BLOCK_LANES = 4096


def block_lanes(n_workloads: int) -> int:
    """Lanes per grid step for ``n_workloads`` rows: ``MAX_BLOCK_LANES``
    for up to six workloads, fewer (in ``LANE`` steps) for more."""
    per_row = BLOCK_ELEMS // max(int(n_workloads), 1)
    return max(LANE, min(MAX_BLOCK_LANES, per_row // LANE * LANE))


def _sweep_kernel(wl_ref, cand_ref, e_ref, l_ref, f_ref, *,
                  sim: costmodel.SimConfig, max_power_w, max_latency_s,
                  min_hbm_fit: bool):
    """All workloads x the whole candidate tile in one kernel body.

    The workload axis is a broadcast DATA axis — per-workload scalars enter
    as [W, 1] column slices against the [1, N] candidate rows, so every
    simulation step is a single [W, N] elementwise op and the traced graph
    is independent of the workload count (no per-workload unrolling)."""
    col = {name: cand_ref[i:i + 1, :] for i, name in enumerate(CAND_COLS)}
    wl = {name: wl_ref[:, i:i + 1] for i, name in enumerate(costmodel.WL_COLS)}
    ana = costmodel.scale_census(wl, wl["base_chips"], col["n_chips"], xp=jnp)
    batch = costmodel.simulate_batch(
        ana, None, col["n_chips"], col["freq_mhz"], sim=sim, xp=jnp,
        gathered={f: col[f] for f in costmodel.SIM_GATHER_FIELDS},
        mesh_pod=col["mesh_pod"], mesh_data=col["mesh_data"],
        mesh_model=col["mesh_model"])
    feas = costmodel.sweep_feasibility(
        batch.power_w, batch.latency_s, col["n_chips"], col["hbm_bytes"],
        wl["base_chips"], wl["state_gb_per_device"], col["valid"],
        max_power_w, max_latency_s, min_hbm_fit, xp=jnp)
    e_ref[...] = jnp.broadcast_to(batch.energy_j, e_ref.shape)
    l_ref[...] = jnp.broadcast_to(batch.latency_s, l_ref.shape)
    f_ref[...] = feas.astype(e_ref.dtype)


def dse_sweep_pallas(cand_cols, wl_cols, *, sim: costmodel.SimConfig,
                     max_power_w=None, max_latency_s=None,
                     min_hbm_fit: bool = True, interpret: bool = True):
    """Raw kernel launch: (energy, latency, feasible) as [W, N] arrays.

    ``cand_cols`` is the packed [len(CAND_COLS), N] candidate matrix with N a
    multiple of ``block_lanes(W)`` (``_pad_lanes`` pads to it); ``wl_cols``
    the [W, len(WL_COLS)] workload matrix, resident in every grid step.
    """
    ncol, n = cand_cols.shape
    if ncol != len(CAND_COLS):
        raise ValueError(f"cand_cols must be [{len(CAND_COLS)}, N] "
                         f"({CAND_COLS}), got {cand_cols.shape}")
    w_count = wl_cols.shape[0]
    block = min(block_lanes(w_count), n)
    if n % block:
        raise ValueError(f"lane count {n} is not a multiple of the "
                         f"{block}-lane block")
    kernel = functools.partial(
        _sweep_kernel, sim=sim, max_power_w=max_power_w,
        max_latency_s=max_latency_s, min_hbm_fit=min_hbm_fit)
    dt = cand_cols.dtype
    out_spec = pl.BlockSpec((w_count, block), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec(wl_cols.shape, lambda i: (0, 0)),
                  pl.BlockSpec((ncol, block), lambda i: (0, i))],
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((w_count, n), dt)] * 3,
        interpret=interpret,
        name="dse_sweep",
    )(wl_cols, cand_cols)


@functools.lru_cache(maxsize=None)
def _jit_dse_sweep(sim: costmodel.SimConfig, max_power_w, max_latency_s,
                   min_hbm_fit: bool, interpret: bool, max_survivors: int):
    def run(cand_cols, wl_cols):
        e, l, f = dse_sweep_pallas(
            cand_cols, wl_cols, sim=sim, max_power_w=max_power_w,
            max_latency_s=max_latency_s, min_hbm_fit=min_hbm_fit,
            interpret=interpret)
        return costmodel._reduce_launch(e, l, f > 0, max_survivors)

    return jax.jit(run)


def pack_cand_cols(arrays: Mapping, dtype=np.float64) -> np.ndarray:
    """Stack the ``CAND_COLS`` entries of ``arrays`` into the packed matrix."""
    return np.stack([np.asarray(arrays[k], dtype) for k in CAND_COLS])


def padded_lanes(n: int, n_workloads: int) -> int:
    """The lane count a tile of ``n`` candidates is padded to: a ``LANE``
    multiple, and past one block a ``block_lanes(n_workloads)`` multiple."""
    target = -(-max(n, 1) // LANE) * LANE
    block = block_lanes(n_workloads)
    return target if target <= block else -(-target // block) * block


def _pad_lanes(cand_cols: np.ndarray, n_valid: int,
               n_workloads: int) -> np.ndarray:
    """Right-pad the lane axis to ``padded_lanes``; padding lanes copy lane
    0 (safe arithmetic — no zero divides) with ``valid`` forced to 0."""
    n = cand_cols.shape[1]
    target = padded_lanes(n, n_workloads)
    if n < target:
        fill = np.repeat(cand_cols[:, :1], target - n, axis=1)
        cand_cols = np.concatenate([cand_cols, fill], axis=1)
    if n_valid < cand_cols.shape[1]:
        valid_row = CAND_COLS.index("valid")
        cand_cols = cand_cols.copy()
        cand_cols[valid_row, n_valid:] = 0.0
    return cand_cols


def stage_cand_cols(cand_arrays: Mapping, n_workloads: int, *,
                    n_valid: Optional[int] = None,
                    interpret: bool = True) -> np.ndarray:
    """The launch-ready candidate matrix of one tile: the ``CAND_COLS``
    columns of ``cand_arrays`` stacked (``pack_cand_cols``), padded to
    ``padded_lanes(N, n_workloads)`` with ``valid`` 0 past ``n_valid``
    (``_pad_lanes``), and float32 when compiled (float64 in interpret
    mode).  It depends on the workloads only through their count, so one
    staged matrix serves every launch of that tile with as many rows."""
    cand_cols = pack_cand_cols(cand_arrays)
    n_valid = cand_cols.shape[1] if n_valid is None else int(n_valid)
    cand_cols = _pad_lanes(cand_cols, n_valid, n_workloads)
    return cand_cols if interpret else cand_cols.astype(np.float32)


def dse_sweep_staged(stage: Callable[[], Any], wl_cols: np.ndarray, *,
                     sim: costmodel.SimConfig = costmodel.SimConfig(),
                     max_power_w: Optional[float] = None,
                     max_latency_s: Optional[float] = None,
                     min_hbm_fit: bool = True,
                     max_survivors: int = 2048,
                     interpret: bool = True,
                     tracer=NULL_TRACER) -> costmodel.SweepReduced:
    """The fused launch on a staged candidate matrix.

    ``stage()`` returns the ``stage_cand_cols`` matrix of the tile for
    ``wl_cols``' row count: made on the spot, or made before and kept (on
    the device when compiled).  It runs inside the ``pack`` span, with the
    cast of ``wl_cols``; the stages of ``costmodel.run_reduced_launch``
    follow.  A host matrix crosses to the device inside the jitted call,
    so its copy starts in ``dispatch`` and ends within ``device_wait``.
    """
    wl_cols = np.asarray(wl_cols, np.float64)
    with tracer.span("pack"):
        cand_cols = stage()
        if not interpret:
            wl_cols = wl_cols.astype(np.float32)
    fn = _jit_dse_sweep(sim, max_power_w, max_latency_s, bool(min_hbm_fit),
                        bool(interpret), int(max_survivors))
    with (jax.enable_x64(True) if interpret else contextlib.nullcontext()):
        return costmodel.run_reduced_launch(fn, (cand_cols, wl_cols),
                                            int(max_survivors), tracer)


def dse_sweep_reduced(cand_arrays: Mapping, wl_cols: np.ndarray, *,
                      sim: costmodel.SimConfig = costmodel.SimConfig(),
                      max_power_w: Optional[float] = None,
                      max_latency_s: Optional[float] = None,
                      min_hbm_fit: bool = True,
                      max_survivors: int = 2048,
                      n_valid: Optional[int] = None,
                      interpret: bool = True,
                      tracer=NULL_TRACER) -> costmodel.SweepReduced:
    """Fused sweep + on-device skyline reduction of one candidate tile.

    ``cand_arrays`` maps every ``CAND_COLS`` name to a length-N column;
    ``wl_cols`` is [W, len(WL_COLS)]; ``n_valid`` marks the real
    (un-padded) tile length.  Returns the ``SweepReduced`` contract shared
    with the jit reference path.  Interpret mode computes in float64
    (scoped x64): the campaign frontier it produces holds the numpy
    evaluator's exact candidate set, with values agreeing to ~1 ulp (XLA
    fusion noise only).  Compiled mode computes in float32.

    The tile is staged (``stage_cand_cols``) inside the launch's ``pack``
    span and launched by ``dse_sweep_staged``; ``tracer`` (a
    ``SpanTracer`` or ``Telemetry``) times the host stages.
    """
    n_workloads = np.shape(wl_cols)[0]
    return dse_sweep_staged(
        lambda: stage_cand_cols(cand_arrays, n_workloads, n_valid=n_valid,
                                interpret=interpret),
        wl_cols, sim=sim, max_power_w=max_power_w,
        max_latency_s=max_latency_s, min_hbm_fit=min_hbm_fit,
        max_survivors=max_survivors, interpret=interpret, tracer=tracer)
