"""Jit'd public wrappers around the Pallas kernels.

These are the entry points models/benchmarks/campaigns use; each handles
layout (GQA head expansion, padding, column packing) and dispatches to the
kernel.  ``interpret`` defaults to the active JAX backend
(``default_interpret``): compiled on TPU, interpreted everywhere else; an
explicit ``interpret=`` argument is the only override, so nothing in the
environment can put a TPU run into the interpreter.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import costmodel
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.dse_sweep import (dse_sweep_reduced, stage_cand_cols,
                                     dse_sweep_staged as _launch_staged)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.telemetry.trace import NULL_TRACER


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode by default: compiled on
    TPU, interpreted on CPU/GPU backends (CPU CI exercises interpret mode
    end to end).  An explicit ``interpret=`` on a wrapper overrides it."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_attention(q, k, v, *, causal: bool, block_q: int, block_k: int,
                     interpret: bool):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    hv = v.shape[-1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hv)
    o = flash_attention_pallas(qf, kf, vf, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return o.reshape(B, H, S, hv).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: [B, S, H, hd]; k, v: [B, S, KV, hd] (GQA expanded here)."""
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k,
                            interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan(x, dt, A, B, C, *, chunk: int, interpret: bool):
    return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """Mamba2 SSD scan: x [b,S,nh,hp], dt [b,S,nh], A [nh], B/C [b,S,1,ds]."""
    return _ssd_scan(x, dt, A, B, C, chunk=chunk,
                     interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("stride", "padding", "tile_h",
                                             "interpret"))
def _conv2d(x, w, *, stride: int, padding: str, tile_h: int, interpret: bool):
    kh, kw = w.shape[:2]
    if stride != 1:
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if padding == "SAME" and (kh > 1 or kw > 1):
        x = jnp.pad(x, ((0, 0), (kh // 2, (kh - 1) // 2),
                        (kw // 2, (kw - 1) // 2), (0, 0)))
    return conv2d_pallas(x, w, tile_h=tile_h, interpret=interpret)


def conv2d(x, w, *, stride: int = 1, padding: str = "SAME", tile_h: int = 8,
           interpret: Optional[bool] = None):
    """NHWC conv via the Pallas kernel (stride-1 path); strided convs fall
    back to XLA (they are 1x1 projections in ResNet, already MXU-shaped)."""
    return _conv2d(x, w, stride=stride, padding=padding, tile_h=tile_h,
                   interpret=_resolve_interpret(interpret))


def _constraint_kw(constraint) -> dict:
    if constraint is None:
        return dict(max_power_w=None, max_latency_s=None, min_hbm_fit=True)
    return dict(max_power_w=constraint.max_power_w,
                max_latency_s=constraint.max_latency_s,
                min_hbm_fit=constraint.min_hbm_fit)


def dse_sweep(cand_arrays, wl_cols, *,
              sim: costmodel.SimConfig = costmodel.SimConfig(),
              constraint=None, max_survivors: int = 2048,
              n_valid: Optional[int] = None,
              interpret: Optional[bool] = None,
              tracer=NULL_TRACER) -> costmodel.SweepReduced:
    """Fused on-device campaign evaluator (see ``kernels.dse_sweep``).

    One launch evaluates all workload rows of ``wl_cols`` against the
    candidate tile ``cand_arrays`` (one column per ``CAND_COLS`` name,
    packed here) and reduces each to its feasible Pareto survivors +
    frontier-accounting aggregates.  ``constraint`` duck-types
    ``dse.Constraint`` (``max_power_w`` / ``max_latency_s`` /
    ``min_hbm_fit``); interpret mode (the CPU default) computes float64 —
    campaign frontiers then hold the numpy evaluator's exact candidate set
    — and compiled mode computes float32.  ``tracer`` times the host
    stages (``dse_sweep_reduced``).
    """
    return dse_sweep_reduced(cand_arrays, wl_cols, sim=sim,
                             max_survivors=max_survivors, n_valid=n_valid,
                             interpret=_resolve_interpret(interpret),
                             tracer=tracer, **_constraint_kw(constraint))


def stage_dse_sweep(cand_arrays, n_workloads: int, *,
                    n_valid: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """``cand_arrays`` staged once for many ``dse_sweep_staged`` launches
    with ``n_workloads`` rows: the ``stage_cand_cols`` matrix, copied to the
    device when compiled.  That copy is the only explicit one; ``dse_sweep``
    hands its host matrix to the jitted call."""
    interpret = _resolve_interpret(interpret)
    cand_cols = stage_cand_cols(cand_arrays, n_workloads, n_valid=n_valid,
                                interpret=interpret)
    return cand_cols if interpret else jax.device_put(cand_cols)


def dse_sweep_staged(stage, wl_cols, *,
                     sim: costmodel.SimConfig = costmodel.SimConfig(),
                     constraint=None, max_survivors: int = 2048,
                     interpret: Optional[bool] = None,
                     tracer=NULL_TRACER) -> costmodel.SweepReduced:
    """``dse_sweep`` on a staged tile: ``stage()`` returns the matrix
    ``stage_dse_sweep`` made for ``wl_cols``' row count, and is called
    inside the launch's ``pack`` span (``kernels.dse_sweep``)."""
    return _launch_staged(stage, wl_cols, sim=sim,
                          max_survivors=max_survivors,
                          interpret=_resolve_interpret(interpret),
                          tracer=tracer, **_constraint_kw(constraint))
