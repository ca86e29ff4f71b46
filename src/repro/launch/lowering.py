"""Shared lowering/compile/analysis pipeline for dry-runs and perf iteration.

lower_cell(cfg, shape, mesh) -> dict with:
  memory   — per-device bytes from compiled.memory_analysis()
  cost     — compiled.cost_analysis() (XLA's census; counts loop bodies ONCE)
  hxa      — HxA census (loop-trip-aware flops/bytes/collective bytes)
  roofline — the three §Roofline terms + dominant bottleneck
  sim      — calibrated latency/power/energy (the slow-accurate path)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import costmodel, hxa
from repro.hw import get_chip
from repro.models import api
from repro.models.dist import make_dist
from repro import optim

_COERCE = {
    "remat": str, "capacity_factor": float, "optimizer": str, "dtype": str,
    "ssm_chunk": int, "attn_type": str, "attn_impl": str, "ssm_impl": str,
    "cache_layout": str,
}


def kernel_substitution(cfg: ArchConfig, shape: ShapeConfig, n_chips: int,
                        mesh_model: int) -> Dict[str, float]:
    """Analytic HBM-traffic delta of Pallas kernelization.

    The XLA fallback materializes fp32 attention-score / SSD-decay blocks in
    HBM every chunk; the fused Pallas kernels (kernels/flash_attention.py,
    kernels/ssd_scan.py) keep them in VMEM.  The dry-run cannot lower TPU
    pallas_call on the CPU backend, so kernelized cells substitute the
    score-block traffic analytically (documented in EXPERIMENTS.md §Perf).
    Returns bytes saved per device (>= 0).
    """
    saved = 0.0
    if shape.kind == "decode":
        return {"attn_bytes_saved_pd": 0.0, "ssm_bytes_saved_pd": 0.0}
    passes = 3.0 if shape.kind == "train" else 1.0   # fwd + bwd(recompute+grads)
    touches = 5.0                                     # s write/read, p write/read, d(p)
    if cfg.attn_impl == "pallas" and cfg.attn_type != "none" and cfg.num_heads:
        causal_pairs = shape.seq_len * shape.seq_len / 2.0
        heads = cfg.num_heads
        layers = cfg.num_layers + cfg.encoder_layers
        total = (causal_pairs * heads * layers * shape.global_batch
                 * 4.0 * touches * passes)
        saved_attn = total / n_chips
    else:
        saved_attn = 0.0
    if cfg.ssm_impl == "pallas" and cfg.ssm_state:
        Q = cfg.ssm_chunk
        nc = shape.seq_len // max(Q, 1)
        blocks = nc * Q * Q * cfg.ssm_nheads * shape.global_batch
        saved_ssm = blocks * 4.0 * touches * passes * cfg.num_layers / n_chips
    else:
        saved_ssm = 0.0
    return {"attn_bytes_saved_pd": saved_attn, "ssm_bytes_saved_pd": saved_ssm}


def apply_overrides(cfg: ArchConfig, overrides: Dict[str, str]) -> ArchConfig:
    if not overrides:
        return cfg
    kw = {}
    for k, v in overrides.items():
        field_types = {f.name: f.type for f in dataclasses.fields(cfg)}
        if k not in field_types:
            raise KeyError(f"unknown config field {k}")
        coerce = _COERCE.get(k)
        if coerce is None:
            cur = getattr(cfg, k)
            coerce = type(cur) if cur is not None else str
            if coerce is bool:
                v = v.lower() in ("1", "true", "yes")
                kw[k] = v
                continue
        kw[k] = coerce(v)
    return dataclasses.replace(cfg, **kw)


def _with_shardings(shape_tree, spec_tree, mesh):
    return jax.tree_util.tree_map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shape_tree, spec_tree)


def sharded_bytes_per_device(sds_tree) -> float:
    """Analytic per-device bytes of a sharded ShapeDtypeStruct tree.

    XLA:CPU's ``temp_size_in_bytes`` ignores buffer reuse, so residency
    ("does the state fit?") is computed from shard shapes directly — exact
    for weights/optimizer/caches, which dominate residency.
    """
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(sds_tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            shard_shape = sharding.shard_shape(leaf.shape)
        else:
            shard_shape = leaf.shape
        n = 1
        for d in shard_shape:
            n *= d
        total += n * leaf.dtype.itemsize
    return total


def _memory_dict(compiled) -> Dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        out[k] = getattr(ma, k, 0)
    out["per_device_total_gb"] = (out["argument_size_in_bytes"]
                                  + out["output_size_in_bytes"]
                                  - out["alias_size_in_bytes"]) / 1e9
    out["per_device_peak_gb"] = out["peak_memory_in_bytes"] / 1e9
    return out


def _cost_dict(compiled) -> Dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               overrides: Optional[Dict[str, str]] = None,
               chip_name: str = "tpu-v5e",
               include_hlo: bool = False) -> Dict:
    cfg = apply_overrides(cfg, overrides or {})
    dist = make_dist(mesh)
    model = api.build_model(cfg)
    n_chips = mesh.devices.size

    if shape.kind == "train":
        optimizer = optim.make_optimizer(cfg.optimizer)
        specs, state_shape = api.state_specs(model, optimizer, dist,
                                             max_seq=shape.seq_len)
        state_in = _with_shardings(
            state_shape,
            api.TrainState(params=specs.params, opt=specs.opt), mesh)
        batch_in = api.input_specs(cfg, shape, dist)
        step = api.make_train_step(model, optimizer, dist)
        resident = (state_in, batch_in)
        lowered = jax.jit(step, donate_argnums=(0,)).lower(state_in, batch_in)
    elif shape.kind == "prefill":
        specs, params_shape = _param_specs_only(model, dist, shape)
        params_in = _with_shardings(params_shape, specs, mesh)
        batch_in = api.input_specs(cfg, shape, dist)
        step = api.make_serve_step(model, "prefill", dist)
        resident = (params_in, batch_in)
        lowered = jax.jit(step).lower(params_in, batch_in)
    else:  # decode
        specs, params_shape = _param_specs_only(model, dist, shape)
        params_in = _with_shardings(params_shape, specs, mesh)
        batch_in = api.input_specs(cfg, shape, dist)
        cache_in = api.cache_specs(model, shape, dist)
        step = api.make_serve_step(model, "decode", dist)
        resident = (params_in, batch_in, cache_in)
        lowered = jax.jit(step, donate_argnums=(2,)).lower(
            params_in, batch_in, cache_in)

    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    analysis = hxa.analyze_hlo_text(hlo_text)
    analysis["hbm_bytes_xla"] = analysis["hbm_bytes"]
    subst = kernel_substitution(cfg, shape, n_chips,
                                dict(zip(mesh.axis_names,
                                         mesh.devices.shape)).get("model", 1))
    saved = subst["attn_bytes_saved_pd"] + subst["ssm_bytes_saved_pd"]
    if saved:
        analysis["hbm_bytes"] = max(analysis["hbm_bytes"] - saved,
                                    analysis["hbm_bytes"] * 0.05)
    analysis["kernel_substitution"] = subst
    chip = get_chip(chip_name)
    roof = costmodel.roofline_terms(analysis, chip, n_chips)
    sim = costmodel.simulate(analysis, chip, n_chips,
                             mesh=mesh.devices.shape)

    mf = cfg.model_flops(shape)
    hlo_flops_global = analysis["flops"] * n_chips
    mem = _memory_dict(compiled)
    mem["state_gb_per_device"] = sharded_bytes_per_device(resident) / 1e9
    result = {
        "config": {k: v for k, v in dataclasses.asdict(cfg).items()
                   if not k.startswith("_")},
        "memory": mem,
        "cost": _cost_dict(compiled),
        "hxa": {k: analysis[k] for k in
                ("flops", "hbm_bytes", "hbm_bytes_xla", "collective_bytes",
                 "wire_bytes", "op_counts", "hbm_by_opcode", "collectives",
                 "loops", "n_computations", "kernel_substitution")},
        "roofline": roof,
        "sim": sim.as_dict(),
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_flops_global) if hlo_flops_global else 0.0,
        "hlo_chars": len(hlo_text),
    }
    if include_hlo:
        result["hlo_text"] = hlo_text
    return result


def _param_specs_only(model, dist, shape):
    from repro.models.sharding import param_specs
    import functools
    params_shape = jax.eval_shape(
        functools.partial(model.init, max_seq=shape.seq_len),
        jax.random.PRNGKey(0))
    return param_specs(params_shape, dist), params_shape
