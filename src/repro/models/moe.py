"""Mixture-of-Experts: dense reference path + expert-parallel (EP) path.

Paths:
  * ``moe_dense``  — computes every expert for every token, exact combine.
    O(E/topk) FLOP waste: used as the smoke/test oracle only.
  * ``moe_ep``     — production path inside ``jax.shard_map``: experts sharded
    over the `model` mesh axis (EP), expert weights additionally FSDP-sharded
    over `data` (gathered per layer, reduce-scattered on the backward pass).
    Dispatch is "gather mode": every model-group selects, from the local
    token set, the (token, expert) assignments routed to its experts with a
    fixed capacity, runs a grouped-GEMM over per-expert capacity buffers, and
    the groups' partial outputs are psum-combined.  For top-8 over 16 groups
    this moves the same bytes as a two-hop all-to-all while being drop-robust;
    an `alltoall` dispatch variant is evaluated in EXPERIMENTS.md §Perf.

Routing: softmax (DeepSeek-V2) or sigmoid+bias (DeepSeek-V3 aux-loss-free;
bias is a non-learned buffer, stop-gradient'd).  A load-balance auxiliary
metric is returned for telemetry either way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L


def init_moe(key, cfg) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = L.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, e), jnp.float32, scale=0.006),
        "router_bias": jnp.zeros((e,), jnp.float32),
        "w_in": L.dense_init(ks[1], (e, d, f), dt),
        "w_gate": L.dense_init(ks[2], (e, d, f), dt),
        "w_out": L.dense_init(ks[3], (e, f, d), dt, scale=0.02 / max(cfg.num_layers, 1) ** 0.5),
    }
    if cfg.num_shared_experts:
        import dataclasses
        shared_cfg = dataclasses.replace(cfg, d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
        p["shared"] = L.init_ffn(ks[4], shared_cfg)
    return p


def _route(p, cfg, xf):
    """xf: [T, D] -> (topk idx [T,k], combine weights [T,k], aux metrics)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    k = cfg.experts_per_token
    if cfg.router_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + jax.lax.stop_gradient(p["router_bias"])   # bias only biases SELECTION
        _, idx = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance telemetry (Switch-style): E * sum_e f_e * p_e
    E = cfg.num_experts
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_p = (jax.nn.softmax(logits, -1) if cfg.router_fn == "sigmoid" else probs).mean(0)
    aux = E * jnp.sum(frac * mean_p)
    return idx, w, aux


def _expert_ffn(xb, w_in, w_gate, w_out):
    """xb: [E_loc, C, D] capacity buffers; weights [E_loc, D, F] / [E_loc, F, D]."""
    h = jnp.einsum("ecd,edf->ecf", xb, w_in)
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, w_out)


def moe_dense(p, cfg, x) -> tuple:
    """Reference: compute all experts densely, exact combine.  x: [B,S,D]."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    idx, w, aux = _route(p, cfg, xf)
    combine = jnp.zeros((xf.shape[0], cfg.num_experts), jnp.float32)
    combine = combine.at[jnp.arange(xf.shape[0])[:, None], idx].add(w)
    ys = _expert_ffn(jnp.broadcast_to(xf, (cfg.num_experts, *xf.shape)),
                     p["w_in"], p["w_gate"], p["w_out"])        # [E, T, D]
    out = jnp.einsum("te,etd->td", combine.astype(x.dtype), ys)
    if "shared" in p:
        out = out + L.ffn_block(p["shared"], cfg, x).reshape(-1, D)
    return out.reshape(B, S, D), aux


def _capacity(cfg, tokens: int) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.capacity_factor / max(cfg.num_experts, 1))
    return max(8, -(-c // 8) * 8)   # round up to 8, floor 8 (decode shapes)


def moe_ep_local(p_local, cfg, x_loc, *, model_axis: str, fsdp_axis: Optional[str],
                 dp_axes: tuple = ()):
    """Body run per-device inside shard_map.

    x_loc: [b, s, D] local batch shard (replicated over `model_axis`).
    p_local: expert weights sharded [E_loc, ...] over model (+ FSDP on D dim).
    """
    n_groups = jax.lax.axis_size(model_axis)
    g = jax.lax.axis_index(model_axis)
    E, k = cfg.num_experts, cfg.experts_per_token
    E_loc = E // n_groups
    b, s, D = x_loc.shape
    T = b * s
    xf = x_loc.reshape(T, D)

    w_in, w_gate, w_out = p_local["w_in"], p_local["w_gate"], p_local["w_out"]
    n_fsdp = jax.lax.axis_size(fsdp_axis) if fsdp_axis is not None else 1
    C_cap = _capacity(cfg, T)
    F = w_in.shape[-1]
    mode = cfg.moe_fsdp
    if mode == "auto" and n_fsdp > 1:
        # weights gathered vs activations psum'd+gathered, bytes per layer:
        bytes_w = 3.0 * (E_loc * D * F) * 2
        bytes_a = 2.0 * 2.0 * E_loc * C_cap * F * 4 + E_loc * C_cap * D * 2
        mode = "partial" if bytes_a < bytes_w else "gather"
    if n_fsdp > 1 and mode != "partial":
        # ZeRO-3: gather this layer's expert weights just-in-time
        w_in = jax.lax.all_gather(w_in, fsdp_axis, axis=1, tiled=True)
        w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
        w_out = jax.lax.all_gather(w_out, fsdp_axis, axis=2, tiled=True)

    idx, wts, aux = _route(p_local, cfg, xf)                    # router replicated
    C = _capacity(cfg, T)

    # flatten assignments; keep only those routed to my expert group
    rid = jnp.repeat(jnp.arange(T), k)                          # [T*k]
    eid = idx.reshape(-1)
    wv = wts.reshape(-1)
    mine = (eid // E_loc) == g
    eloc = jnp.where(mine, eid % E_loc, E_loc)                  # sentinel E_loc = drop
    # position within expert via one-hot cumsum (stable, order-preserving)
    onehot = jax.nn.one_hot(eloc, E_loc, dtype=jnp.int32)       # [T*k, E_loc]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = jnp.sum(pos * onehot, axis=1)                    # [T*k]
    valid = mine & (pos_in_e < C)
    # scatter token rows into per-expert capacity buffers (drop overflow)
    e_idx = jnp.where(valid, eloc, E_loc)                       # out-of-range -> dropped
    pos_c = jnp.where(valid, pos_in_e, 0)
    # slot->row index map (tiny int32), then ONE [E_loc, C, D] gather — never
    # materializes the [T*k, D] expanded copy of the token embeddings.
    slot_rid = jnp.full((E_loc + 1, C), T, jnp.int32)
    slot_rid = slot_rid.at[e_idx, pos_c].set(
        jnp.where(valid, rid, T), mode="drop")[: E_loc]
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])
    buf = xf_pad[slot_rid]                                      # [E_loc, C, D]
    if n_fsdp > 1 and mode == "partial":
        # partial-contraction FSDP: contract each device's D-shard of the
        # expert weights against the matching slice of the rows, psum the
        # small [E_loc, C, F] activations, and all-gather the D-sharded
        # output — never materializes gathered weights (the decode-path
        # collective killer: activations << weights there).
        D_loc = D // n_fsdp
        f_idx = jax.lax.axis_index(fsdp_axis)
        buf_d = jax.lax.dynamic_slice_in_dim(buf, f_idx * D_loc, D_loc, axis=2)
        h = jnp.einsum("ecd,edf->ecf", buf_d, w_in)
        g = jnp.einsum("ecd,edf->ecf", buf_d, w_gate)
        hg = jax.lax.psum(jnp.stack([h, g]), fsdp_axis)
        act = jax.nn.silu(hg[1]) * hg[0]
        y_shard = jnp.einsum("ecf,efd->ecd", act.astype(buf.dtype), w_out)
        y = jax.lax.all_gather(y_shard, fsdp_axis, axis=2, tiled=True)
    else:
        y = _expert_ffn(buf, w_in, w_gate, w_out)               # [E_loc, C, D]
    # combine back: weight slots in place, scatter-add [E_loc*C, D] (not
    # [T*k, D]) — invalid slots carry rid=T and land on the dropped pad row.
    w_slot = jnp.zeros((E_loc + 1, C), jnp.float32)
    w_slot = w_slot.at[e_idx, pos_c].set(wv * valid, mode="drop")[: E_loc]
    y_w = y.astype(jnp.float32) * w_slot[..., None]
    out = jnp.zeros((T + 1, D), jnp.float32)
    out = out.at[slot_rid.reshape(-1)].add(y_w.reshape(-1, D), mode="drop")[:T]
    if "shared" in p_local:
        # shared expert: F dim TP-sharded over `model`; D dim FSDP-gathered.
        ps = p_local["shared"]
        if fsdp_axis is not None and jax.lax.axis_size(fsdp_axis) > 1:
            ps = {"w_in": jax.lax.all_gather(ps["w_in"], fsdp_axis, axis=0, tiled=True),
                  "w_gate": jax.lax.all_gather(ps["w_gate"], fsdp_axis, axis=0, tiled=True),
                  "w_out": jax.lax.all_gather(ps["w_out"], fsdp_axis, axis=1, tiled=True)}
        out = out + L.ffn_block(ps, cfg, x_loc).reshape(T, D).astype(jnp.float32)
    out = jax.lax.psum(out.astype(jnp.dtype(cfg.moe_combine_dtype)), model_axis)
    aux = jax.lax.pmean(aux, axis_name=tuple(dp_axes) + (model_axis,))
    return out.reshape(b, s, D).astype(x_loc.dtype), aux


def moe_block(p, cfg, x, dist=None) -> tuple:
    """Dispatch to dense (no mesh) or EP (distributed) path.  Returns (y, aux)."""
    if dist is None or not dist.use_ep:
        return moe_dense(p, cfg, x)
    from jax.sharding import PartitionSpec as P
    dp, mdl, fsdp = dist.dp_axes, dist.model_axis, dist.fsdp_axis
    spec_x = P(dp, None, None)
    in_specs = (
        {
            "router": P(None, None),
            "router_bias": P(None),
            "w_in": P(mdl, fsdp, None),
            "w_gate": P(mdl, fsdp, None),
            "w_out": P(mdl, None, fsdp),
            **({"shared": {"w_in": P(fsdp, mdl), "w_gate": P(fsdp, mdl),
                           "w_out": P(mdl, fsdp)}} if "shared" in p else {}),
        },
        spec_x,
    )
    dp_tuple = dp if isinstance(dp, tuple) else (dp,)
    fn = functools.partial(moe_ep_local, cfg=cfg, model_axis=mdl, fsdp_axis=fsdp,
                           dp_axes=dp_tuple)
    y, aux = jax.shard_map(
        lambda pp, xx: fn(pp, x_loc=xx),
        mesh=dist.mesh,
        in_specs=in_specs,
        out_specs=(spec_x, P()),
        check_vma=False,
    )(p, x)
    return y, aux
