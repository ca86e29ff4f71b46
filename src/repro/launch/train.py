"""End-to-end training driver with fault tolerance.

  python -m repro.launch.train --arch stablelm-1.6b --steps 200 --reduced \
      --ckpt-dir /tmp/ckpt [--restore] [--mesh 1x1]

Wires together: config -> model -> optimizer -> data pipeline -> jit'd train
step -> async checkpointing -> straggler telemetry -> preemption handling.
On the CPU container it runs REDUCED configs for real (examples/quickstart);
on a TPU slice the same driver takes the full configs.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.checkpoint import store
from repro.configs.base import ShapeConfig, get_config
from repro.data.pipeline import DataConfig, DataIterator
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models.dist import make_dist
from repro import optim
from repro.runtime.fault_tolerance import (PreemptionHandler, StragglerDetector,
                                           recoverable_step)


def train(arch: str, steps: int = 100, reduced: bool = True,
          seq_len: int = 128, batch: int = 8, ckpt_dir: Optional[str] = None,
          restore: bool = False, ckpt_every: int = 50, mesh_shape=None,
          log_every: int = 10, lr: float = 3e-4, seed: int = 0,
          install_signals: bool = True, straggler_k: float = 5.0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("train_cli", seq_len, batch, "train")
    model = api.build_model(cfg)
    optimizer = optim.make_optimizer(cfg.optimizer, lr=lr, total_steps=steps)

    dist = None
    if mesh_shape and int(np.prod(mesh_shape)) > 1:
        mesh = make_mesh(mesh_shape, ("data", "model")[: len(mesh_shape)])
        dist = make_dist(mesh)

    params = model.init(jax.random.PRNGKey(seed), max_seq=seq_len)
    state = api.TrainState(params, optimizer.init(params))

    start_step = 0
    data_cfg = DataConfig(seed=seed + 1)
    ckpt: Optional[store.AsyncCheckpointer] = None
    if ckpt_dir:
        ckpt = store.AsyncCheckpointer(ckpt_dir)
        if restore and store.latest_step(ckpt_dir) is not None:
            start_step, state, extra = store.restore(ckpt_dir)
            print(f"[train] restored step {start_step}")

    step_fn = jax.jit(api.make_train_step(model, optimizer, dist),
                      donate_argnums=(0,))
    data = DataIterator(cfg, shape, data_cfg, start_step=start_step)
    straggler = StragglerDetector(k=straggler_k)
    preempt = PreemptionHandler(install=install_signals)

    losses = []
    try:
        for step in range(start_step, steps):
            batch_np = next(data)
            t0 = time.perf_counter()
            state, metrics = recoverable_step(step_fn, state, batch_np)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            if straggler.observe(dt):
                print(f"[train] step {step}: STRAGGLER ({dt:.3f}s vs "
                      f"median {straggler.summary()['median_s']:.3f}s)")
            losses.append(float(metrics["loss"]))
            if step % log_every == 0:
                print(f"[train] step {step} loss {losses[-1]:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt * 1e3:.0f}ms")
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save_async(step + 1, state, extra=data.state())
            if preempt.requested:
                print("[train] preemption requested: checkpointing and exiting")
                if ckpt:
                    ckpt.save_async(step + 1, state, extra=data.state())
                break
    finally:
        data.close()
        if ckpt:
            ckpt.wait()
    return losses, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", help="e.g. 2x4")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()
    mesh_shape = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else None
    losses, _ = train(args.arch, steps=args.steps, reduced=args.reduced,
                      seq_len=args.seq_len, batch=args.batch,
                      ckpt_dir=args.ckpt_dir, restore=args.restore,
                      ckpt_every=args.ckpt_every, mesh_shape=mesh_shape,
                      lr=args.lr)
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
