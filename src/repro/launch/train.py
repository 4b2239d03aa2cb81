"""Training launcher: mesh + sharded step + checkpoint/restore loop.

The mesh is built from the devices present (launch/mesh.device_mesh:
(n, 1) over ("data", "model"), a 1x1 mesh on one chip); --smoke only
swaps in the reduced() widths the CPU tests use.  The fixed 16x16 pod
meshes belong to the compile-only dry-run (launch/dryrun.py).

Fault tolerance: resume-from-latest is automatic; on a device failure
the runbook in repro/dist/fault.py applies (re-mesh over survivors via
mesh.make_mesh_for + plan_remesh, re-lower, restore, continue).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch bnn-lm-100m --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import reduced
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import sharding as S
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import device_mesh
from repro.layers import common as C
from repro.models import transformer as M
from repro.optim import optimizer as opt_mod


def train(arch: str, *, smoke: bool = False,
          steps: int = 50, global_batch: int = 8, seq_len: int = 128,
          microbatches: int = 1, ckpt_dir: str | None = None,
          ckpt_every: int = 20, lr: float = 3e-4, log_every: int = 10,
          precision: str | None = None, seed: int = 0,
          schedule_total: int | None = None):
    cfg = configs.get_config(arch)
    if smoke:
        cfg = reduced(cfg)
    if precision:
        cfg = cfg.replace(precision=precision)
    mesh = device_mesh()

    rules = S.rules_train(False, fsdp=not smoke)
    C.set_sharding_context(mesh, rules)
    try:
        params, specs = M.init(jax.random.PRNGKey(seed), cfg)
        total = schedule_total or steps
        opt_cfg = opt_mod.AdamWConfig(lr_peak=lr,
                                      warmup_steps=max(total // 10, 1),
                                      total_steps=total)
        opt_state = opt_mod.init(opt_cfg, params)

        pshard = S.param_shardings(mesh, jax.eval_shape(lambda: params), specs,
                                   rules)
        params = jax.device_put(params, pshard)

        data = SyntheticLM(DataConfig(cfg.vocab, seq_len, global_batch,
                                      seed=seed))
        step_fn = steps_mod.build_train_step(
            cfg, opt_cfg, microbatches=microbatches,
            loss_chunk=min(512, seq_len))
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
        start = 0
        if mgr and mgr.latest_step() is not None:
            (params, opt_state), start = mgr.restore((params, opt_state))
            params = jax.device_put(params, pshard)
            print(f"[train] resumed from step {start}")

        losses = []
        t0 = time.time()
        for step in range(start, steps):
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            params, opt_state, metrics = jstep(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                print(f"[train] step={step:5d} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"({dt:.1f}s)", flush=True)
            if mgr and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, (params, opt_state))
        if mgr:
            mgr.save(steps, (params, opt_state))
            mgr.wait()
        return losses
    finally:
        C.clear_sharding_context()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bnn-lm-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced() widths (CPU tests)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, smoke=args.smoke,
          steps=args.steps, global_batch=args.global_batch,
          seq_len=args.seq_len, microbatches=args.microbatches,
          ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, lr=args.lr,
          precision=args.precision)


if __name__ == "__main__":
    main()
