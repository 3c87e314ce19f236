"""End-to-end training driver with fault tolerance.

Features exercised here (and in tests/test_ft.py):
  * deterministic restart-safe data (batch = f(seed, step)),
  * atomic checkpoints every --ckpt-every steps with auto-resume,
  * failure injection (--fail-at-step kills the process mid-run; rerunning
    the same command resumes from the last commit),
  * elastic restore: resuming on a different --data/--model mesh re-shards
    the checkpoint (the npz is mesh-agnostic),
  * straggler watchdog fed with per-step times,
  * optional int8 gradient compression across the 'pod' axis.

Example (CPU, reduced config):
  python -m repro.launch.train --arch yi_6b --reduced --steps 50 \
      --batch 8 --seq 128 --ckpt-dir /tmp/ck --ckpt-every 20
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint as ckpt
from repro.launch.mesh import make_mesh
from repro.configs.base import ShapeConfig, get_config, get_reduced
from repro.data.pipeline import make_batch
from repro.distributed import sharding as shard
from repro.ft.watchdog import Watchdog
from repro.models import transformer as T
from repro.models.layers import activation_sharding
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="failure injection: exit(17) before this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    import dataclasses
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype=args.dtype)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    mesh = make_mesh((args.data, args.model), ("data", "model"))
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.dtype == "bfloat16":
        params = T.cast_params(params, jnp.bfloat16)
    opt_state = opt.init_adamw(params)
    p_shard = shard.param_shardings(params, mesh)
    o_shard = opt.AdamWState(step=NamedSharding(mesh, P()), m=p_shard,
                             v=jax.tree.map(lambda s: s, p_shard))
    params = jax.tree.map(jax.device_put, params, p_shard)
    opt_state = jax.tree.map(jax.device_put, opt_state, o_shard)

    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start_step = ckpt.restore(
            args.ckpt_dir, (params, opt_state),
            shardings=(p_shard, o_shard))
        print(f"[train] resumed from step {start_step}", flush=True)

    step_fn = make_train_step(
        cfg, opt.AdamWConfig(lr=args.lr), microbatch=args.microbatch)
    batch_sharding = {k: NamedSharding(mesh, shard.batch_spec(mesh, v.ndim))
                      for k, v in make_batch(cfg, shape, 0, args.seed).items()}
    with activation_sharding(mesh, ("data",)):
        jstep = jax.jit(step_fn, in_shardings=(p_shard, o_shard, batch_sharding),
                        out_shardings=(p_shard, o_shard, None),
                        donate_argnums=(0, 1))

    wd = Watchdog(hosts=jax.process_count())
    losses = []
    for step in range(start_step, args.steps):
        if step == args.fail_at_step:
            print(f"[train] INJECTED FAILURE at step {step}", flush=True)
            os._exit(17)
        batch = {k: jax.device_put(v, batch_sharding[k])
                 for k, v in make_batch(cfg, shape, step, args.seed).items()}
        t0 = time.monotonic()
        params, opt_state, metrics = jstep(params, opt_state, batch)
        loss = float(metrics["loss"])
        wd.beat(jax.process_index(), time.monotonic() - t0)
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"t={time.monotonic()-t0:.2f}s "
                  f"watchdog={wd.decide()}", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, (params, opt_state))
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, (params, opt_state))
    print(f"[train] done. first loss={losses[0]:.4f} last={losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
