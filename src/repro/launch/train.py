"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 50 --batch 8 --seq 128

Production features wired here: deterministic step-indexed data (resume =
set step), periodic async checkpoints with atomic rename, emergency
checkpoint on watchdog timeout, straggler statistics, elastic restore (a
checkpoint taken on one mesh restores onto another via
`checkpoint.restore_checkpoint(shardings=...)`).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import ARCHS, get_config
from repro.data.pipeline import DataConfig, make_batch
from repro.ft import StepTimer, Watchdog
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.steps import make_train_step, param_shardings, opt_shardings, batch_shardings
from repro.nn.model import init_params
from repro.optim.adamw import adamw_init
from repro.parallel.sharding import use_mesh


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCHS, default="minicpm-2b")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-sized)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--mesh", choices=("none", "debug", "pod", "multipod"),
                   default="none")
    p.add_argument("--watchdog-s", type=float, default=600.0)
    args = p.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    data = DataConfig(seq_len=args.seq, global_batch=args.batch)

    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh(1, 1)
    elif args.mesh == "pod":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "multipod":
        mesh = make_production_mesh(multi_pod=True)

    with use_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 2 else a,
            params)
        opt_state = adamw_init(params)
        start = 0
        if args.ckpt_dir:
            last = latest_step(args.ckpt_dir)
            if last is not None:
                shardings = None
                if mesh is not None:
                    psh = param_shardings(cfg, mesh)
                    shardings = {"params": psh,
                                 "opt": opt_shardings(psh, mesh)}
                state = restore_checkpoint(
                    args.ckpt_dir, last, {"params": params, "opt": opt_state},
                    shardings)
                params, opt_state = state["params"], state["opt"]
                start = last
                print(f"resumed from step {last}")

        step_fn = jax.jit(make_train_step(cfg, peak_lr=args.lr),
                          donate_argnums=(0, 1))

        timer = StepTimer()

        def emergency(step: int) -> None:
            if args.ckpt_dir:
                print(f"WATCHDOG: step {step} hung; emergency checkpoint")
                save_checkpoint(args.ckpt_dir, step,
                                {"params": params, "opt": opt_state})

        wd = Watchdog(args.watchdog_s, on_timeout=emergency)

        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     make_batch(cfg, data, step).items()}
            timer.start()
            with wd.armed(step):
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch, jnp.int32(step))
                metrics = jax.device_get(metrics)
            dt = timer.stop()
            rep = timer.report(step)
            flag = " STRAGGLER" if rep.flagged else ""
            print(f"step {step:5d} loss={metrics['loss']:.4f} "
                  f"ce={metrics['ce']:.4f} gnorm={metrics['grad_norm']:.3f} "
                  f"lr={metrics['lr']:.2e} {dt*1e3:8.1f} ms{flag}",
                  flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1,
                                {"params": params, "opt": opt_state},
                                block=False)
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps,
                            {"params": params, "opt": opt_state})
        print("done")


if __name__ == "__main__":
    main()
