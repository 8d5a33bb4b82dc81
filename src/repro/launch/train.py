"""Training driver: fault-tolerant loop with auto-resume, async
checkpointing, straggler monitoring, deterministic data, and optional
post-training MoR calibration.

CPU-runnable end-to-end on reduced configs:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --reduced --steps 200 --batch 16 --seq 64 --ckpt-dir /tmp/ckpt
On a real cluster the same driver runs the full config against
``make_production_mesh()`` (--mesh pod).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config, reduce_config
from repro.configs.base import ShapeSpec
from repro.data import DataConfig
from repro.data.pipeline import make_batch
from repro.distributed.fault_tolerance import StragglerMonitor
from repro.distributed.sharding_rules import (activation_context,
                                              batch_sharding, param_sharding)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import init_train_state, make_train_step
from repro.models import get_model
from repro.optim import OptConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="host", choices=("host", "pod"))
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--calibrate", action="store_true",
                    help="run MoR calibration after training")
    ap.add_argument("--out-json", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    cfg = cfg.replace(grad_accum=1)
    api = get_model(cfg)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    opt_cfg = OptConfig(lr=args.lr, moment_dtype="float32"
                        if cfg.dtype == "float32" else "bfloat16")
    mesh = (make_production_mesh() if args.mesh == "pod"
            else make_host_mesh(args.model_parallel))
    dcfg = DataConfig(seed=args.seed)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    key = jax.random.PRNGKey(args.seed)
    params, opt_state = init_train_state(key, cfg, opt_cfg)
    start_step = 0
    if mgr and mgr.latest_step() is not None:
        # elastic restore: the checkpoint re-places onto whatever mesh we
        # have now (device counts may differ from the saving job)
        shardings = {"params": param_sharding(params, mesh),
                     "opt": None}
        state, extra = mgr.restore({"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = extra["step"]
        print(f"[train] resumed from step {start_step}")

    train_step = jax.jit(make_train_step(cfg, opt_cfg,
                                         total_steps=args.steps),
                         donate_argnums=(0, 1))
    monitor = StragglerMonitor(n_hosts=1)
    losses = []
    t_start = time.time()
    with activation_context(mesh, sequence_parallel=False):
        for step in range(start_step, args.steps):
            batch_np = make_batch(cfg, shape, dcfg, step)
            batch = jax.tree_util.tree_map(jnp.asarray, batch_np)
            t0 = time.time()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            dt = time.time() - t0
            monitor.record_step({0: dt})
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            if mgr and (step + 1) % args.save_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state},
                 block=True)
        mgr.wait()

    report = {
        "arch": cfg.name, "steps": args.steps,
        "loss_first": losses[0] if losses else None,
        "loss_last": float(np.mean(losses[-10:])) if losses else None,
        "wall_s": round(time.time() - t_start, 1),
    }

    if args.calibrate:
        from repro.core.deploy import calibrate_lm
        def batches():
            s = 10_000
            while True:
                b = make_batch(cfg, shape, dcfg, s)
                yield jax.tree_util.tree_map(jnp.asarray, b)
                s += 1
        params2, mor, cal = calibrate_lm(params, cfg, api.forward,
                                         batches(), cfg.mor.calib_batches)
        report["calibration"] = cal
        if mgr:
            mgr.save(args.steps + 1,
                     {"params": params2, "opt": opt_state}, block=True)
        print("[train] calibration:", cal)

    print("[train] done:", report)
    if args.out_json:
        json.dump(report, open(args.out_json, "w"), indent=1)
    return report


if __name__ == "__main__":
    main()
