"""Training driver: mesh + sharded train loop + checkpoint/restart + fault
hooks.  Runs real (small) jobs on CPU and is the same code path the dry-run
lowers for the production meshes.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --reduced --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

NaN containment follows the paper's Fig-1 guard: a non-finite loss triggers
rollback to the last checkpoint with the LR (the "conductance") halved —
the same bisection-on-overflow logic, applied to training.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, reduced as make_reduced
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import sharding as SH
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.optim import adamw, schedule
from repro.runtime.fault_tolerance import FailureDetector, HeartbeatMonitor
from repro.runtime.straggler import StragglerPolicy


def make_train_step(cfg, ocfg):
    def train_step(params, opt_state, batch):
        def lf(p):
            return T.loss_fn(p, cfg, batch)
        (loss, metrics), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        new_params, new_opt, om = adamw.update(ocfg, grads, opt_state,
                                               params)
        return new_params, new_opt, {"loss": loss, **metrics, **om}
    return train_step


def run(arch: str, steps: int = 50, batch: int = 8, seq: int = 256,
        use_reduced: bool = True, ckpt_dir: Optional[str] = None,
        ckpt_every: int = 25, lr: float = 3e-3, seed: int = 0,
        model_parallel: int = 1, log_every: int = 10,
        lr_floor_scale: float = 0.125):
    cfg = get_config(arch)
    if use_reduced:
        cfg = make_reduced(cfg)
    mesh = make_local_mesh(model_parallel)

    sched = schedule.warmup_cosine(lr, warmup=min(20, steps // 5 + 1),
                                   total=steps)
    ocfg = adamw.AdamWConfig(lr=sched, grad_clip=1.0)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                      seed=seed)
    pipe = TokenPipeline(dcfg)

    mgr = CheckpointManager(ckpt_dir, max_to_keep=2) if ckpt_dir else None

    with SH.activate(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(seed))
        opt_state = adamw.init(ocfg, params)
        pshard = SH.spec_tree_to_shardings(
            SH.param_specs(params, mesh), mesh)
        params = jax.device_put(params, pshard)

        step_fn = jax.jit(make_train_step(cfg, ocfg),
                          donate_argnums=(0, 1))

        # restart?
        start = 0
        if mgr and mgr.latest_step() is not None:
            start = mgr.latest_step()
            snap = mgr.restore(start, {"params": params, "opt": opt_state})
            params, opt_state = snap["params"], snap["opt"]
            pipe = TokenPipeline.restore(dcfg, {"step": start,
                                                "shard_index": 0,
                                                "num_shards": 1,
                                                "seed": seed})
            print(f"[train] restored step {start}")

        monitor = HeartbeatMonitor([f"host{jax.process_index()}"])
        detector = FailureDetector(monitor)
        straggler = StragglerPolicy()

        losses = []
        lr_scale = 1.0
        i = start
        while i < steps:
            batch_data = pipe.next_batch()
            if cfg.family == "encdec":
                b = batch_data["tokens"].shape[0]
                batch_data["audio"] = 0.1 * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), i),
                    (b, cfg.enc_seq, cfg.d_model))
            if cfg.family == "vlm":
                b = batch_data["tokens"].shape[0]
                batch_data["img"] = 0.1 * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), i),
                    (b, cfg.img_tokens, cfg.img_embed_dim))
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 batch_data)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            monitor.beat(f"host{jax.process_index()}")
            straggler.observe(f"host{jax.process_index()}", dt)

            if not np.isfinite(loss):
                # paper Fig-1 guard: overflow -> roll back, halve the scale
                if mgr is None or mgr.latest_step() is None:
                    raise FloatingPointError(
                        f"non-finite loss at step {i} and no checkpoint")
                lr_scale = max(lr_scale * 0.5, lr_floor_scale)
                back = mgr.latest_step()
                print(f"[train] NaN at step {i}; rollback to {back}, "
                      f"lr_scale={lr_scale}")
                ocfg = dataclasses.replace(
                    ocfg, lr=lambda s: sched(s) * lr_scale)
                step_fn = jax.jit(make_train_step(cfg, ocfg),
                                  donate_argnums=(0, 1))
                snap = mgr.restore(back, {"params": params,
                                          "opt": opt_state})
                params, opt_state = snap["params"], snap["opt"]
                pipe = TokenPipeline.restore(
                    dcfg, {"step": back, "shard_index": 0,
                           "num_shards": 1, "seed": seed})
                i = back
                continue

            losses.append(loss)
            i += 1
            if i % log_every == 0 or i == steps:
                print(f"[train] step {i:5d} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms/step)")
            if mgr and (i % ckpt_every == 0 or i == steps):
                mgr.save(i, {"params": params, "opt": opt_state})
        if mgr:
            mgr.wait()
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (default: reduced)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)
    losses = run(args.arch, steps=args.steps, batch=args.batch,
                 seq=args.seq, use_reduced=not args.full,
                 ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                 lr=args.lr, seed=args.seed,
                 model_parallel=args.model_parallel)
    print(f"[train] first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
