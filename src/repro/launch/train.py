"""End-to-end training driver.

Runs a real training loop on the available devices (CPU here; the same code
path drives a TPU pod slice): config -> mesh -> sharded init -> jitted
train_step -> checkpointed, fault-tolerant loop with straggler monitoring.

Examples
    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
        --steps 50 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_model_config
from repro.config.base import ParallelConfig, TrainConfig
from repro.launch.mesh import make_mesh_for
from repro.models import build_model
from repro.parallel.sharding import ShardingRules, named
from repro.train.checkpoint import CheckpointManager
from repro.train.data import SyntheticDataset
from repro.train.elastic import FailureRecovery, StragglerMonitor
from repro.train.optimizer import adam_update, clip_by_global_norm, init_adam
from repro.train.train_step import batch_specs


def build(arch: str, smoke: bool, par: ParallelConfig, train_cfg: TrainConfig):
    model_cfg = get_model_config(arch, smoke=smoke)
    model = build_model(model_cfg, remat=par.remat)
    mesh = make_mesh_for(par, devices=np.array(jax.devices()[:par.num_devices]))
    rules = ShardingRules(model_cfg, par)

    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, train_cfg.grad_clip)
        params, opt_state, om = adam_update(params, grads, opt_state, train_cfg)
        out = {"loss": metrics["loss"], "ce": metrics["ce"],
               "grad_norm": gnorm, **om}
        return params, opt_state, out

    return model, model_cfg, mesh, rules, step_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    par = ParallelConfig(multi_pod=False, data=args.data, model=args.model)
    train_cfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                            lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 10, 1),
                            ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every)
    model, model_cfg, mesh, rules, step_fn = build(
        args.arch, args.smoke, par, train_cfg)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(train_cfg.seed))
        opt = init_adam(params, par.opt_state_dtype)
        pspecs = rules.params_tree_specs(params)
        from repro.train.optimizer import AdamState
        from jax.sharding import PartitionSpec as P
        opt_specs = AdamState(step=P(), m=pspecs, v=pspecs)
        params = jax.device_put(params, named(mesh, pspecs))
        opt = jax.device_put(opt, named(mesh, opt_specs))
        bspec = named(mesh, batch_specs(model_cfg, rules))
        data = SyntheticDataset(model_cfg, train_cfg, sharding=bspec)
        ckpt = CheckpointManager(train_cfg.ckpt_dir, keep=train_cfg.ckpt_keep)
        monitor = StragglerMonitor()

        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        state = {"params": params, "opt": opt}

        def run(start: int) -> int:
            step = start
            while step < train_cfg.total_steps:
                t0 = time.time()
                batch = data.batch_at(step)
                state["params"], state["opt"], metrics = jstep(
                    state["params"], state["opt"], batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.time() - t0
                verdict = monitor.observe(dt)
                step += 1
                if step % args.log_every == 0 or step == 1:
                    print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                          f"ce {float(metrics['ce']):.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms"
                          f"{' [' + verdict + ']' if verdict != 'ok' else ''}",
                          flush=True)
                if step % train_cfg.ckpt_every == 0:
                    ckpt.save(step, state)
            return step

        recovery = FailureRecovery(ckpt, max_restarts=train_cfg.max_restarts)
        start = 0
        latest = ckpt.latest_step()
        if latest is not None:
            start, state = ckpt.restore(latest, state)
            print(f"resumed from checkpoint step {start}")
        final = recovery.run(run, start, train_cfg.total_steps)
        ckpt.save(final, state)
        ckpt.wait()
        print(f"done at step {final}")
        return final


if __name__ == "__main__":
    main()
