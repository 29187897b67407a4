"""Training driver: synthetic data -> sharded train loop -> checkpoints.

Runs the same code path at every scale: smoke configs on 1 CPU device,
full configs on the production mesh (the mesh adapts to whatever devices
exist). Fault tolerance: --resume picks up the latest checkpoint (params,
optimizer, data-pipeline step) and continues bit-exactly.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import Checkpointer, latest_step, restore
from repro.configs import ARCHS, get_config
from repro.data import ShardedLoader, SyntheticLM
from repro.launch import shardings as sh
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.launch.steps import TrainState, init_train_state, make_train_step
from repro.models import build_model
from repro.optim import adamw_init, make_schedule


def make_mesh_for_devices():
    """Best mesh for whatever devices exist (1 CPU -> (1,1))."""
    n = len(jax.devices())
    model_par = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0 and cand <= n:
            model_par = cand
            break
    from repro.compat import make_mesh
    return make_mesh((n // model_par, model_par), ("data", "model"))


def train(arch: str, smoke: bool = True, steps: int = 100, batch: int = 8,
          seq: int = 64, lr: float = 3e-3, accum: int = 1,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          resume: bool = False, seed: int = 0, log_every: int = 10,
          verbose: bool = True):
    cfg = get_config(arch, smoke=smoke)
    model = build_model(cfg)
    mesh = make_mesh_for_devices()
    schedule = make_schedule(cfg.schedule, lr, steps, warmup_steps=min(
        20, steps // 5 + 1))
    step_fn = make_train_step(model, schedule=schedule, accum_steps=accum)

    # shardings
    p_shapes = model.abstract_params()
    p_pspecs = sh.tree_pspecs(model.param_axes(), p_shapes, cfg, mesh, "train")
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_pspecs)
    opt_pspecs = sh.opt_state_pspecs(p_pspecs, p_shapes, mesh)
    state_shard = TrainState(
        params=p_shard,
        opt=jax.tree.map(lambda s: NamedSharding(mesh, s), opt_pspecs))
    jstep = jax.jit(step_fn, in_shardings=(state_shard, None),
                    out_shardings=(state_shard, None), donate_argnums=(0,))

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       global_batch=batch, seed=seed)
    loader = ShardedLoader(data)
    ckpt = Checkpointer(ckpt_dir, keep=3) if ckpt_dir else None

    start = 0
    if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
        target = TrainState(params=model.abstract_params(),
                            opt=jax.eval_shape(adamw_init,
                                               model.abstract_params()))
        blob = restore(ckpt_dir, target={"state": target, "data_step": 0})
        state = jax.device_put(blob["state"], state_shard)
        start = int(blob["data_step"])
        loader.load_state_dict({"step": start})
        if verbose:
            print(f"resumed from step {start}")
    else:
        with jax.set_mesh(mesh):
            state = init_train_state(model, jax.random.key(seed))
            state = jax.device_put(state, state_shard)

    losses = []
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        for i in range(start, steps):
            batch_i = loader.next()
            state, metrics = jstep(state, batch_i)
            losses.append(float(metrics["ce"]))
            if verbose and (i % log_every == 0 or i == steps - 1):
                dt = time.perf_counter() - t0
                print(f"step {i:5d} ce={losses[-1]:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} [{dt:.1f}s]")
            if ckpt and ((i + 1) % ckpt_every == 0 or i == steps - 1):
                ckpt.save_async(i + 1, {"state": state, "data_step": i + 1})
    if ckpt:
        ckpt.wait()
    return state, losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="minicpm-2b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _, losses = train(args.arch, args.smoke, args.steps, args.batch,
                      args.seq, args.lr, args.accum, args.ckpt_dir,
                      args.ckpt_every, args.resume, args.seed)
    print(f"final ce: {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
