"""Evolution drivers — the paper's experiments at every scale.

Two modes:

* ``ea``  — the NodIO experiment proper: N islands x pool, trap or F15,
  single host or shard_map-sharded over all local devices.
* ``pbt`` — pods-as-islands pool-based training of an assigned LM arch
  (core/pbt.py): each member trains with chromosome hyperparameters and
  migrates through the PoolServer every epoch.

CPU examples:
  PYTHONPATH=src python -m repro.launch.evolve ea --problem trap --islands 8
  PYTHONPATH=src python -m repro.launch.evolve pbt --arch minicpm-2b \
      --members 4 --epochs 5
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import ARCHS, get_config
from repro.core import (AcceptanceConfig, AsyncConfig, AsyncHostBridge,
                        EAConfig, HostBridge, MigrationConfig, PoolServer,
                        available_acceptance_policies, available_topologies,
                        make_problem, run_experiment, run_experiment_async,
                        run_fused, run_fused_async)
from repro.core import pbt as pbt_lib
from repro.core.sharded import (run_fused_sharded, run_fused_sharded_async,
                                run_sharded)
from repro.kernels import ga as ga_kernels
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import TrainState, init_train_state
from repro.models import build_model
from repro.optim import adamw_update


def run_ea(problem_name: str = "trap", islands: int = 8, epochs: int = 50,
           w2: bool = False, sharded: bool = False, seed: int = 0,
           verbose: bool = True, topology: str = "pool", fused: bool = False,
           bridge: bool = False, runtime: str = "sync",
           acfg: AsyncConfig = None, acceptance: str = "always",
           acceptance_epsilon: float = 0.0, impl: str = "jnp",
           max_pop: int = None, min_pop: int = None,
           gens_per_epoch: int = None, snapshot_every: int = None,
           snapshot_dir: str = None, resume: bool = False,
           **problem_kwargs):
    """Run the NodIO experiment. ``topology`` selects the registered
    migration strategy, ``fused`` the lax.scan driver (single compile, max
    device throughput), ``bridge`` attaches a host PoolServer through a
    HostBridge (host-loop drivers only). ``runtime='async'`` switches to
    the asynchronous per-island-clock runtime (core.async_migration):
    ``acfg`` carries the volunteer-speed / staleness / churn model, and
    ``bridge`` becomes the non-blocking AsyncHostBridge. ``acceptance``
    selects the registered immigrant-acceptance policy (core.acceptance)
    applied by every pool insert and migration delivery —
    ``acceptance_epsilon`` is the 'dedup' rejection radius; the bridged
    PoolServer mirrors the same policy so host and device pools agree.
    ``impl`` selects the generation-operator engine (repro.kernels.ga):
    'jnp' is the classic path, 'pallas' the fused megakernel.

    Durability (fused drivers only): ``snapshot_every``/``snapshot_dir``
    snapshot the full ExperimentState between scan segments; ``resume=True``
    restores the latest snapshot and continues bit-for-bit — kill -9 the
    process mid-run, rerun with ``resume``, and the final state equals the
    uninterrupted seeded run (scripts/kill_resume_smoke.py exercises this).
    A resume with a different ``islands`` count triggers elastic resize."""
    problem = make_problem(problem_name, **problem_kwargs)
    ea_kw = {"impl": impl}
    if max_pop is not None:
        ea_kw["max_pop"] = max_pop
    if min_pop is not None:
        ea_kw["min_pop"] = min_pop
    if gens_per_epoch is not None:
        ea_kw["generations_per_epoch"] = gens_per_epoch
    cfg = EAConfig(**ea_kw)
    acc = AcceptanceConfig(policy=acceptance, epsilon=acceptance_epsilon)
    mig = MigrationConfig(topology=topology, acceptance=acc)
    is_async = runtime == "async"
    if acfg is None:
        acfg = AsyncConfig()
    if bridge and (fused or (sharded and is_async)):
        print("note: --bridge needs a host loop; the fused lax.scan driver "
              "(incl. the sharded async driver) runs entirely on device — "
              "bridge disabled")
        bridge = False
    snap_kw = {"snapshot_every": snapshot_every, "snapshot_dir": snapshot_dir,
               "resume": resume}
    if snapshot_dir is not None and not (fused or (sharded and is_async)):
        print("note: --snapshot-dir snapshots the fused lax.scan drivers; "
              "host-loop drivers are not segmented — snapshotting disabled")
        snap_kw = {}
    server = PoolServer(capacity=256, seed=seed,
                        acceptance=acc if acceptance != "always" else None
                        ) if bridge else None
    host_bridge = None
    if bridge:
        host_bridge = (AsyncHostBridge(server, acceptance=acc) if is_async
                       else HostBridge(server, acceptance=acc))
    t0 = time.perf_counter()
    if sharded:
        mesh = make_host_mesh()
        n_shards = mesh.shape["islands"]
        per = max(1, islands // n_shards)
        if is_async:
            # async sharded is fused-only (one shard_map(lax.scan) driver)
            isl, pool, ep = run_fused_sharded_async(
                mesh, problem, cfg, mig, acfg, islands_per_shard=per,
                max_ticks=epochs, w2=w2, rng=jax.random.key(seed),
                **snap_kw)
        elif fused:
            isl, pool, ep = run_fused_sharded(
                mesh, problem, cfg, mig, islands_per_shard=per,
                max_epochs=epochs, w2=w2, rng=jax.random.key(seed),
                **snap_kw)
        else:
            isl, pool, ep = run_sharded(mesh, problem, cfg, mig,
                                        islands_per_shard=per,
                                        max_epochs=epochs, w2=w2,
                                        rng=jax.random.key(seed),
                                        host_bridge=host_bridge)
        best = float(jax.device_get(isl.best_fitness.max()))
        if verbose:
            print(f"[sharded x{n_shards} {'fused ' if fused else ''}"
                  f"{'async ' if is_async else ''}topo={topology}] "
                  f"best={best} epochs={int(ep)} ({time.perf_counter()-t0:.1f}s)")
            print(f"final best={best!r} epochs={int(ep)}")
        return isl, pool
    if fused:
        run = (partial(run_fused_async, acfg=acfg, max_ticks=epochs)
               if is_async else partial(run_fused, max_epochs=epochs))
        isl, pool, ep = run(problem, cfg, mig, n_islands=islands, w2=w2,
                            rng=jax.random.key(seed), **snap_kw)
        if verbose:
            best = float(jax.device_get(isl.best_fitness.max()))
            print(f"[fused {'async ' if is_async else ''}topo={topology}] "
                  f"best={best} epochs={int(ep)} ({time.perf_counter()-t0:.1f}s)")
            print(f"final best={best!r} epochs={int(ep)}")
        return isl, pool
    if is_async:
        res = run_experiment_async(problem, cfg, mig, acfg,
                                   n_islands=islands, max_ticks=epochs,
                                   w2=w2, rng=jax.random.key(seed),
                                   verbose=verbose, host_bridge=host_bridge)
        if host_bridge is not None:
            res.pool = host_bridge.flush(res.pool)
            host_bridge.close()
    else:
        res = run_experiment(problem, cfg, mig, n_islands=islands,
                             max_epochs=epochs, w2=w2,
                             rng=jax.random.key(seed), verbose=verbose,
                             host_bridge=host_bridge)
    if verbose:
        extra = f" fires={res.total_fires}" if is_async else ""
        print(f"success={res.success} evals_to_solution="
              f"{res.evaluations_to_solution} wall={res.wall_time_s:.1f}s"
              + (f" bridge={host_bridge.stats()}" if host_bridge else "")
              + extra)
    return res


def run_pbt(arch: str = "minicpm-2b", members: int = 4, epochs: int = 5,
            steps_per_epoch: int = 20, batch: int = 8, seq: int = 64,
            seed: int = 0, verbose: bool = True):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       global_batch=batch, seed=seed)

    @jax.jit
    def step_fn(state, batch_, lr, wd):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(state.params, batch_)
        params, opt, om = adamw_update(grads, state.opt, state.params,
                                       lr=lr, weight_decay=wd)
        return TrainState(params, opt), {**metrics, **om}

    @jax.jit
    def eval_fn(state, batch_):
        return model.loss(state.params, batch_)[0]

    ctrl = pbt_lib.PBTController(
        step_fn=step_fn, eval_fn=eval_fn,
        init_state_fn=lambda uid: init_train_state(
            model, jax.random.key(seed + uid)),
        pool=PoolServer(capacity=64, seed=seed), seed=seed)

    def batches(uid, epoch):
        # each member trains on its own slice of the step space (islands
        # see different data — the volunteer heterogeneity); offsetting by
        # uid avoids any divisibility constraint between batch and members
        return (data.batch_for_step(
            uid * 1_000_000 + epoch * steps_per_epoch + s, 0, 1)
                for s in range(steps_per_epoch))

    def eval_batch(uid, epoch):
        return data.batch_for_step(10_000 + epoch, 0, 1)

    hist = ctrl.run(members, epochs, batches, eval_batch, verbose=verbose)
    best = ctrl.best_member()
    if verbose:
        print(f"best member {best.uuid}: val={-best.fitness:.4f} "
              f"lr={best.hypers['lr']:.2e} exploits={best.exploits}")
    return ctrl


def main(argv=None):
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    ea = sub.add_parser("ea")
    ea.add_argument("--problem", default="trap")
    ea.add_argument("--islands", type=int, default=8)
    ea.add_argument("--epochs", type=int, default=50)
    ea.add_argument("--seed", type=int, default=0)
    ea.add_argument("--w2", action="store_true")
    ea.add_argument("--sharded", action="store_true")
    ea.add_argument("--max-pop", type=int, default=None,
                    help="static lane count (padded population)")
    ea.add_argument("--min-pop", type=int, default=None,
                    help="W² lower population bound")
    ea.add_argument("--gens-per-epoch", type=int, default=None,
                    help="generations between migrations (paper's n)")
    ea.add_argument("--snapshot-every", type=int, default=None,
                    help="snapshot the full ExperimentState every N epochs "
                         "(fused drivers; enables kill -9 + --resume)")
    ea.add_argument("--snapshot-dir", default=None,
                    help="checkpoint directory for --snapshot-every/--resume")
    ea.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot from --snapshot-dir "
                         "and continue bit-for-bit (elastic: a different "
                         "--islands count resizes the restored state)")
    ea.add_argument("--topology", default="pool",
                    choices=available_topologies(),
                    help="registered migration topology (core.migration)")
    ea.add_argument("--fused", action="store_true",
                    help="lax.scan fused driver (single compile per topology)")
    ea.add_argument("--bridge", action="store_true",
                    help="sync the device pool with a host PoolServer")
    ea.add_argument("--runtime", choices=("sync", "async"), default="sync",
                    help="async = per-island clocks, no epoch barrier "
                         "(core.async_migration)")
    ea.add_argument("--min-rate", type=float, default=0.25,
                    help="slowest volunteer speed (async runtime)")
    ea.add_argument("--max-rate", type=float, default=1.0,
                    help="fastest volunteer speed (async runtime)")
    ea.add_argument("--staleness", type=int, default=3,
                    help="inbox immigrant lifetime in ticks (async runtime)")
    ea.add_argument("--churn", type=float, default=0.0,
                    help="fraction of islands with a seeded down-window "
                         "(async runtime)")
    ea.add_argument("--acceptance", default="always",
                    choices=available_acceptance_policies(),
                    help="registered immigrant-acceptance policy "
                         "(core.acceptance): always = the paper's "
                         "accept-every-PUT ring; elitist = replace worst "
                         "if better; crowding = replace nearest by genome "
                         "distance; dedup = reject epsilon-duplicates "
                         "then elitist")
    ea.add_argument("--acceptance-epsilon", type=float, default=0.0,
                    help="dedup rejection radius (genome distance; 0 = "
                         "exact duplicates only)")
    ea.add_argument("--impl", default="jnp",
                    choices=ga_kernels.available_impls("generation"),
                    help="generation-operator engine (repro.kernels.ga "
                         "registry): jnp = classic four-op jax.random "
                         "path; pallas = fused selection->crossover->"
                         "mutation[->fitness] VMEM megakernel with "
                         "on-chip counter RNG (interpret mode on the CPU); "
                         "pallas_ref = the megakernel's pure-jnp oracle. "
                         "Benchmark the impls against each other with "
                         "`python -m benchmarks.speed_baseline`, which "
                         "writes BENCH_speed.json (evals_per_sec rows "
                         "per problem x genome length x impl; the host "
                         "block records jax/backend/device so numbers "
                         "are comparable across machines)")
    pbt = sub.add_parser("pbt")
    pbt.add_argument("--arch", choices=ARCHS, default="minicpm-2b")
    pbt.add_argument("--members", type=int, default=4)
    pbt.add_argument("--epochs", type=int, default=5)
    pbt.add_argument("--steps-per-epoch", type=int, default=20)
    args = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    devs = jax.devices()
    print(f"jax devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)} "
          f"compile_cache={cache_dir}", flush=True)
    if args.mode == "ea":
        acfg = AsyncConfig(min_rate=args.min_rate, max_rate=args.max_rate,
                           staleness=args.staleness,
                           churn_fraction=args.churn)
        run_ea(args.problem, args.islands, args.epochs, args.w2,
               args.sharded, seed=args.seed, topology=args.topology,
               fused=args.fused, bridge=args.bridge, runtime=args.runtime,
               acfg=acfg, acceptance=args.acceptance,
               acceptance_epsilon=args.acceptance_epsilon, impl=args.impl,
               max_pop=args.max_pop, min_pop=args.min_pop,
               gens_per_epoch=args.gens_per_epoch,
               snapshot_every=args.snapshot_every,
               snapshot_dir=args.snapshot_dir, resume=args.resume)
    else:
        run_pbt(args.arch, args.members, args.epochs, args.steps_per_epoch)


if __name__ == "__main__":
    main()
