"""Distributed training driver: the program the dry-run lowers, executed.

On one chip the mesh defaults to (1, jax.device_count()); on the CPU,
--devices N forces N host devices so the same sharded program runs on a
small host-device mesh. Demonstrates the full production loop: sharded
params/optimizer, per-host data shards, checkpoint/restart (elastic),
straggler monitoring.

Usage:
  PYTHONPATH=src python -m repro.launch.train --devices 8 --mesh 4,2 \
      --arch granite-8b --reduced --steps 20
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.bayes.variational import KLSchedule
from repro.configs import get_config, reduced_config
from repro.data.tokens import TokenPipeline
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.training.checkpoint import CheckpointManager
from repro.training.fault_tolerance import StepMonitor
from repro.training.optimizer import Adam, cosine_schedule
from repro.training.train_loop import (TrainState, init_train_state,
                                       make_svi_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="host devices to force on the CPU backend; an "
                         "accelerator backend ignores it")
    ap.add_argument("--mesh", default=None,
                    help="data,model (default: 1,<device count>)")
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/pfp_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    if args.devices:
        # Read when the CPU backend starts, at the first device query below.
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dims = (tuple(int(x) for x in args.mesh.split(",")) if args.mesh
            else (1, jax.device_count()))
    mesh = make_mesh(dims, ("data", "model"))
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    print(f"mesh={dict(mesh.shape)} arch={cfg.name} "
          f"params~{cfg.param_count() / 1e6:.1f}M")

    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = Adam(learning_rate=cosine_schedule(1e-3, 5, args.steps),
               clip_norm=1.0)
    state = init_train_state(params, opt)

    # Shard the train state onto the mesh (same rules as the dry-run).
    p_sh = shlib.params_shardings(
        jax.eval_shape(lambda: params), mesh)
    state_sh = TrainState(
        params=p_sh,
        opt_state=type(state.opt_state)(
            step=shlib.replicated(mesh), m=p_sh, v=p_sh),
        step=shlib.replicated(mesh))
    state = jax.device_put(state, state_sh)

    def fwd(p, batch, ctx):
        logits, aux, _ = lm.forward(p, cfg, batch, ctx)
        return logits, aux

    step_fn = jax.jit(
        make_svi_train_step(fwd, opt,
                            num_data=args.batch * args.seq * args.steps,
                            kl_schedule=KLSchedule(0.25, args.steps)),
        in_shardings=(state_sh,
                      shlib.batch_shardings(
                          {"tokens": jax.ShapeDtypeStruct(
                              (args.batch, args.seq), jnp.int32),
                           "targets": jax.ShapeDtypeStruct(
                              (args.batch, args.seq), jnp.int32)}, mesh),
                      shlib.replicated(mesh)),
        # Pin the output state to the input sharding: the state feeds back
        # into the next step (donated), so XLA must not re-shard it.
        out_shardings=(state_sh, None),
        donate_argnums=(0,))

    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch)
    mgr = CheckpointManager(args.ckpt_dir)
    monitor = StepMonitor()
    start = 0
    if args.resume and mgr.latest_step() is not None:
        state, start = mgr.restore(state, shardings=state_sh)
        print(f"resumed from step {start} (elastic onto {dims} mesh)")

    with mesh:
        for i in range(start, args.steps):
            t0 = time.perf_counter()
            batch = jax.tree_util.tree_map(jnp.asarray, pipe.batch(i))
            state, m = step_fn(state, batch, jax.random.PRNGKey(i))
            dt = time.perf_counter() - t0
            verdict = monitor.record(i, dt)
            if i % 5 == 0 or verdict == "straggle":
                print(f"step {i:4d} loss={float(m['loss']):.3f} "
                      f"nll={float(m['nll']):.3f} {dt * 1e3:.0f}ms [{verdict}]")
            if (i + 1) % 10 == 0:
                mgr.save(i + 1, state)
    mgr.wait()
    print("done; latest checkpoint:", mgr.latest_step())
    return 0


if __name__ == "__main__":
    sys.exit(main())
