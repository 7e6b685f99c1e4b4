"""Production training launcher.

Single-process CPU (default) or multi-controller TPU fleet:

  # one host of a fleet (called once per host by the cluster scheduler):
  python -m repro.launch.train --arch phi3-mini-3.8b --shape train_4k \
      --mesh 16,16 --axes data,model \
      --coordinator 10.0.0.1:8476 --num-processes 64 --process-id $RANK

  # laptop-scale smoke run:
  python -m repro.launch.train --arch phi3-mini-3.8b --reduced --steps 20 \
      --set dp.noise_multiplier=0.8 --set optim.lr=3e-4

``main(argv)`` also runs in-process (chip_smoke.py does) and returns the
trainer, the final state and the launch memory report.

The loop is the same fault-tolerant ``Trainer`` the tests exercise;
at fleet scale the step function is pjit-sharded over the production mesh
and each host feeds its deterministic shard of the global batch.
"""
from __future__ import annotations

import argparse
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (SHAPES, apply_overrides, get_arch, parse_set_args,
                           reduced)
from repro.configs.base import ShapeConfig, TrainConfig
from repro.dist import batch_shardings, runtime, state_shardings
from repro.dist.sharding import (batch_axis_width, batch_pspec,
                                 stage_axis_width)
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import build_model_for
from repro.train import Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke scale)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 16,16")
    ap.add_argument("--axes", default="data,model")
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides, e.g. --set dp.clip_norm=0.5")
    ap.add_argument("--autotune", action="store_true",
                    help="solve for the fastest feasible launch plan "
                         "(launch/autotune.py) before launching; knobs "
                         "via --set tune.seed=... etc.")
    # multi-controller flags
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.coordinator:
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    shape = SHAPES[args.shape]
    if args.batch or args.seq or args.reduced:
        shape = ShapeConfig(shape.name,
                            args.seq or (64 if args.reduced else shape.seq_len),
                            args.batch or (8 if args.reduced else
                                           shape.global_batch),
                            shape.kind)

    cfg = TrainConfig(arch=arch.name, shape=shape.name)
    cfg = apply_overrides(cfg, parse_set_args(args.set))
    if args.steps is not None:
        cfg = replace(cfg, steps=args.steps,
                      optim=replace(cfg.optim, total_steps=args.steps))

    if args.mesh:
        mesh = make_mesh([int(s) for s in args.mesh.split(",")],
                         args.axes.split(","))
    else:
        mesh = make_host_mesh()

    plan = None
    if args.autotune:
        from repro.launch.autotune import solve
        mesh_shape = tuple(int(s) for s in mesh.devices.shape)
        report = solve(arch, cfg, shape, mesh_shapes=[mesh_shape])
        plan = report.plan
        print(f"[train] autotune ({report.method}, seed={report.seed}): "
              f"searched {report.space_size} plans, {report.traces} traces "
              f"({report.cache_hits} cache hits); winner {plan}")
        if report.rank_correlation is not None:
            print(f"[train] autotune predicted-vs-measured rank "
                  f"correlation: {report.rank_correlation:.3f} over "
                  f"{len(report.measured)} measured plans")
        cfg = plan.apply(cfg)

    model = build_model_for(arch, param_dtype=cfg.param_dtype,
                            compute_dtype=cfg.compute_dtype, remat=cfg.remat,
                            pp_stages=cfg.pp_stages,
                            pp_microbatches=cfg.pp_microbatches)

    # the trainer owns the physical per-step row count: == global_batch for
    # fixed sampling; under dp.sampling="poisson" a padded step-invariant
    # capacity rounded to the mesh's batch-axis width so the batch — and
    # its (B,) bool mask leaf — shards over the full data axis
    trainer = Trainer(model, cfg, shape, batch_multiple=batch_axis_width(mesh),
                      plan=plan)
    phys_batch = trainer.capacity
    if cfg.dp.sampling == "poisson":
        print(f"[train] poisson sampling: expected batch "
              f"{shape.global_batch}, padded capacity {phys_batch}")

    # batch-local layout active while the step traces: MoE dispatch and the
    # embedding norm rule run per-batch-shard under shard_map instead of the
    # GSPMD-replicated scatter (dist/runtime.py)
    with mesh, runtime.layout(mesh, batch_pspec(mesh, phys_batch)):
        def shard_batch(b):
            abs_tree = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b)
            sh = batch_shardings(mesh, abs_tree, phys_batch)
            return jax.tree.map(lambda a, s: jax.device_put(a, s), b, sh)

        trainer.shard_batch = shard_batch
        # compute the target state shardings *before* restore so a sharded
        # checkpoint is assembled straight onto its destination devices
        # (no single-host funnel) — works for fresh init and for
        # checkpoints restored from a different mesh (elastic restart)
        state_abs = trainer.abstract_state()
        sh = state_shardings(mesh, model, state_abs, zero1=cfg.zero1)
        fresh = trainer.ckpt.latest_step() is None
        state = trainer.restore_or_init(jax.random.PRNGKey(cfg.seed),
                                        shardings=sh)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), state, sh)
        if fresh:
            # multi-process init verification: every host fingerprints its
            # view of the initialized params; mismatch = seed/config drift
            fp = runtime.verify_init_consistency(state.params)
            print(f"[train] init fingerprint {fp:#010x} "
                  f"({jax.process_count()} process(es) agree)")
        # estimated-vs-compiled peak, logged every launch so estimator
        # drift (and the remat policy's effect) is visible in production
        rep = trainer.memory_report(
            state, shard_batch(trainer.make_batch(int(state.step))),
            jax.random.PRNGKey(cfg.seed), compile=cfg.mem.compiled_check)
        xla = rep.get("xla_peak_bytes")
        print(f"[train] memory: estimated peak "
              f"{rep['peak_bytes'] / 1e9:.3f} GB (remat={cfg.remat}, "
              f"grad_accum={trainer.cfg.grad_accum}, "
              f"per-example side-channel "
              f"{rep['per_example_grad_bytes'] / 1e9:.3f} GB)"
              + (f"; compiled peak {xla / 1e9:.3f} GB "
                 f"(estimate/xla {rep['estimate_vs_xla']:.2f}, "
                 f"compiled in {rep['compile_s']:.1f} s)"
                 if xla else ""))
        from repro.launch.memory import per_device_peak_bytes
        per_dev = per_device_peak_bytes(rep, batch_axis_width(mesh),
                                        stages=stage_axis_width(mesh))
        if cfg.mem.hbm_budget_bytes and per_dev > cfg.mem.hbm_budget_bytes:
            print(f"[train] WARNING estimated per-device peak "
                  f"{per_dev / 1e9:.3f} GB exceeds mem.hbm_budget_bytes="
                  f"{cfg.mem.hbm_budget_bytes / 1e9:.3f} GB "
                  f"(set mem.auto_microbatch=true to split the batch)")
        state = trainer.run(state)
        eps = trainer.accountant.epsilon_at(int(state.step))
        print(f"[train] finished at step {int(state.step)}; "
              f"privacy spent: eps={eps:.3f} "
              f"(delta={cfg.dp.delta}, sampling={cfg.dp.sampling}, "
              f"q={trainer.sample_rate:.2e})")
    return trainer, state, rep


if __name__ == "__main__":
    main()
