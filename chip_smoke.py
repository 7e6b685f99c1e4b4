"""Smoke run of DP-SGD training on TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, on four chips

One chip: trains ``vit-cifar10`` at its published widths (8 layers,
d_model 256, 8 heads, d_ff 1024, patch 4 over 32x32x3 = 64 tokens) for 5
DP-SGD(R) steps at the ``train_4k`` global batch of 256, with the fused
norm strategy on the Pallas kernels, through ``repro.launch.train.main``.
It then checks that the compiled step holds Mosaic kernels
(``tpu_custom_call``), and that from one state and batch the kernel route
and the jnp route (``dp.use_kernels=false``) give the same per-example
norms² and the same clipped gradient sum.

Four chips: only the sharded phase.  The same steps run on a (4,) data mesh
and on a (2, 2) data,model mesh, and each is compared with a run on device 0
alone.

The process owns the chips and starts no other process.  It exits non-zero
and prints no result when JAX finds no TPU, or when the repository's
sources are not beside this file; any failed phase exits non-zero.  The
last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
from dataclasses import replace
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "vit-cifar10"
BATCH = 256          # train_4k global batch
STEPS = 5
# Route check, kernel vs jnp, one step from one state and batch (bf16
# compute): per-example norm² and clipped-sum relative errors.  The two
# routes differ only in f32 accumulation order and in where bf16 roundings
# fall; see CHANGES.md for the measured spread these bounds sit above.
NSQ_RTOL = 2e-2
SUM_RTOL = 2e-2
# Mesh check, sharded vs device 0 alone, over the 5 steps: per-step loss
# relative error, and the error of the 5 steps' parameter change relative
# to that change (bf16 parameters; see CHANGES.md).
LOSS_RTOL = 1e-2
UPDATE_RTOL = 5e-2


def fail(msg: str):
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < chips:
        fail(f"{chips} chips asked for, JAX sees {len(devices)}")
    return devices


def train(steps: int, batch: int, *, mesh: str = "", axes: str = "data",
          extra=()):
    """``steps`` DP-SGD(R) steps of the ViT through the training launcher,
    from a fresh checkpoint directory; fails unless exactly those steps
    ran and every loss is finite.  Returns (trainer, state, memory report).
    """
    from repro.launch import train as launcher
    with tempfile.TemporaryDirectory() as ckpt:
        argv = ["--arch", ARCH, "--shape", "train_4k",
                "--steps", str(steps), "--batch", str(batch),
                "--set", "dp.algo=dpsgd_r",
                "--set", "dp.norm_strategy=fused",
                "--set", "dp.use_kernels=true",
                "--set", f"ckpt_dir={ckpt}", "--set", "log_every=1",
                *extra]
        if mesh:
            argv += ["--mesh", mesh, "--axes", axes]
        trainer, state, rep = launcher.main(argv)
    ran = [h["step"] for h in trainer.history]
    if int(state.step) != steps or ran != list(range(steps)):
        fail(f"expected steps 0..{steps - 1}, ran {ran} "
             f"(final step {int(state.step)})")
    losses = [h["loss"] for h in trainer.history]
    if not all(math.isfinite(x) for x in losses):
        fail(f"non-finite loss: {losses}")
    return trainer, state, rep


def step_inputs(trainer, state):
    """The batch and key the trainer would feed its next step."""
    import jax
    step = int(state.step)
    batch = trainer.shard_batch(trainer.make_batch(step))
    key = jax.random.fold_in(jax.random.PRNGKey(trainer.cfg.seed), step)
    return batch, key


def rel_l2(a_tree, b_tree, origin=None) -> float:
    """‖a − b‖ / ‖b − origin‖ over all leaves (origin 0 when None), in
    float64 on the host."""
    import jax
    import numpy as np
    leaves = jax.tree.leaves
    origins = leaves(origin) if origin is not None else \
        [0.0] * len(leaves(b_tree))
    num = den = 0.0
    for a, b, o in zip(leaves(a_tree), leaves(b_tree), origins):
        a, b, o = (np.asarray(t).astype(np.float64) for t in (a, b, o))
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum((b - o) ** 2))
    return math.sqrt(num / max(den, 1e-300))


def phase_train(batch: int):
    trainer, state, rep = train(STEPS, batch)
    for h in trainer.history:
        print(f"[chip_smoke] step {h['step']} loss {h['loss']!r} "
              f"finite {math.isfinite(h['loss'])} "
              f"step_time_s {h['sec']!r}")
    print(f"[chip_smoke] compile_s {rep['compile_s']!r} "
          f"(train step, launcher's AOT compile)")
    return trainer, state


def phase_hlo(trainer, state):
    batch, key = step_inputs(trainer, state)
    hlo = trainer.step_fn.lower(state, batch, key).compile().as_text()
    n = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"[chip_smoke] tpu_custom_call in compiled step HLO: {n}")
    if n <= 0:
        fail("the compiled step holds no Mosaic kernel")


def phase_route(trainer, state):
    """One step's clipped sum through the kernel route and the jnp route,
    from the same state and batch (the key only draws the noise, which is
    identical by construction and added after this)."""
    import jax
    import numpy as np
    from repro.core.algo import make_clipped_sum_fn
    batch, _ = step_inputs(trainer, state)
    out = {}
    for route, use_kernels in (("kernel", True), ("jnp", False)):
        dp = replace(trainer.cfg.dp, use_kernels=use_kernels)
        fn = jax.jit(make_clipped_sum_fn(trainer.model.loss_fn, dp))
        summed, (_, nsq) = fn(state.params, batch)
        out[route] = (summed, np.asarray(nsq, np.float64))
    nk, nj = out["kernel"][1], out["jnp"][1]
    nsq_err = float(np.max(np.abs(nk - nj) / np.maximum(np.abs(nj), 1e-30)))
    sum_err = rel_l2(out["kernel"][0], out["jnp"][0])
    print(f"[chip_smoke] route check kernel vs jnp: per-example nsq max "
          f"rel err {nsq_err!r} (tol {NSQ_RTOL}), clipped sum rel L2 err "
          f"{sum_err!r} (tol {SUM_RTOL}), nsq range "
          f"[{nj.min()!r}, {nj.max()!r}]")
    if not (nsq_err <= NSQ_RTOL and sum_err <= SUM_RTOL):
        fail("kernel route disagrees with the jnp route")


def phase_memory():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[chip_smoke] peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")


def phase_mesh(batch: int):
    """The sharded launcher path against device 0 alone."""
    import jax
    no_check = ("--set", "mem.compiled_check=false")
    ref, ref_state, _ = train(STEPS, batch, mesh="1", axes="data",
                              extra=no_check)
    ref_losses = [h["loss"] for h in ref.history]
    init = ref.init_state(jax.random.PRNGKey(ref.cfg.seed)).params
    for mesh, axes in (("4", "data"), ("2,2", "data,model")):
        tr, state, _ = train(STEPS, batch, mesh=mesh, axes=axes,
                             extra=no_check)
        losses = [h["loss"] for h in tr.history]
        loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                           ref_losses))
        update_err = rel_l2(state.params, ref_state.params, init)
        print(f"[chip_smoke] mesh ({mesh}) {axes} vs device 0: losses "
              f"{losses} vs {ref_losses}; max loss rel err {loss_err!r} "
              f"(tol {LOSS_RTOL}), parameter change rel L2 err "
              f"{update_err!r} (tol {UPDATE_RTOL})")
        if not (loss_err <= LOSS_RTOL and update_err <= UPDATE_RTOL):
            fail(f"mesh ({mesh}) {axes} disagrees with device 0")


def run(chips: int, batch: int = BATCH):
    if chips == 4:
        phase_mesh(batch)
    else:
        trainer, state = phase_train(batch)
        phase_hlo(trainer, state)
        phase_route(trainer, state)
        phase_memory()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"[chip_smoke] FAIL: no repository sources at {SRC}",
              file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, str(SRC))
    devices = require_tpu(args.chips)
    from repro.launch import compile_cache
    cache = Path(compile_cache.enable())
    n = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    print(f"[chip_smoke] compile cache {cache}: {n} entries at start")
    run(args.chips)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
