"""Fused-vs-separate kernel benchmark for the DP side-channel.

Times the single-launch fused dense backward (``kops.dense_bwd_norm``:
activation grad + per-example norm² in one kernel sweep,
kernels/fused_bwd.py) against the two-launch separate-pass baseline
(``kops.dense_dgrad`` + ``kops.pegrad_norm``: the dgrad kernel followed by
DiVa's outer-product norm kernel re-reading x/gy from HBM) at the dense-site
shapes of reduced arch presets, plus the cnn-cifar10 conv-patch shape.  An
informational (ungated) cell times the Pallas flash-attention backward pair
against the blocked-jnp backward.

  PYTHONPATH=src python -m benchmarks.kernel_bench \
      [--archs phi3-mini-3.8b stablelm-3b] [--batch 4] [--seq 64] [--reps 5]

Writes ``BENCH_kernels.json`` and **exits non-zero if any gated fused cell
is slower than its separate-pass baseline** — the `make bench-kernels` / CI
regression gate for ROADMAP item 1 (kernel fusion of the norm
side-channel).  Interpret-mode caveat: off-TPU both routes run the same
Pallas interpreter, so the measured win is launch/traffic structure (one
grid sweep and one HBM read of x/gy instead of two), not MXU throughput.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.kernels import ops as kops

F32 = jnp.float32


def _time(fn, *args, reps: int = 5):
    """jit + warm + min-of-reps wall time (s).  Min, not median: the
    interpret-mode runs sit on a shared CPU where scheduling noise is
    one-sided (it only ever adds time), so the minimum is the stable
    estimator of the actual work."""
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        walls.append(time.perf_counter() - t0)
    return float(np.min(walls)), [round(w, 5) for w in walls]


def dense_cells(names, B, T, key):
    """One gated cell per (arch, dense-site shape): attention out-proj
    (d_model × d_model) and FFN down-proj (d_ff × d_model)."""
    cells = []
    for name in names:
        arch = reduced(ARCHS[name])
        if arch.family == "cnn":
            # conv2d fused route operates on im2col patches: the dense-site
            # shape is (B, H·W, kh·kw·Cin) @ (kh·kw·Cin, Cout)
            c = arch.cnn
            s, cin, cout = c.image_size, c.stage_channels[0], \
                c.stage_channels[1]
            shapes = [("conv-patch", B, s * s, c.kernel * c.kernel * cin,
                       cout)]
        else:
            shapes = [("attn-out", B, T, arch.d_model, arch.d_model),
                      ("ffn-down", B, T, arch.ff_dense(), arch.d_model)]
        for site, b, t, di, do in shapes:
            cells.append({"arch": name, "site": site,
                          "shape": [b, t, di, do]})
    # dedupe identical shapes across presets (reduced archs often collapse)
    seen, out = set(), []
    for c in cells:
        k = (c["site"], tuple(c["shape"]))
        if k not in seen:
            seen.add(k)
            out.append(c)
    return out


def bench_dense_cell(cell, key, reps):
    B, T, di, do = cell["shape"]
    x = jax.random.normal(key, (B, 1, T, di), F32)
    gy = jax.random.normal(jax.random.fold_in(key, 1), (B, 1, T, do), F32)
    w = jax.random.normal(jax.random.fold_in(key, 2), (di, do), F32)

    def fused(x, gy, w):
        return kops.dense_bwd_norm(x, gy, w)

    def separate(x, gy, w):
        return kops.dense_dgrad(gy, w), kops.pegrad_norm(x, gy)

    # parity guard: the bench only counts if the two routes agree
    (gx_f, nsq_f) = jax.jit(fused)(x, gy, w)
    (gx_s, nsq_s) = jax.jit(separate)(x, gy, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nsq_f), np.asarray(nsq_s),
                               rtol=1e-5)

    t_f, walls_f = _time(fused, x, gy, w, reps=reps)
    t_s, walls_s = _time(separate, x, gy, w, reps=reps)
    return dict(cell, fused_s=round(t_f, 5), separate_s=round(t_s, 5),
                fused_walls_s=walls_f, separate_walls_s=walls_s,
                speedup=round(t_s / t_f, 3), gated=True)


def bench_attention_cell(B, T, key, reps):
    """Informational: Pallas flash backward pair vs the blocked-jnp
    backward.  Not gated — off-TPU the interpreter loses to fused XLA."""
    KV, rep, hd = 2, 2, 16
    q = 0.5 * jax.random.normal(key, (B, T, KV, rep, hd), F32)
    k = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (B, T, KV, hd),
                                F32)
    v = 0.5 * jax.random.normal(jax.random.fold_in(key, 2), (B, T, KV, hd),
                                F32)
    do = jax.random.normal(jax.random.fold_in(key, 3), (B, T, KV, rep, hd),
                           F32)

    def pallas(q, k, v, do):
        return kops.flash_attention_bwd(q, k, v, do, True)

    def jnp_bwd(q, k, v, do):
        _, pull = jax.vjp(lambda qq, kk, vv:
                          kops.flash_attention(qq, kk, vv, True), q, k, v)
        return pull(do)

    for g, r in zip(jax.jit(pallas)(q, k, v, do),
                    jax.jit(jnp_bwd)(q, k, v, do)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)
    t_p, walls_p = _time(pallas, q, k, v, do, reps=reps)
    t_j, walls_j = _time(jnp_bwd, q, k, v, do, reps=reps)
    return {"arch": "-", "site": "flash-bwd", "shape": [B, T, KV, rep, hd],
            "fused_s": round(t_p, 5), "separate_s": round(t_j, 5),
            "fused_walls_s": walls_p, "separate_walls_s": walls_j,
            "speedup": round(t_j / t_p, 3), "gated": False,
            "note": "pallas bwd kernels vs blocked-jnp bwd; informational"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+",
                    default=["phi3-mini-3.8b", "stablelm-3b", "cnn-cifar10"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()

    key = jax.random.PRNGKey(args.seed)
    cells = [bench_dense_cell(c, jax.random.fold_in(key, i), args.reps)
             for i, c in enumerate(dense_cells(args.archs, args.batch,
                                               args.seq, key))]
    cells.append(bench_attention_cell(args.batch, args.seq,
                                      jax.random.fold_in(key, 999),
                                      args.reps))

    gated = [c for c in cells if c["gated"]]
    losers = [c for c in gated if c["speedup"] < 1.0]
    result = {
        "config": {"archs": args.archs, "batch": args.batch, "seq": args.seq,
                   "reps": args.reps,
                   "interpret": kops.interpret(),
                   "baseline": "dense_dgrad + pegrad_norm (2 launches)",
                   "fused": "dense_bwd_norm (1 launch)"},
        "cells": cells,
        "min_gated_speedup": min(c["speedup"] for c in gated),
        "geomean_gated_speedup": round(float(np.exp(np.mean(
            [np.log(c["speedup"]) for c in gated]))), 3),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"[kernel_bench] {len(gated)} gated cells, min speedup "
          f"{result['min_gated_speedup']}x, geomean "
          f"{result['geomean_gated_speedup']}x; wrote {args.out}")
    if losers:
        raise SystemExit(
            "[kernel_bench] FAIL: fused slower than separate-pass baseline "
            "on: " + ", ".join(f"{c['arch']}/{c['site']}" for c in losers))


if __name__ == "__main__":
    main()
