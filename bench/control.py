"""Readings that set the upper end of each limit; not run by the benchmark.

    python bench/control.py --workload <name> --seeds 11,12,13 [--program 21,22,...]

For each seed, at the cell's own sizes: the plain reference (float32,
HIGHEST), the control (the same reference with float8 e4m3 matmul
operands, the precision below the configuration's bfloat16) and two
half-batch faults (half of the examples left out, and the mean taken over
the rest, ``half``, or the configuration's normaliser kept, ``halfsum``),
each compared with the reference by the numbers of bench/check.py.
A step that returns its state unchanged reads 1 on ``change_gap`` by
construction, and an altered token or answer reads at least 1 on
``batch_mismatch``; neither needs a run.  With ``--program``, first runs
the harness itself (set-up, a short window, the check) on each of those
seeds in this one process, for the lower readings.  Prints one JSON line
per seed and variant.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, variants=("control", "half", "halfsum")
             ) -> dict:
    """Each variant's compared numbers on one seed, on the cell's chips."""
    import jax
    from bench import check, harness, reference
    mix = cell.mix
    want = [cell.cfg.batch(cell.conf, mix, seed, s)
            for s in range(harness.FIRST_STEPS)]
    draw = harness.reference_weights(cell, seed, jax.devices()[:cell.chips])
    run = lambda mm, fault=None: reference.run(
        cell.ref.row_losses, cell.conf, mix, draw, want, seed, mm,
        block=mix["reference_block"], fault=fault)
    ref = run(reference.Mm())
    out = {}
    for v in variants:
        got = run(reference.Mm(fp8=True)) if v == "control" else \
            run(reference.Mm(), fault=v)
        out[v] = check.numbers(dict(got, batch_mismatch=0), ref,
                               mix["algo"] != "sgd")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, args.workload)
    for seed in (int(s) for s in args.program.split(",") if s):
        import jax
        t = time.perf_counter()
        out = harness.run_cell(spec, args.workload, seed, args.seconds, False,
                               t, devices=jax.devices()[:cell.chips])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": "program", "correct": out["correct"],
                          "numbers": {k: v["value"]
                                      for k, v in out["checks"].items()},
                          "seconds": time.perf_counter() - t}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        for variant, nums in readings(cell, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": variant, "numbers": nums,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
