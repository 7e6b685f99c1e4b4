"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in BENCHMARK.json from its files under bench/, runs
set-up (weights from the seed, compile or cache load, the first steps the
check follows, warm-up), measures ``--seconds`` of whole loop periods of
the program's training loop, then checks the first steps against the plain
reference.  With ``--trace 1`` the window is traced and the per-layer
metrics are read from the trace; otherwise the end-to-end metrics are
reported.  The last line of standard output is the result object; the
compared numbers, each beside its limit, are the last lines of standard
error.  Exits non-zero, printing no result, without the cell's chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}.get(args.workload)
    if cell is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("the program (src/repro) is not beside the benchmark",
              file=sys.stderr)
        return 2
    # the compile cache lives at one fixed path inside the checkout
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = [d for d in jax.devices() if d.platform == "tpu"]
    if len(devices) < int(cell["chips"]):
        print(f"the cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    from bench import harness
    out = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START,
                           devices=devices[:int(cell["chips"])])
    for k, v in out["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
