"""Per-chip memory of a cell's programs, compiled for a described TPU
topology; no chip is needed, and nothing runs.

    python bench/topology.py --workload <name> [--chips 4] \\
        [--conf '{"num_hidden_layers": 32}'] \\
        [--mix '{"mesh": {"data": 2, "model": 2}}'] [--topology v5e:2x2]

Compiles, for the first ``chips`` devices of the topology, the program's
training step on the cell's mesh (the state and the batch placed as
``harness.build_program`` places them), and the reference's block step
and noise over the reference's shardings, and prints each one's
``memory_analysis()`` per chip as one JSON line.  ``--conf`` and ``--mix``
override the cell's files, and ``--chips`` its entry, as a later cell's
files would.  A compile is not a run: these are the compiler's numbers
for one program at a time, not a chip's.  Run it on the CPU, with
``JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "generated_code_bytes": m.generated_code_size_in_bytes}


def program_step(cell, devices) -> dict:
    """The program's jitted step on the cell's mesh over ``devices``."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.dist import batch_shardings, runtime, state_shardings
    from repro.dist.sharding import batch_pspec
    from bench import harness

    axes = tuple(cell.mesh)
    mesh = jax.make_mesh(tuple(cell.mesh.values()), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = harness.make_trainer(cell, 0, ckpt, mesh)
    phys = trainer.capacity
    state = trainer.abstract_state()
    sh = state_shardings(mesh, trainer.model, state, zero1=trainer.cfg.zero1)
    placed = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state = jax.tree.map(placed, state, sh)
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         cell.cfg.batch(cell.conf, cell.mix, 0, 0))
    batch = jax.tree.map(placed, batch, batch_shardings(mesh, batch, phys))
    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32,
                               sharding=NamedSharding(mesh, P()))
    with mesh, runtime.layout(mesh, batch_pspec(mesh, phys)):
        compiled = trainer.step_fn.lower(state, batch, key).compile()
    hlo = compiled.as_text()
    out = _memory(compiled)
    out["collectives"] = {op: hlo.count(f" {op}(") + hlo.count(
        f" {op}-start(") for op in ("all-gather", "reduce-scatter",
                                    "all-reduce", "collective-permute",
                                    "all-to-all")}
    return out


def reference_steps(cell, devices) -> dict:
    """The reference's block step and noise over ``devices`` (its update
    goes leaf by leaf, never more than one leaf beyond the trees)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import reference, weights as W

    abstract = W.abstract(cell.cfg.layout(cell.conf, cell.mix))
    store = tuple(l.dtype for l in jax.tree.leaves(abstract))
    sh = reference.shardings(abstract, devices)
    f32 = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, jnp.float32, sharding=s), abstract, sh)
    step = reference.Step(cell.ref.row_losses, cell.conf, cell.mix,
                          reference.Mm(), cell.mix["reference_block"], sh,
                          store)
    whole = NamedSharding(jax.tree.leaves(sh)[0].mesh, P())
    rows = cell.mix["reference_block"] * cell.mix["augmult"]
    blk = {k: jax.ShapeDtypeStruct((rows,) + v.shape[1:], v.dtype,
                                   sharding=whole)
           for k, v in cell.cfg.batch(cell.conf, cell.mix, 0, 0).items()
           if k != "mask"}
    n = rows if not step.private else cell.mix["reference_block"]
    mask = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=whole)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    tree = sum(4 * int(np.prod(a.shape)) for a in jax.tree.leaves(abstract))
    out = {"f32_tree_bytes_per_chip": tree / len(devices),
           "block": _memory(step.block_fn.lower(f32, f32, blk, mask).compile())}
    if step.private:
        out["add_noise"] = _memory(step.add_noise.lower(f32, key).compile())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--conf", default="{}")
    ap.add_argument("--mix", default="{}")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from jax.experimental import topologies
    from repro.kernels import ops
    from bench import harness

    # the described chips are compiled for, not run: no persistent cache,
    # and the Pallas kernels lower to Mosaic, not to the interpreter
    jax.config.update("jax_enable_compilation_cache", False)
    ops.interpret = lambda: False
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if args.chips:
        {w["name"]: w for w in spec["workloads"]}[args.workload][
            "chips"] = args.chips
    cell = harness.load_cell(spec, args.workload,
                             {"conf": json.loads(args.conf),
                              "mix": json.loads(args.mix)})
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devices = topo.devices[:cell.chips]
    out = {"workload": args.workload, "chips": cell.chips,
           "mesh": cell.mesh, "topology": args.topology,
           "device_kind": devices[0].device_kind,
           "program_step": program_step(cell, devices),
           "reference": reference_steps(cell, devices)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
