"""A cell whose model is sharded over a (2, 2) data,model mesh, on a host
of four forced CPU devices.  XLA fixes the device count at its first
start, so each test runs its body in a subprocess of its own
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``):

* a mix with ``mesh`` builds that mesh; the cell runs through
  ``run_cell`` with its state split over ``model`` and comes out
  correct, and the half-batch fault comes out not correct;
* ``weights.make`` into a (2, 2) sharding draws the same bits as without
  one;
* the reference keeps at most three parameter-sized float32 trees alive
  and splits them over the four devices;
* a mesh that does not span the cell's chips fails before set-up.

Slow (interpret-mode kernels): run by hand,
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_mesh.py``.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness

PRELUDE = f"""
import json, sys, time
sys.path[:0] = {[str(harness.ROOT / "src"), str(harness.ROOT),
                 str(harness.BENCH / "tests")]!r}
import jax
import numpy as np
jax.config.update("jax_enable_compilation_cache", False)
assert jax.device_count() == 4, jax.devices()
from bench import harness
from cells import SMALL
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 77
"""

# phi3.dp.s1024.b8 on four chips: its files, shrunk, on a (2, 2) mesh, with
# float32 weights and compute so that the cell's own limits, set for
# bfloat16 at full width, hold at this size
MESH_CELL = """
w = {w["name"]: w for w in SPEC["workloads"]}["phi3.dp.s1024.b8"]
w["chips"] = 4
OVERRIDES = {"conf": SMALL["phi3"]["conf"],
             "mix": dict(SMALL["phi3"]["mix"], mesh={"data": 2, "model": 2},
                         param_dtype="float32", compute_dtype="float32")}
"""


def _child(body: str, head: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        PRELUDE + head + textwrap.dedent(body)],
                       capture_output=True, text=True, env=env,
                       cwd=harness.ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_is_correct_and_split_over_model():
    out = _child(head=MESH_CELL, body="""
    seen = []

    def record(trainer):
        step = trainer.step_fn

        def recorded(state, batch, key):
            if not seen:
                seen.extend(
                    (list(x.sharding.mesh.shape.items()), str(x.sharding.spec),
                     x.shape, x.sharding.shard_shape(x.shape))
                    for x in jax.tree.leaves(state.params))
            return step(state, batch, key)
        trainer.step_fn = recorded

    out = harness.run_cell(SPEC, w["name"], SEED, 1.0, False,
                           time.perf_counter(), devices=jax.devices(),
                           plant=record, overrides=OVERRIDES)
    print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                      "leaves": seen}))
    """)
    print(out["checks"])
    assert out["correct"] is True, out["checks"]
    meshes = {json.dumps(mesh) for mesh, *_ in out["leaves"]}
    assert meshes == {json.dumps([["data", 2], ["model", 2]])}, meshes
    split = [(spec, shape, local) for _, spec, shape, local in out["leaves"]
             if "model" in spec]
    assert split, out["leaves"]
    for spec, shape, local in split:
        assert local != shape, (spec, shape, local)


def test_mesh_cell_half_fault_is_not_correct():
    out = _child(head=MESH_CELL, body="""
    from cells import half
    out = harness.run_cell(SPEC, w["name"], SEED, 1.0, False,
                           time.perf_counter(), devices=jax.devices(),
                           plant=half, overrides=OVERRIDES)
    print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
    """)
    assert out["correct"] is False, out["checks"]


def test_sharded_draw_is_the_same_bits():
    out = _child("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import weights as W
    from repro.launch.mesh import make_mesh
    cell = harness.load_cell(SPEC, "phi3.dp.s1024.b8", SMALL["phi3"])
    layout = cell.cfg.layout(cell.conf, cell.mix)
    mesh = make_mesh((2, 2), ("data", "model"))

    def spec(leaf):
        shape = leaf.shape
        axes = [None] * len(shape)
        free = [i for i in range(len(shape)) if shape[i] % 2 == 0]
        for ax, i in zip(("data", "model"), free[-2:]):
            axes[i] = ax
        return NamedSharding(mesh, P(*axes))

    sh = jax.tree.map(spec, W.abstract(layout))
    whole = jax.tree.leaves(W.make(layout, SEED))
    split = jax.tree.leaves(W.make(layout, SEED, sh))
    bits = lambda a: np.asarray(a).view(np.uint8)
    print(json.dumps({
        "same": [bool(np.array_equal(bits(a), bits(b)))
                 for a, b in zip(whole, split)],
        "dtypes": sorted({str(a.dtype) for a in split}),
        "placed": [a.sharding == s for a, s in
                   zip(split, jax.tree.leaves(sh))],
        "split": [a.sharding.shard_shape(a.shape) != a.shape
                  for a in split]}))
    """)
    assert out["same"] and all(out["same"]), out
    assert all(out["placed"]) and all(out["split"]), out
    assert out["dtypes"] == ["bfloat16", "float32"]


def test_reference_keeps_three_trees_over_four_devices():
    out = _child("""
    from bench import reference, weights as W
    cell = harness.load_cell(SPEC, "phi3.dp.s1024.b8", SMALL["phi3"])
    layout = cell.cfg.layout(cell.conf, cell.mix)
    tree = sum(4 * int(np.prod(l.shape))
               for l in jax.tree.leaves(W.abstract(layout)))
    peak, split = [0], []

    def sample():
        peak[0] = max(peak[0], sum(a.nbytes for a in jax.live_arrays()
                                   if a.dtype == np.float32))

    def probed(fn):
        def call(*args):
            sample()
            out = fn(*args)
            sample()
            split.extend(x.size // np.prod(x.sharding.shard_shape(x.shape))
                         for x in jax.tree.leaves(out) if x.size >= 1024)
            return out
        return call

    class Probed(reference.Step):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            for name in ("block_fn", "add_noise", "rescale", "update"):
                setattr(self, name, probed(getattr(self, name)))

    reference.Step = Probed
    batches = [cell.cfg.batch(cell.conf, cell.mix, SEED, s)
               for s in range(harness.FIRST_STEPS)]
    out = reference.run(cell.ref.row_losses, cell.conf, cell.mix,
                        harness.reference_weights(cell, SEED, jax.devices()),
                        batches, SEED, reference.Mm(),
                        block=cell.mix["reference_block"])
    print(json.dumps({"peak": peak[0], "tree": tree,
                      "split": [int(n) for n in split],
                      "loss": out["loss"]}))
    """)
    assert out["split"] and set(out["split"]) == {4}, out["split"]
    assert 0 < out["peak"] <= 3.3 * out["tree"], out
    assert all(l > 0 for l in out["loss"]), out


@pytest.mark.parametrize("mesh, chips", [({"data": 2, "model": 2}, 1),
                                         ({"data": 2}, 4),
                                         ({"data": 1, "model": 2}, 4)])
def test_mesh_that_is_not_the_chips_fails_before_set_up(mesh, chips):
    out = _child(f"""
    w = {{w["name"]: w for w in SPEC["workloads"]}}["phi3.dp.s1024.b8"]
    w["chips"] = {chips}
    built = []
    harness.build_program = lambda *a, **k: built.append(1)
    try:
        harness.run_cell(SPEC, w["name"], SEED, 1.0, False,
                         time.perf_counter(), devices=jax.devices()[:{chips}],
                         overrides={{"conf": SMALL["phi3"]["conf"],
                                     "mix": {{"mesh": {mesh!r}}}}})
        err = None
    except ValueError as e:
        err = str(e)
    print(json.dumps({{"err": err, "built": len(built)}}))
    """)
    spans = 1
    for n in mesh.values():
        spans *= n
    assert out["err"] and out["built"] == 0, out
    assert f"spans {spans} devices" in out["err"], out["err"]
    assert f"asks for {chips} chip" in out["err"], out["err"]


@pytest.mark.parametrize("chips", [1, 4])
def test_mesh_without_the_key_is_every_chip_on_data(chips):
    assert harness.mesh_of({}, chips) == {"data": chips}
    assert list(harness.mesh_of({"mesh": {"data": 2, "model": 2}}, 4)) == [
        "data", "model"]
