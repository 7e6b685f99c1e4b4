"""The reference's block step, compiled for one chip of a described TPU
v5e (no chip needed): the running buffer is added to a block's sum in an
add of its own, never inside the output fusion of the HIGHEST dot that
makes the sum.  There, on the TPU, the buffer did not keep its float32
value, and the reference went wrong from its second step on while the
CPU agreed.

Loads the TPU compiler: run by hand, in a process of its own,
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_reference_compile.py``.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from cells import small
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import harness, reference, weights as W

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _folded(hlo: str) -> tuple[int, int]:
    """(buffer leaves, uses of a buffer leaf, or of its copy, as an
    operand of a convolution or of an output fusion)."""
    entry = hlo[hlo.index("\nENTRY"):].splitlines()
    names = {re.match(r"\s*%(\S+) = ", l).group(1) for l in entry
             if " parameter(" in l and l.lstrip().startswith("%buf")}
    leaves = len(names)
    for l in entry:                     # follow the buffer's copies
        m = re.match(r"\s*%(\S+) = .* copy-(?:start|done)\(%(\S+)\)", l)
        if m and m.group(2) in names:
            names.add(m.group(1))
    used = sum(1 for l in entry if "kind=kOutput" in l or "convolution(" in l
               for n in names if re.search(rf"%{re.escape(n)}[,)]", l))
    return leaves, used


@pytest.mark.parametrize("name", ["vit.sgd.pois1024.k4", "vit.dp.pois1024.k4",
                                  "phi3.dp.s1024.b8"])
def test_buffer_is_added_apart_from_the_dot(chip, name):
    cell = harness.load_cell(SPEC, name, small(name))
    abstract = W.abstract(cell.cfg.layout(cell.conf, cell.mix))
    sh = reference.shardings(abstract, [chip])
    f32 = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, jnp.float32, sharding=s), abstract, sh)
    step = reference.Step(cell.ref.row_losses, cell.conf, cell.mix,
                          reference.Mm(), cell.mix["reference_block"], sh,
                          tuple(a.dtype for a in jax.tree.leaves(abstract)))
    whole = NamedSharding(jax.tree.leaves(sh)[0].mesh, P())
    rows = cell.mix["reference_block"] * cell.mix["augmult"]
    blk = {k: jax.ShapeDtypeStruct((rows,) + v.shape[1:], v.dtype,
                                   sharding=whole)
           for k, v in cell.cfg.batch(cell.conf, cell.mix, 0, 0).items()
           if k != "mask"}
    n = cell.mix["reference_block"] if step.private else rows
    mask = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=whole)
    hlo = step.block_fn.lower(f32, f32, blk, mask).compile().as_text()
    leaves, used = _folded(hlo)
    assert leaves == len(jax.tree.leaves(abstract))
    assert used == 0, f"{used} buffer uses inside a dot's fusion"
