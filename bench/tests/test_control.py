"""The check fails what it must, at a size a CPU test run can hold:

* the control (the plain reference with float8 matmul operands, the
  precision below the configuration's bfloat16) comes out not correct
  under each cell's limits;
* a run of the harness, with its look for a chip skipped and the timed
  path broken underneath, comes out not correct for each fault a
  one-chip training cell can have: a step that returns its state
  unchanged, half of the batch left out with the mean taken over the
  rest or with the full batch's normaliser kept, and a token or label
  altered where the feed produces it.

Slow (interpret-mode kernels): run by hand,
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_control.py``.
"""
import json
import time

import jax
import pytest
from cells import altered, half, half_sum, small, unchanged

from bench import check, control, harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 99


@pytest.fixture(autouse=True, scope="module")
def no_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = harness.load_cell(SPEC, name, small(name))
    nums = control.readings(cell, SEED, ("control",))["control"]
    ok, _ = check.judge(nums, cell.limits)
    assert not ok, nums


@pytest.mark.parametrize("fault", [unchanged, half, half_sum, altered],
                         ids=["unchanged", "half", "halfsum", "altered"])
@pytest.mark.parametrize("name", ["vit.dp.pois1024.k4", "phi3.dp.s1024.b8"])
def test_fault_is_not_correct(name, fault):
    out = harness.run_cell(SPEC, name, SEED, 1.0, False, time.perf_counter(),
                           devices=jax.devices()[:1], plant=fault,
                           overrides=small(name))
    assert out["correct"] is False, out["checks"]
