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
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SMALL = {
    "vit": {"conf": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                         head_dim=16, d_ff=128, image_size=8, patch_size=2),
            "mix": dict(batch=8, augmult=2, reference_block=4)},
    "phi3": {"conf": dict(num_hidden_layers=2, hidden_size=64,
                          intermediate_size=128, num_attention_heads=4,
                          num_key_value_heads=4, head_dim=16, vocab_size=256,
                          padded_vocab=256),
             "mix": dict(batch=4, seq_len=16, reference_block=1)},
}
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 99


def small(name):
    return SMALL[name.split(".")[0]]


@pytest.fixture(autouse=True, scope="module")
def no_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = harness.load_cell(SPEC, name, small(name))
    nums = control.readings(cell, SEED, ("control",))["control"]
    ok, _ = check.judge(nums, cell.limits)
    assert not ok, nums


def _unchanged(trainer):
    step = trainer.step_fn

    def returns_input(state, batch, key):
        _, metrics = step(state, batch, key)
        return state, metrics
    trainer.step_fn = returns_input


def _half(trainer, keep_normaliser=False):
    from repro.train.trainer import make_train_step
    K = trainer.cfg.dp.augmult
    poisson = trainer.sampling == "poisson"
    B = trainer.shape.global_batch
    half = jax.jit(make_train_step(
        trainer.model, trainer.cfg,
        expected_batch_size=B if keep_normaliser else
        B / 2 if poisson else None))

    def half_batch(state, batch, key):
        if "mask" in batch:
            me = batch["mask"].reshape(-1, K)[:, 0]
            keep = me & (jnp.cumsum(me) <= (jnp.sum(me) + 1) // 2)
            batch = dict(batch, mask=jnp.repeat(keep, K))
        else:
            R = len(jax.tree.leaves(batch)[0])
            batch = jax.tree.map(lambda a: a[:R // 2], batch)
        return half(state, batch, key)
    trainer.step_fn = half_batch


def _half_sum(trainer):
    _half(trainer, keep_normaliser=True)


def _altered(trainer):
    make = trainer.make_batch

    def altered(step):
        b = {k: np.array(v) for k, v in make(step).items()}
        if "labels" in b:
            b["labels"][0] = (b["labels"][0] + 1) % 10
        else:
            b["tokens"][0, 1] = (b["tokens"][0, 1] + 1) % 256
        return b
    trainer.make_batch = altered


@pytest.mark.parametrize("fault", [_unchanged, _half, _half_sum, _altered],
                         ids=["unchanged", "half", "halfsum", "altered"])
@pytest.mark.parametrize("name", ["vit.dp.pois1024.k4", "phi3.dp.s1024.b8"])
def test_fault_is_not_correct(name, fault):
    out = harness.run_cell(SPEC, name, SEED, 1.0, False, time.perf_counter(),
                           devices=jax.devices()[:1], plant=fault,
                           overrides=small(name))
    assert out["correct"] is False, out["checks"]
