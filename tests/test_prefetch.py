"""Input prefetch in ``Trainer.run``: the next step's batch is built on one
worker thread while the device runs the step, and nothing else changes."""
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs.base import (DPConfig, OptimConfig, ShapeConfig,
                                TrainConfig)
from repro.train import Trainer, telemetry
from repro.train.checkpoint import CheckpointManager

from helpers import tiny_model

CASES = {
    # tiny ViT, Poisson sampling, augmult 2
    "vit-poisson-k2": ("vit-cifar10", ShapeConfig("t", 4, 8, "train"),
                       dict(sampling="poisson", augmult=2)),
    # tiny LM, fixed-size batches
    "lm-fixed": ("stablelm-3b", ShapeConfig("tiny", 8, 4, "train"),
                 dict(sampling="fixed")),
}


def _trainer(tmp_path, case="vit-poisson-k2", steps=4, log_every=1):
    name, shape, dp = CASES[case]
    arch, model = tiny_model(name)
    cfg = TrainConfig(arch=arch.name, steps=steps, log_every=log_every,
                      ckpt_every=10**6, ckpt_dir=str(tmp_path),
                      ckpt_async=False, param_dtype="float32",
                      compute_dtype="float32",
                      dp=DPConfig(algo="dpsgd_r", noise_multiplier=0.5, **dp),
                      optim=OptimConfig(lr=1e-3, total_steps=steps))
    return Trainer(model, cfg, shape)


def _keep_batches(tr):
    """Wrap ``make_batch`` on the instance, as the benchmark harness does;
    returns the {step: batch} it handed out."""
    got, make = {}, tr.make_batch

    def make_batch(step):
        got[step] = make(step)
        return got[step]

    tr.make_batch = make_batch
    return got


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_run_matches_one_step_runs(tmp_path, case):
    n = 4
    tr = _trainer(tmp_path / "a", case, steps=n)
    init = tr.init_state(jax.random.PRNGKey(0))
    whole = _keep_batches(tr)
    s_whole = tr.run(init, n, install_signals=False)
    prefetched = [telemetry.RECORDER.counts(s).get("prefetched", 0)
                  for s in range(n)]
    assert prefetched == [0] + [1] * (n - 1)

    tr.make_batch = type(tr).make_batch.__get__(tr)
    tr.ckpt = CheckpointManager(str(tmp_path / "b"), use_async=False)
    single = _keep_batches(tr)
    s_single = init
    for s in range(n):
        s_single = tr.run(s_single, s + 1, install_signals=False)
        assert "prefetched" not in telemetry.RECORDER.counts(s)

    assert sorted(whole) == sorted(single) == list(range(n))
    for s in range(n):
        _assert_trees_equal(whole[s], single[s])
    _assert_trees_equal(s_whole.params, s_single.params)
    _assert_trees_equal(s_whole.opt_state, s_single.opt_state)
    assert int(s_whole.step) == int(s_single.step) == n


def test_make_batch_once_per_step_on_the_loop_thread(tmp_path):
    tr = _trainer(tmp_path)
    calls, built = [], []
    loop = threading.get_ident()
    for name in ("make_batch", "shard_batch", "step_fn", "_watchdog"):
        fn = getattr(tr, name)

        def wrapped(*a, _fn=fn, _name=name):
            assert threading.get_ident() == loop, _name
            calls.append(_name if _name != "make_batch" else (_name, a[0]))
            return _fn(*a)
        setattr(tr, name, wrapped)
    build = tr._build_batch

    def build_batch(step):
        built.append(step)
        return build(step)
    tr._build_batch = build_batch

    state = tr.run(tr.init_state(jax.random.PRNGKey(0)), 3,
                   install_signals=False)
    assert sorted(built) == [0, 1, 2]      # nothing for a step >= 3
    tr.run(state, 5, install_signals=False)
    assert sorted(built) == [0, 1, 2, 3, 4]
    want = [c for s in range(5) for c in
            (("make_batch", s), "shard_batch", "step_fn", "_watchdog")]
    assert calls == want


def test_prefetched_and_batch_wait_per_step(tmp_path):
    tr = _trainer(tmp_path, log_every=10**6)
    state = tr.run(tr.init_state(jax.random.PRNGKey(0)), 4,
                   install_signals=False)
    tr.run(state, 7, install_signals=False)
    rec = telemetry.RECORDER
    for first, end in ((0, 4), (4, 7)):
        assert sum(rec.counts(s).get("prefetched", 0)
                   for s in range(first, end)) == end - first - 1
    for s in range(7):
        spans = rec.spans(s)
        assert spans["batch_wait"] >= 0
        # the worker's spans of the step survive the loop's start(step)
        assert {"sample", "augment"} <= set(spans), (s, spans)
    # each call logs its last step, with the counter's sum in the record
    assert [h["step"] for h in tr.history] == [3, 6]
    assert [h["prefetched"] for h in tr.history] == [3, 2]
    assert all(h["batch_wait_ms"] >= 0 for h in tr.history)


class Boom(Exception):
    pass


def test_builder_error_surfaces_at_its_step(tmp_path):
    tr = _trainer(tmp_path)
    state = tr.run(tr.init_state(jax.random.PRNGKey(0)), 1,
                   install_signals=False)
    threads = threading.active_count()
    err = Boom("build failed")
    build, taken = tr._build_batch, []

    def build_batch(step):
        if step == 3:
            raise err
        return build(step)
    tr._build_batch = build_batch
    make = tr.make_batch

    def make_batch(step):
        taken.append(step)
        return make(step)
    tr.make_batch = make_batch

    with pytest.raises(Boom) as info:
        tr.run(state, 6, install_signals=False)
    assert info.value is err
    assert taken == [1, 2, 3]               # raised where step 3 took it
    assert [h["step"] for h in tr.history] == [0, 1, 2]
    assert threading.active_count() == threads


def test_no_thread_outlives_a_preempted_run(tmp_path):
    tr = _trainer(tmp_path)
    state = tr.run(tr.init_state(jax.random.PRNGKey(0)), 1,
                   install_signals=False)
    threads = threading.active_count()
    watchdog = tr._watchdog

    def preempt_at_2(step, dt):
        if step == 2:
            tr._preempted = True        # as a SIGTERM mid-run would
        return watchdog(step, dt)
    tr._watchdog = preempt_at_2
    state = tr.run(state, 6, install_signals=False)
    assert int(state.step) == 3
    assert threading.active_count() == threads
    # the build of step 3 was joined with the call; a later call to
    # make_batch builds afresh and takes nothing left over
    batch = tr.make_batch(3)
    assert "prefetched" not in telemetry.RECORDER.counts(3)
    assert batch["images"].shape[0] == tr.capacity * 2


def test_recorder_written_from_two_threads_loses_nothing():
    rec = telemetry.Recorder(max_steps=64)
    n, steps = 4000, 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)         # switch threads as often as it can
    errors = []

    def writer(name):
        try:
            for i in range(n):
                step = i % steps
                rec.count(name, step)
                rec.count("both", step)
                with rec.span(name, step):
                    pass
                rec.summary(range(steps))
        except Exception as e:          # pragma: no cover - the failure
            errors.append(e)

    try:
        ts = [threading.Thread(target=writer, args=(f"w{j}",))
              for j in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    got = rec.summary(range(steps))
    assert got["w0"] == got["w1"] == n
    assert got["both"] == 2 * n
    for s in range(steps):
        assert set(rec.spans(s)) == {"w0", "w1"}
        assert rec.counts(s) == {"w0": n // steps, "w1": n // steps,
                                 "both": 2 * n // steps}
