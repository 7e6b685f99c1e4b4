"""The training loop's host spans and counters (repro.train.telemetry), and
the names the DP step gives its device work (named scopes)."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import (DPConfig, OptimConfig, ShapeConfig,
                                TrainConfig)
from repro.train import Trainer, telemetry
from repro.train.trainer import make_train_step

from helpers import tiny_model

SHAPE = ShapeConfig("t", 4, 8, "train")
LOOP_SPANS = {"batch_wait", "sample", "transfer", "dispatch", "device_wait",
              "log", "checkpoint"}


def _trainer(tmp_path, K, steps=3, log_every=1):
    arch, model = tiny_model("vit-cifar10")
    cfg = TrainConfig(arch=arch.name, steps=steps, log_every=log_every,
                      ckpt_every=1, ckpt_dir=str(tmp_path), ckpt_async=False,
                      param_dtype="float32", compute_dtype="float32",
                      dp=DPConfig(algo="dpsgd_r", sampling="poisson",
                                  noise_multiplier=0.5, augmult=K),
                      optim=OptimConfig(lr=1e-3, total_steps=steps))
    return Trainer(model, cfg, SHAPE)


def _run(tr):
    return tr.run(tr.init_state(jax.random.PRNGKey(0)),
                  install_signals=False)


@pytest.mark.parametrize("K", [1, 2])
def test_every_step_records_each_span(tmp_path, K):
    tr = _trainer(tmp_path, K)
    _run(tr)
    rec = telemetry.RECORDER
    for step in range(3):
        spans = rec.spans(step)
        want = LOOP_SPANS | ({"augment"} if K > 1 else set())
        assert set(spans) == want, (step, spans)
        assert all(v >= 0 for v in spans.values())
        assert rec.counts(step).get("retries", 0) == 0


def test_rows_counters_match_the_mask(tmp_path):
    tr = _trainer(tmp_path, K=2)
    _run(tr)
    for step in range(3):
        counts = telemetry.RECORDER.counts(step)
        batch = tr.make_batch(step)          # (seed, step)-keyed: the same
        assert counts["rows"] == batch["images"].shape[0] == tr.capacity * 2
        assert counts["real_rows"] == int(np.sum(batch["mask"]))
        assert 0 < counts["real_rows"] < counts["rows"]


def test_retries_are_counted(tmp_path):
    arch, model = tiny_model("stablelm-3b")
    cfg = TrainConfig(steps=3, log_every=1, ckpt_every=10,
                      ckpt_dir=str(tmp_path),
                      dp=DPConfig(algo="dpsgd_r", noise_multiplier=0.5),
                      optim=OptimConfig(lr=1e-3, total_steps=3))
    tr = Trainer(model, cfg, ShapeConfig("tiny", 32, 8, "train"),
                 inject_failure_at=1)
    _run(tr)
    assert [telemetry.RECORDER.counts(s).get("retries", 0)
            for s in range(3)] == [0, 1, 0]


def test_records_stay_bounded():
    rec = telemetry.Recorder(max_steps=8)
    for step in range(20):
        rec.start(step)
        with rec.span("sample", step):
            pass
        rec.count("rows", step, 4)
    assert rec.steps() == list(range(12, 20))
    assert rec.spans(3) == {} and rec.counts(19) == {"rows": 4}
    rec.start(19)                              # a re-run of the step
    assert rec.counts(19) == {}
    assert telemetry.RECORDER.max_steps == telemetry.MAX_STEPS == 4096


def test_summary_means_and_sums():
    rec = telemetry.Recorder()
    for step, (s, n) in enumerate([(0.002, 3), (0.004, 5)]):
        rec._record(step)[0]["sample"] = s
        rec.count("rows", step, n)
    rec._record(1)[0]["augment"] = 0.010
    got = rec.summary(range(0, 2))
    assert got["sample_ms"] == pytest.approx(3.0)
    assert got["augment_ms"] == pytest.approx(5.0)   # per recorded step
    assert got["rows"] == 8
    assert rec.mean_ms("transfer", range(2)) is None


def test_history_records_carry_span_means(tmp_path, capsys):
    tr = _trainer(tmp_path, K=2, steps=4, log_every=2)
    _run(tr)
    assert [h["step"] for h in tr.history] == [1, 3]
    rec = telemetry.RECORDER
    for h, steps in zip(tr.history, ([0, 1], [2, 3])):
        for name in ("sample", "augment", "transfer", "dispatch",
                     "device_wait"):
            mean = 1e3 * sum(rec.spans(s)[name] for s in steps) / 2
            assert h[f"{name}_ms"] == pytest.approx(mean)
        assert h["rows"] == sum(rec.counts(s)["rows"] for s in steps)
        assert h["real_rows"] == sum(rec.counts(s)["real_rows"]
                                     for s in steps)
    assert " host " in capsys.readouterr().out


def test_profiler_trace_carries_train_events(tmp_path):
    tr = _trainer(tmp_path / "ckpt", K=2, steps=2)
    with jax.profiler.trace(str(tmp_path / "trace")):
        _run(tr)
    xplane = sorted(Path(tmp_path / "trace").rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    names = [e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events]
    got = {n for n in names if n.startswith(telemetry.PREFIX)}
    assert {f"train.{s}" for s in LOOP_SPANS | {"augment"}} <= got
    assert names.count("train") == 2          # one step marker per step
    assert not [n for n in names if n.startswith("bench.")]


@pytest.mark.parametrize("strategy", ["materialize", "gram", "fused"])
def test_dp_step_names_its_device_work(strategy):
    """The lowered step's op metadata carries each site's norm scope (with
    the resolved rule), the clipping, the noise and the optimizer update."""
    arch, model = tiny_model("stablelm-3b")
    cfg = TrainConfig(dp=DPConfig(algo="dpsgd_r", noise_multiplier=1.0,
                                  norm_strategy=strategy),
                      optim=OptimConfig(name="sgd", lr=1e-2))
    tr = Trainer(model, cfg, ShapeConfig("tiny", 16, 2, "train"),
                 jit_step=False)
    state = tr.init_state(jax.random.PRNGKey(0))
    batch = tr.shard_batch(tr.make_batch(0))
    step = make_train_step(model, cfg)
    text = jax.jit(step).lower(state, batch, jax.random.PRNGKey(1)).as_text(
        debug_info=True)
    for scope in (f"dp_norm.dense.{strategy}", "dp_norm.embed.segment_sum",
                  "dp_norm.tap.direct", "dp_clip", "dp_noise",
                  "optim_update"):
        assert scope in text, scope
