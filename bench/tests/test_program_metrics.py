"""The readers of the program's own spans and of the named norm kernels,
on hand-made records and trace reductions."""
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace as T
from repro.train import telemetry

FIRST = harness.FIRST_STEPS + harness.WARM_STEPS


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "bench_metric")


@pytest.fixture
def recorded():
    """Steps before, inside and after a three-step window: only the
    window's steps may count."""
    rec = telemetry.RECORDER
    rec.clear()
    for step in range(FIRST - 2, FIRST + 5):
        rec.start(step)
        inside = FIRST <= step < FIRST + 3
        spans = rec._record(step)[0]
        spans["sample"] = 0.010 * (step - FIRST + 1) if inside else 9.0
        spans["augment"] = 0.200 if inside else 9.0
    yield SimpleNamespace(steps=3)
    rec.clear()


def test_host_sample_ms(recorded):
    assert _reader("host_sample_ms").read(recorded) == pytest.approx(20.0)


def test_host_augment_ms(recorded):
    assert _reader("host_augment_ms").read(recorded) == pytest.approx(200.0)


def test_host_augment_ms_without_augmult(recorded):
    for step in telemetry.RECORDER.steps():
        telemetry.RECORDER._record(step)[0].pop("augment")
    assert _reader("host_augment_ms").read(recorded) is None


def _reduction(calls, kernels):
    return T.Reduction(window_s=2.0, busy_s=1.0, ops={}, calls=calls,
                       gap_list=[], n_devices=1,
                       kernels={k: {"kernel": "kernel", "operands": [],
                                    "results": []} for k in kernels})


def test_norm_kernel_share():
    """The three norm kernels count, by instruction stem; other kernels,
    and ops that are not custom calls of the step, do not."""
    calls = [("gram_norm_kernel.3", 0.10), ("gram_norm_kernel", 0.05),
             ("_fused_kernel.12", 0.20), ("pegrad_norm_kernel.1", 0.01),
             ("flash_fwd_kernel.2", 0.30), ("dgrad_kernel.4", 0.07),
             ("gram_norm_kernel.9", 0.40)]            # not in the HLO
    kernels = [n for n, _ in calls[:-1]]
    ctx = SimpleNamespace(trace=_reduction(calls, kernels))
    got = _reader("norm_kernel_share").read(ctx)
    assert got == pytest.approx(100.0 * 0.36)


def test_norm_kernel_share_silent_without_named_kernels():
    """The instruction names a program gives without ``name=`` on its
    kernels read nothing; so does an untraced run."""
    calls = [("dense_bwd_norm.1", 0.2), ("transpose_jvp_jit_gram_norm___.2",
                                         0.1)]
    ctx = SimpleNamespace(trace=_reduction(calls, [n for n, _ in calls]))
    assert _reader("norm_kernel_share").read(ctx) is None
    assert _reader("norm_kernel_share").read(
        SimpleNamespace(trace=None)) is None
