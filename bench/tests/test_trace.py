"""The trace reduction, on hand-made intervals and on a small trace
recorded on one TPU v5 lite by the harness (``data/``: a traced window of
four loop periods of a two-layer, d_model 128 ViT under DP-SGD(R) with
fused norms on the kernels, expected batch 32, augmult 2; and the
custom-call lines of its compiled step's HLO)."""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"

HLO_LINE = (
    '  %dense_bwd_norm.1 = (bf16[16,256,256]{2,1,0:T(8,128)(2,1)}, '
    'f32[16,1,128]{2,1,0:T(1,128)S(1)}) custom-call(%x.1, %gy.1, %w.1), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[16,256,256]{2,1,0}, bf16[16,256,1024]{2,1,0}, '
    'bf16[1,256,1024]{2,1,0}}, frontend_attributes={kernel_metadata={}}, '
    'backend_config={"custom_call_config":{"body":"BODY"}}')


def test_union_gaps_and_tags():
    merged = T.union([(5, 6), (0, 2), (1, 3)])
    assert merged == [(0, 3), (5, 6)]
    assert T.gaps(merged, 0, 10) == [(3, 5), (6, 10)]
    assert T.clip([(0, 3), (5, 6)], 2, 5.5) == [(2, 3), (5, 5.5)]
    spans = [("batch_build", 2.5, 4.5), ("transfer", 4.5, 5.0)]
    assert T.tag((3, 5), spans) == "batch_build"
    assert T.tag((6, 10), spans) == "other"


def test_custom_calls_from_hlo():
    import base64
    body = base64.b64encode(b"\x00func\x00_fused_kernel\x00").decode()
    cc = T.custom_calls(HLO_LINE.replace("BODY", body))
    assert cc == {"dense_bwd_norm.1": {
        "kernel": "_fused_kernel",
        "operands": [("bf16", (16, 256, 256)), ("bf16", (16, 256, 1024)),
                     ("bf16", (1, 256, 1024))],
        "results": [("bf16", (16, 256, 256)), ("f32", (16, 1, 128))]}}


@pytest.fixture(scope="module")
def recorded():
    xplane, hlo = DATA / "trace.xplane.pb", DATA / "step.hlo.txt"
    if not xplane.exists():
        pytest.skip("no recorded trace")
    return T.reduce(xplane, hlo.read_text())


def test_recorded_trace(recorded):
    r = recorded
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    idle = sum(s for _, s in r.gap_list)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert {n for n, _ in r.gap_list} <= {
        "batch_build", "transfer", "dispatch", "device_wait", "bookkeeping",
        "other"}
    ops = r.top_ops(10)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(r.ops.values()) >= r.busy_s
    fused = r.custom_seconds("_fused_kernel")
    assert 0 < fused <= r.custom_seconds() < r.busy_s
    kernels = {v["kernel"] for v in r.kernels.values()}
    assert "_fused_kernel" in kernels
