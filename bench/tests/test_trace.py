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


# one device plane's ops, in ms: an all-reduce half covered by a fusion, an
# asynchronous all-gather whose start is partly and whose done is wholly
# covered, two collectives that overlap only each other, and ops whose
# names only resemble a collective's
PLANE = [("fusion.1", 0, 10), ("all-reduce.1", 5, 15),
         ("all-gather-start.2", 20, 22), ("fusion.3", 21, 30),
         ("all-gather-done.2", 22, 24),
         ("collective-permute-done.4", 40, 41), ("reduce-scatter.5", 40, 42),
         ("reduce.7", 50, 51), ("scatter.8", 52, 53)]


def test_collective_times_exact():
    # collectives: [5, 15) + [20, 24) + [40, 42) = 16; covered by other ops:
    # [5, 10) + [21, 24) = 8
    assert T.collective_times(PLANE) == (16, 8)
    assert T.collective_times([e for e in PLANE if "." in e[0]
                               and not T.is_collective(e[0])]) == (0, 0)
    assert [n for n, *_ in PLANE if T.is_collective(n)] == [
        "all-reduce.1", "all-gather-start.2", "all-gather-done.2",
        "collective-permute-done.4", "reduce-scatter.5"]


def test_reduce_averages_collectives_over_planes(monkeypatch):
    """Through ``reduce``: two device planes, one with the collectives
    above and one with none, in a 100 ms window."""
    from types import SimpleNamespace as NS

    ms = 1_000_000

    def ev(name, s, e):
        return NS(name=f"%{name} = f32[8] op()", start_ns=s * ms,
                  duration_ns=(e - s) * ms)

    def line(name, events):
        return NS(name=name, events=events)

    planes = [
        NS(name="/host:CPU", lines=[line("main", [
            NS(name="bench.window", start_ns=0, duration_ns=100 * ms)])]),
        NS(name="/device:TPU:0",
           lines=[line("XLA Ops", [ev(*e) for e in PLANE])]),
        NS(name="/device:TPU:1",
           lines=[line("XLA Ops", [ev("fusion.1", 0, 30)])])]
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: NS(planes=planes)))
    r = T.reduce("unused.xplane.pb")
    assert r.n_devices == 2 and r.window_s == pytest.approx(0.1, rel=1e-12)
    assert r.collective_s == pytest.approx(0.016 / 2, rel=1e-12)
    assert r.exposed_collective_s == pytest.approx(0.008 / 2, rel=1e-12)
    # busy: plane 0 [0, 15) + [20, 30) + [40, 42) + [50, 51) + [52, 53),
    # plane 1 [0, 30): as before
    assert r.busy_s == pytest.approx((0.029 + 0.030) / 2, rel=1e-12)


def test_one_chip_reads_no_collectives(recorded):
    assert recorded.collective_s == 0 and recorded.exposed_collective_s == 0
