"""The peaks table, the MFU FLOP formulas and the fused dense backward's
work, against values computed by hand."""
import json

import pytest

from bench import harness, peaks, work

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _cell(name):
    return harness.load_cell(SPEC, name)


def test_peaks_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def test_vit_flops_per_example():
    # per token: patch 4*4*3*256 = 12288, 7 layers x (4*256*256 + 2*256*512)
    # = 3670016; x 64 tokens = 235667456; head 256*10 = 2560 once per view.
    # 6 x 235670016 + attention 12*7*256*64*64 = 88080384 -> 1502100480
    # per view, x 4 views.
    c = _cell("vit.dp.pois1024.k4")
    assert c.cfg.flops_per_example(c.conf, c.mix) == 6_008_401_920


def test_phi3_flops_per_example():
    # per token: 2 x (4*3072^2 + 3*3072*8192) + 3072*32064 = 324993024;
    # 6 x that x 1024 + 12*2*3072*1024*1024 (attention)
    c = _cell("phi3.dp.s1024.b8")
    assert c.cfg.flops_per_example(c.conf, c.mix) == 2_074_066_550_784


def test_dense_bwd_norm_vit_mlp_in():
    # ViT MLP w1 site under augmult 4: 1216 examples x 256 rows, 256 -> 512.
    # dgrad 2*1216*256*256*512; per example the materialised rule
    # (2*256*256*512 + 2*256*512 = 67371008) beats Gram (100794368).
    f, b = work.dense_bwd_norm(1216, 256, 256, 512)
    assert f == 163_527_524_352
    # x and gx 2 x 1216*256*256*2, gy 1216*256*512*2, w 256*512*2, norms
    assert b == 637_801_216
    t, bound = work.least_time(f, b, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(f / 197e12)


def test_dense_bwd_norm_phi3_mlp_in():
    # Phi-3 w1 site: 8 sequences x 1024 tokens, 3072 -> 8192; per example
    # Gram (2*1024^2*11264 + 2*1024^2 = 23624417280) beats materialising.
    f, b = work.dense_bwd_norm(8, 1024, 3072, 8192)
    assert f == 601_312_198_656
    assert b == 285_212_704
    assert work.least_time(f, b, peaks.peaks("TPU v5 lite"))[1] == "compute"


def test_work_from_hlo_shapes():
    info = {"kernel": "_fused_kernel",
            "operands": [("bf16", (1216, 256, 256)), ("bf16", (1216, 256, 512)),
                         ("bf16", (1, 256, 512))],
            "results": [("bf16", (1216, 256, 256)), ("f32", (1216, 1, 128))]}
    assert work.from_call(info) == work.dense_bwd_norm(1216, 256, 256, 512)
