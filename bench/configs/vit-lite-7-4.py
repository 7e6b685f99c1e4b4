"""ViT-Lite-7/4 (Hassani et al. 2021) as the benchmark runs it, on the
program's ViT: the program's configuration, the benchmark's weights in the
program's parameter layout, the model FLOPs per example, and the feed's
batch rebuilt for the check.  The plain reference is beside this file
(``vit-lite-7-4.ref.py``); the program's departures from the paper's
model are listed in ``vit-lite-7-4.json``."""
from __future__ import annotations

from dataclasses import replace

import numpy as np

from bench import feed


def arch(conf: dict):
    """The registry's ViT ArchConfig with every size the file states."""
    from repro.configs import get_arch
    a = get_arch(conf["registry"])
    a = replace(a, n_layers=conf["n_layers"], d_model=conf["d_model"],
                n_heads=conf["n_heads"], n_kv_heads=conf["n_kv_heads"],
                head_dim=conf["head_dim"], d_ff=conf["d_ff"],
                mlp_act=conf["mlp_act"], norm_eps=conf["norm_eps"],
                vocab=conf["num_classes"],
                vit=replace(a.vit, image_size=conf["image_size"],
                            in_channels=conf["in_channels"],
                            patch_size=conf["patch_size"],
                            num_classes=conf["num_classes"]))
    assert a.family == "vit" and a.rotary_pct == 0.0 and not a.qk_norm, a
    return a


def layout(conf: dict, mix: dict) -> dict:
    pd = mix["param_dtype"]
    """Parameter tree of (shape, dtype name, init std or 'ones'/'zeros')."""
    d, f, p, c = (conf["d_model"], conf["d_ff"], conf["patch_size"],
                  conf["in_channels"])
    h = conf["n_heads"] * conf["head_dim"]
    mat = lambda i, o: ((i, o), pd, 1.0 / np.sqrt(i))
    vec = lambda n, v: ((n,), "float32", v)
    block = {"ln1": vec(d, "ones"),
             "attn": {"wq": mat(d, h), "wk": mat(d, h), "wv": mat(d, h),
                      "wo": mat(h, d)},
             "ln2": vec(d, "ones"),
             "mlp": {"w1": mat(d, f), "w2": mat(f, d)}}
    grid = conf["image_size"] // p
    return {
        "patch": {"w": ((p, p, c, d), pd, 1.0 / np.sqrt(p * p * c)),
                  "b": vec(d, "zeros")},
        "pos": ((grid * grid, d), "float32", "zeros"),
        "blocks": [block for _ in range(conf["n_layers"])],
        "final_norm": vec(d, "ones"),
        "head": {"w": mat(d, conf["num_classes"]),
                 "b": vec(conf["num_classes"], "zeros")},
    }


def flops_per_example(conf: dict, mix: dict) -> float:
    """Model FLOPs of forward + backward of one example (K augmult views):
    6 x matmul parameters x the tokens each meets (the head meets the one
    pooled vector of a view), plus 12 x layers x d_model x T per token for
    attention (PaLM, appendix B).  No recompute, no DP side channel."""
    d, f, L = conf["d_model"], conf["d_ff"], conf["n_layers"]
    h = conf["n_heads"] * conf["head_dim"]
    p, c = conf["patch_size"], conf["in_channels"]
    T = (conf["image_size"] // p) ** 2
    per_token = p * p * c * d + L * (4 * d * h + 2 * d * f)
    head = d * conf["num_classes"]
    view = 6 * (per_token * T + head) + 12 * L * h * T * T
    return float(view * mix["augmult"])


def batch(conf: dict, mix: dict, seed: int, step: int) -> dict:
    return feed.image_batch(seed, step, mix, conf["image_size"],
                            conf["in_channels"], conf["num_classes"])
