"""The benchmark's own copy of the training feed's generator.

The program draws every batch from (seed, step) through counter-based
Philox streams (``repro/data/pipeline.py``).  This is a copy of that
generator, kept with the benchmark so that the correctness check can
rebuild each batch independently and compare it with what the timed feed
produced: the Poisson sample and its mask, the augmult views, the labels
and the tokens.  The program's copy is listed for deletion in PERF.md.
"""
from __future__ import annotations

import numpy as np

EXAMPLE_STREAM_STEP = 0x0DA7A5E7   # index-keyed example content
AUG_STREAM_BASE = 0xA6000000       # augmentation draws
POISSON_STREAM = 0xB0              # the Poisson draw
DATASET_SIZE = 1_000_000           # the synthetic source's nominal N


def rng(seed: int, step: int, stream: int) -> np.random.Generator:
    k0 = (seed * 0x9E3779B97F4A7C15 + step) & 0xFFFFFFFFFFFFFFFF
    key = np.array([k0, stream & 0xFFFFFFFFFFFFFFFF], dtype=np.uint64)
    return np.random.Generator(np.random.Philox(key=key))


def poisson_capacity(expected: int, q: float, z: float = 6.0) -> int:
    return int(np.ceil(expected + z * np.sqrt(expected * max(1.0 - q, 0.0))))


def poisson_indices(seed: int, step: int, n: int, q: float) -> np.ndarray:
    g = rng(seed, step, POISSON_STREAM)
    size = int(g.binomial(n, q))
    return np.sort(g.choice(n, size=size, replace=False).astype(np.int64))


def image_example(seed: int, step: int, stream: int, size: int,
                  channels: int, n_classes: int):
    g = rng(seed, step, stream)
    label = np.int32(g.integers(0, n_classes))
    img = g.standard_normal((size, size, channels)).astype(np.float32)
    return img, label


def image_batch(seed: int, step: int, mix: dict, size: int, channels: int,
                n_classes: int) -> dict:
    """The step's image batch, (B·K, ...) rows b-major/k-minor, with the
    Poisson ``mask`` when the mix samples so."""
    if mix["sampling"] == "poisson":
        q = mix["batch"] / DATASET_SIZE
        cap = poisson_capacity(mix["batch"], q)
        idx = poisson_indices(seed, step, DATASET_SIZE, q)[:cap]
        keyed = [(EXAMPLE_STREAM_STEP, int(i) + 1) for i in idx]
    else:
        cap = mix["batch"]
        keyed = [(step, i + 1) for i in range(cap)]
    imgs = np.zeros((cap, size, size, channels), np.float32)
    labels = np.zeros((cap,), np.int32)
    for row, (st, stream) in enumerate(keyed):
        imgs[row], labels[row] = image_example(seed, st, stream, size,
                                               channels, n_classes)
    out = {"images": imgs, "labels": labels}
    if mix["sampling"] == "poisson":
        mask = np.zeros((cap,), np.bool_)
        mask[:len(keyed)] = True
        out["mask"] = mask
    return augment(out, mix["augmult"], seed, step)


def token_batch(seed: int, step: int, mix: dict, vocab: int) -> dict:
    """The step's fixed-size token batch, (B, T + 1) int32."""
    assert mix["sampling"] == "fixed" and mix["augmult"] == 1, mix
    n, T = mix["batch"], mix["seq_len"]
    tok = np.empty((n, T + 1), np.int32)
    for i in range(n):
        tok[i] = rng(seed, step, i + 1).integers(0, vocab, T + 1, np.int64)
    return {"tokens": tok}


def augment(batch: dict, k: int, seed: int, step: int, pad: int = 4) -> dict:
    """K views per example: view 0 as is, views k >= 1 a pad-``pad`` random
    crop with a random horizontal flip; other leaves repeat over K."""
    if k <= 1:
        return batch
    out = {}
    for name, v in batch.items():
        if name != "images":
            out[name] = np.repeat(v, k, axis=0)
            continue
        B, H, W, _ = v.shape
        views = np.empty((B * k,) + v.shape[1:], v.dtype)
        padded = np.pad(v, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        for b in range(B):
            views[b * k] = v[b]
            for kk in range(1, k):
                g = rng(seed, step, AUG_STREAM_BASE + b * k + kk)
                dy, dx = (int(x) for x in g.integers(0, 2 * pad + 1, 2))
                view = padded[b, dy:dy + H, dx:dx + W]
                if g.integers(0, 2):
                    view = view[:, ::-1]
                views[b * k + kk] = view
        out[name] = views
    return out
