"""The numbers that decide ``correct``, and their limits.

Each is a gap between what the timed path produced and what the plain
reference gives from the same seed:

* ``batch_mismatch``: elements of the first three batches the feed built
  (images or tokens, labels, Poisson mask, augmult views) that differ from
  the benchmark's own rebuild.  Exact: limit 0.
* ``loss_gap``: the largest relative gap of the first three steps' loss.
* ``norm_gap``: the largest relative gap of the mean and of the largest
  per-example gradient norm, over the first three steps (DP mixes only).
* ``grad_gap``: by the worst leaf, the gap between the norms of the first
  gradient as the optimizer got it (from its momentum after one step) and
  the reference's, over the larger of that leaf's reference norm and the
  median leaf's.  Under DP the noise of the configuration's own draw is
  subtracted on both sides first (``reference.noise_terms``), so that
  what is compared is the clipped sum over the expected batch: at batch 8
  the noise's norm is thousands of times the clipped sum's.
* ``change_gap``: the same for the parameters' change over three steps,
  as stored in their own dtype, with the noise of the three draws
  subtracted likewise, leaving out leaves whose noise-free reference
  gradient is under a thousandth of the median leaf's (they move by
  round-off alone).

Limits live in ``limits/<workload>.json``; PERF.md gives the readings each
was set from.
"""
from __future__ import annotations

import numpy as np

EXCLUDE_BELOW = 1e-3


def batch_mismatch(got: list, want: list) -> int:
    bad = 0
    for g, w in zip(got, want):
        if set(g) != set(w):
            return 10**12
        for k in w:
            a, b = np.asarray(g[k]), np.asarray(w[k])
            if a.shape != b.shape:
                bad += b.size
            else:
                bad += int(np.count_nonzero(a != b))
    return bad + 10**12 * abs(len(got) - len(want))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def leaf_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    den = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(den, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap)) if gap.size else float("nan")


def numbers(prog: dict, ref: dict, private: bool) -> dict:
    """The compared numbers from the program's and the reference's
    readings (see ``harness`` and ``reference.run`` for the keys)."""
    out = {"batch_mismatch": float(prog["batch_mismatch"]),
           "loss_gap": rel(prog["loss"], ref["loss"])}
    if private:
        out["norm_gap"] = max(rel(prog["norm_mean"], ref["norm_mean"]),
                              rel(prog["norm_max"], ref["norm_max"]))
    out["grad_gap"] = leaf_gap(prog["grad_norms"], ref["grad_norms"])
    clean = np.asarray(ref["clean_norms"])
    keep = clean >= EXCLUDE_BELOW * np.median(clean)
    out["change_gap"] = leaf_gap(prog["change_norms"], ref["change_norms"],
                                 keep)
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}).  A number with no limit, or
    one that is not finite, is not correct."""
    checks, ok = {}, True
    for k, v in nums.items():
        lim = limits.get(k)
        good = lim is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        checks[k] = {"value": v, "limit": lim}
    return ok, checks
