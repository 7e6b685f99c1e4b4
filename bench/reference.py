"""Plain reference of one DP-SGD training step, and its lower-precision
control.

Given a configuration's ``row_losses`` (its ``<config>.ref.py``), this
computes what the timed path should: per-example gradients of the mean
over an example's augmult views, materialised in blocks by ``vmap``; their
norms; clipping to C; the clipped sum; Gaussian noise; the normaliser; and
SGD with momentum, with each parameter stored back in the dtype the
configuration keeps it in.  Under ``algo = "sgd"`` it is the plain mean
gradient over the real rows, with no clipping and no noise.

The noise is the one the configuration's DP step draws: for step s, key
``fold_in(PRNGKey(seed), s)`` split into one key per parameter leaf in
tree order, each leaf ``normal(key, shape, float32)`` scaled by sigma*C.
The compared gradient and parameter change are taken with that draw's
share subtracted (``noise_terms``, ``noise_free_norms``), on the
program's side and on the reference's alike: at a small batch the noise
is far larger than the clipped sum, and the norms of the noisy tensors
would agree however the clipped sum was computed.

``Mm`` carries the precision: float32 at HIGHEST (the reference) or
float8 e4m3 operands with one scale per tensor and float32 accumulation
(the control: the nearest precision below the configuration's bfloat16).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Fake-quantise to float8 e4m3 with one amax scale per tensor;
    straight-through in the backward pass."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


@dataclasses.dataclass(frozen=True)
class Mm:
    fp8: bool = False

    def _q(self, x):
        return _fp8(x) if self.fp8 else x

    def dot(self, a, b):
        return jnp.matmul(self._q(a), self._q(b), precision=HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST)


def noise_tree(like, seed: int, step: int, std: float):
    leaves, tree = jax.tree.flatten(like)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        std * jax.random.normal(k, l.shape, F32) for k, l in zip(keys, leaves)])


def noise_terms(mix: dict, steps: int) -> list:
    """[(step, coefficient)]: the noise a tensor carries is the sum of
    coefficient x that step's unit draw.  ``steps = 1``: the first
    gradient as the optimizer gets it, sigma*C/(q*N) x the draw.
    ``steps = n``: the parameters' change after n steps of SGD with
    momentum beta and rate lr, which adds -lr*sigma*C/(q*N) x (1 + beta +
    ... + beta^(n-1-s)) x step s's draw.  Empty for plain SGD."""
    if mix["algo"] == "sgd":
        return []
    c = float(mix["noise_multiplier"]) * float(mix["clip_norm"]) / float(
        mix["batch"])
    if steps == 1:
        return [(0, c)]
    lr, beta = float(mix["optimizer"]["lr"]), float(mix["optimizer"]["momentum"])
    return [(s, -lr * c * sum(beta ** j for j in range(steps - s)))
            for s in range(steps)]


def noise_free_norms(tree, seed: int, terms: list, base=None) -> np.ndarray:
    """Per-leaf norms of ``tree`` (less ``base``, where given) with the
    noise of ``terms`` subtracted, leaf by leaf on the device: the draws
    are made inside one jitted call and never held whole."""
    leaves = jax.tree.leaves(tree)
    bases = jax.tree.leaves(base) if base is not None else [None] * len(leaves)

    @jax.jit
    def f(leaves, bases):
        keys = [jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                    s), len(leaves))
                for s, _ in terms]
        out = []
        for i, (l, b) in enumerate(zip(leaves, bases)):
            x = l.astype(F32)
            if b is not None:
                x = x - b.astype(F32)
            for (_, c), ks in zip(terms, keys):
                x = x - c * jax.random.normal(ks[i], l.shape, F32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(x))))
        return jnp.stack(out)

    return np.asarray(f(leaves, bases), np.float64)


class Step:
    """The reference DP step of one configuration and mix."""

    def __init__(self, row_losses, conf, mix, mm: Mm, block: int):
        self.mix, self.K, self.block = mix, mix["augmult"], block
        self.private = mix["algo"] != "sgd"
        K = self.K

        def rows(p, b):
            return row_losses(p, b, conf, mm)

        def per_example(p, ex, m):       # ex leaves: (K, ...)
            l = rows(p, ex)              # a padded example seeds a zero
            return m * jnp.mean(l), l    # cotangent: no gradient at all

        C = float(mix["clip_norm"])

        @jax.jit
        def dp_block(p, blk, mask):
            ex = jax.tree.map(lambda a: a.reshape((-1, K) + a.shape[1:]), blk)
            g, l = jax.vmap(jax.grad(per_example, has_aux=True),
                            in_axes=(None, 0, 0))(p, ex, mask)
            nsq = sum(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
                      for x in jax.tree.leaves(g))
            c = C / jnp.maximum(jnp.sqrt(nsq), C)
            s = jax.tree.map(lambda x: jnp.tensordot(c, x, 1,
                                                     precision=HIGHEST), g)
            return s, nsq, l.reshape(-1)

        @jax.jit
        def sgd_block(p, blk, mask_rows):
            def total(p_):
                l = rows(p_, blk)
                return jnp.sum(l * mask_rows), l
            g, l = jax.grad(total, has_aux=True)(p)
            return g, jnp.zeros((0,), F32), l

        self._block_fn = dp_block if self.private else sgd_block

    def grads(self, params, batch, seed: int, step: int):
        """(gradient as the optimizer gets it, its noise-free part,
        per-step readings)."""
        mix, K = self.mix, self.K
        R = len(next(iter(batch.values())))
        mask_rows = (batch["mask"].astype(np.float32) if "mask" in batch
                     else np.ones((R,), np.float32))
        data = {k: v for k, v in batch.items() if k != "mask"}
        rows_per = self.block * K
        acc, nsq, losses = None, [], []
        for lo in range(0, R, rows_per):
            blk = {k: jnp.asarray(v[lo:lo + rows_per]) for k, v in data.items()}
            m = mask_rows[lo:lo + rows_per]
            if self.private:
                m = m.reshape(-1, K)[:, 0]
            s, n, l = self._block_fn(params, blk, jnp.asarray(m))
            acc = s if acc is None else jax.tree.map(jnp.add, acc, s)
            nsq.append(np.asarray(n))
            losses.append(np.asarray(l))
        losses = np.concatenate(losses)
        loss = float(np.sum(losses * mask_rows) / max(mask_rows.sum(), 1.0))
        read = {"loss": loss}
        if self.private:
            me = mask_rows.reshape(-1, K)[:, 0]
            n = np.sqrt(np.maximum(np.concatenate(nsq), 0.0))
            read["norm_mean"] = float(np.sum(n * me) / max(me.sum(), 1.0))
            read["norm_max"] = float(np.max(n * me))
            denom = float(mix["batch"])
            clean = jax.tree.map(lambda a: a / denom, acc)
            std = float(mix["noise_multiplier"]) * float(mix["clip_norm"])
            noise = noise_tree(acc, seed, step, std)
            g = jax.tree.map(lambda a, z: (a + z) / denom, acc, noise)
        else:
            count = max(float(mask_rows.sum()), 1.0)
            clean = g = jax.tree.map(lambda a: a / count, acc)
        return g, clean, read


def momentum_update(params, mom, g, store, lr: float, beta: float):
    """SGD with momentum; each leaf is stored back in its own dtype."""
    mom = jax.tree.map(lambda m, x: beta * m + x, mom, g)
    params = jax.tree.map(lambda p, m, d: (p - lr * m).astype(d).astype(F32),
                          params, mom, store)
    return params, mom


def run(row_losses, conf, mix, params0, batches, seed: int, mm: Mm,
        block: int, steps: int = 3, fault: str | None = None) -> dict:
    """Three reference steps from ``params0`` (the benchmark's weights)
    over the rebuilt ``batches``.  The first gradient and the change are
    read as the harness reads the program's, with the configuration's
    noise subtracted.  ``fault`` plants a fault, read for the upper
    limits only: ``"half"`` leaves out half of the examples and takes the
    mean over the rest; ``"halfsum"`` leaves them out and keeps the
    configuration's normaliser."""
    opt = mix["optimizer"]
    assert opt["name"] == "sgd" and opt["schedule"] == "constant", opt
    store = jax.tree.map(lambda p: p.dtype, params0)
    p = jax.tree.map(lambda a: a.astype(F32), params0)
    mom = jax.tree.map(jnp.zeros_like, p)
    step_fn = Step(row_losses, conf, mix if fault != "half" else
                   dict(mix, batch=mix["batch"] / 2), mm, block)
    out = {"loss": [], "norm_mean": [], "norm_max": []}
    for s in range(steps):
        b = batches[s]
        if fault in ("half", "halfsum"):
            b = _half(b, mix["augmult"], keep_rows=fault == "halfsum")
        g, clean, read = step_fn.grads(p, b, seed, s)
        for k, v in read.items():
            out[k].append(v)
        if s == 0:
            out["grad_norms"] = noise_free_norms(g, seed, noise_terms(mix, 1))
            out["clean_norms"] = noise_free_norms(clean, seed, [])
        p, mom = momentum_update(p, mom, g, store, opt["lr"], opt["momentum"])
        del g, clean
    out["change_norms"] = noise_free_norms(p, seed, noise_terms(mix, steps),
                                           base=params0)
    return out


def _half(batch: dict, K: int, keep_rows: bool = False) -> dict:
    """Leave out the second half of the batch's real examples: masked out
    under Poisson sampling; under fixed sampling cut off, or masked out
    (``keep_rows``) so that the mean keeps the full batch's count."""
    if "mask" not in batch and keep_rows:
        R = len(next(iter(batch.values())))
        m = np.ones((R,), bool)
        m[(R // K // 2) * K:] = False
        return dict(batch, mask=m)
    if "mask" in batch:
        m = batch["mask"].copy()
        real = np.flatnonzero(m.reshape(-1, K)[:, 0])
        for b in real[(len(real) + 1) // 2:]:
            m[b * K:(b + 1) * K] = False
        return dict(batch, mask=m)
    R = len(next(iter(batch.values())))
    keep = (R // K // 2) * K
    return {k: v[:keep] for k, v in batch.items()}
