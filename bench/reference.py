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
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

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


def noise_free_norms(tree, seed: int, terms: list, base=None,
                     denom: float | None = None) -> np.ndarray:
    """Per-leaf norms of ``tree`` (less ``base``, where given; over
    ``denom``, where given) with the noise of ``terms`` subtracted, leaf
    by leaf on the device: the draws are made inside one jitted call and
    never held whole."""
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
            if denom is not None:
                x = x / denom
            for (_, c), ks in zip(terms, keys):
                x = x - c * jax.random.normal(ks[i], l.shape, F32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(x))))
        return jnp.stack(out)

    return np.asarray(f(leaves, bases), np.float64)


def shardings(abstract, devices):
    """Where the reference keeps each parameter-sized float32 tree: every
    leaf split over all of ``devices`` on its largest dimension that their
    number divides, replicated where none does.  The rule is the
    reference's own, not the program's, and spreads every leaf over every
    chip, whatever the program's mesh leaves whole."""
    mesh = Mesh(np.asarray(devices), ("ref",), axis_types=(AxisType.Auto,))
    n = len(devices)

    def one(leaf):
        spec = [None] * len(leaf.shape)
        fits = [i for i, d in enumerate(leaf.shape) if d % n == 0]
        if fits:
            spec[max(fits, key=lambda i: leaf.shape[i])] = "ref"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, abstract)


class Step:
    """The reference DP step of one configuration and mix, over trees
    placed by ``sh`` (a tree of shardings of the parameters' structure).

    At most three parameter-sized float32 trees are alive at once: the
    parameters, one running buffer and, inside a block's call, that
    block's per-example gradients (or, inside ``update``, the parameters'
    next values).  The buffer holds B x the momentum, B the step's
    normaliser: a step scales it by beta (``rescale``), adds each block's
    clipped per-example gradients (``block_fn``), then the step's noise
    (``add_noise``), and ``update`` applies it."""

    def __init__(self, row_losses, conf, mix, mm: Mm, block: int, sh,
                 store: tuple):
        self.mix, self.K, self.block = mix, mix["augmult"], block
        self.private = mix["algo"] != "sgd"
        K = self.K
        opt = mix["optimizer"]
        lr = float(opt["lr"])
        keep = lambda t: jax.lax.with_sharding_constraint(t, sh)
        # a block's sum is added to the buffer in a fusion of its own: the
        # TPU's compiler would fold the add into the HIGHEST dot that makes
        # the sum, and there the buffer does not keep its float32 value
        apart = jax.lax.optimization_barrier

        def rows(p, b):
            return row_losses(p, b, conf, mm)

        def per_example(p, ex, m):       # ex leaves: (K, ...)
            l = rows(p, ex)              # a padded example seeds a zero
            return m * jnp.mean(l), l    # cotangent: no gradient at all

        C = float(mix["clip_norm"])

        @partial(jax.jit, donate_argnums=1)
        def dp_block(p, buf, blk, mask):
            ex = jax.tree.map(lambda a: a.reshape((-1, K) + a.shape[1:]), blk)
            g, l = jax.vmap(jax.grad(per_example, has_aux=True),
                            in_axes=(None, 0, 0))(p, ex, mask)
            g = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(s.mesh, P(None, *s.spec))), g, sh)
            nsq = sum(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
                      for x in jax.tree.leaves(g))
            c = C / jnp.maximum(jnp.sqrt(nsq), C)
            buf = jax.tree.map(
                lambda a, x: a + apart(jnp.tensordot(c, x, 1,
                                                     precision=HIGHEST)),
                buf, g)
            return keep(buf), nsq, l.reshape(-1)

        @partial(jax.jit, donate_argnums=1)
        def sgd_block(p, buf, blk, mask_rows):
            def total(p_):
                l = rows(p_, blk)
                return jnp.sum(l * mask_rows), l
            g, l = jax.grad(total, has_aux=True)(p)
            buf = jax.tree.map(lambda a, x: a + apart(x), buf, g)
            return keep(buf), jnp.zeros((0,), F32), l

        self.block_fn = dp_block if self.private else sgd_block

        std = float(mix["noise_multiplier"]) * C

        @partial(jax.jit, donate_argnums=0)
        def add_noise(buf, key):
            leaves, tree = jax.tree.flatten(buf)
            keys = jax.random.split(key, len(leaves))
            return keep(jax.tree.unflatten(tree, [
                l + std * jax.random.normal(k, l.shape, F32)
                for k, l in zip(keys, leaves)]))

        @partial(jax.jit, donate_argnums=0)
        def rescale(buf, f):
            return keep(jax.tree.map(lambda a: a * f, buf))

        def update(p, buf, denom):
            """SGD with momentum, the momentum being buf / denom; each
            leaf is stored in its own dtype (``store``, in leaf order).
            Op by op, leaf by leaf: fused into one executable, the TPU's
            compiler may keep the wider value of the rounded leaf."""
            leaves, tree = jax.tree.flatten(p)
            return jax.tree.unflatten(tree, [
                (a - lr * (m / denom)).astype(d).astype(F32)
                for a, m, d in zip(leaves, jax.tree.leaves(buf), store)])

        self.add_noise, self.rescale, self.update = add_noise, rescale, update
        self.zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                             out_shardings=sh)

    def normaliser(self, batch) -> float:
        """B: the expected batch under DP (the configuration's), the real
        rows under plain SGD."""
        if self.private:
            return float(self.mix["batch"])
        if "mask" not in batch:
            return float(len(next(iter(batch.values()))))
        return max(float(np.sum(batch["mask"])), 1.0)

    def accumulate(self, params, buf, batch):
        """``buf`` plus the step's clipped per-example gradients (the
        plain gradient's sum under SGD), and the per-step readings."""
        K = self.K
        R = len(next(iter(batch.values())))
        mask_rows = (batch["mask"].astype(np.float32) if "mask" in batch
                     else np.ones((R,), np.float32))
        data = {k: v for k, v in batch.items() if k != "mask"}
        rows_per = self.block * K
        nsq, losses = [], []
        for lo in range(0, R, rows_per):
            blk = {k: jnp.asarray(v[lo:lo + rows_per]) for k, v in data.items()}
            m = mask_rows[lo:lo + rows_per]
            if self.private:
                m = m.reshape(-1, K)[:, 0]
            buf, n, l = self.block_fn(params, buf, blk, jnp.asarray(m))
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
        return buf, read


def run(row_losses, conf, mix, draw, batches, seed: int, mm: Mm,
        block: int, steps: int = 3, fault: str | None = None) -> dict:
    """Three reference steps over the rebuilt ``batches`` from the
    benchmark's weights, which ``draw()`` returns where the reference
    keeps them (``shardings``); it is called again for the change.  The
    first gradient and the change are read as the harness reads the
    program's, with the configuration's noise subtracted.  ``fault``
    plants a fault, read for the upper limits only: ``"half"`` leaves
    out half of the examples and takes the mean over the rest;
    ``"halfsum"`` leaves them out and keeps the configuration's
    normaliser."""
    opt = mix["optimizer"]
    assert opt["name"] == "sgd" and opt["schedule"] == "constant", opt
    params0 = draw()
    store = tuple(l.dtype for l in jax.tree.leaves(params0))
    p = jax.tree.map(lambda a: a.astype(F32), params0)
    del params0
    sh = jax.tree.map(lambda a: a.sharding, p)
    step_fn = Step(row_losses, conf, mix if fault != "half" else
                   dict(mix, batch=mix["batch"] / 2), mm, block, sh, store)
    beta = float(opt["momentum"])
    out = {"loss": [], "norm_mean": [], "norm_max": []}
    buf, prev = step_fn.zeros(p), None
    for s in range(steps):
        b = batches[s]
        if fault in ("half", "halfsum"):
            b = _half(b, mix["augmult"], keep_rows=fault == "halfsum")
        denom = step_fn.normaliser(b)
        if prev is not None:            # buf = B x beta x last momentum
            buf = step_fn.rescale(buf, np.float32(beta * denom / prev))
        buf, read = step_fn.accumulate(p, buf, b)
        for k, v in read.items():
            out[k].append(v)
        if s == 0:                      # the momentum is zero: buf / B is
            out["clean_norms"] = noise_free_norms(   # the noise-free mean
                buf, seed, [], denom=denom)
        if step_fn.private:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), s)
            buf = step_fn.add_noise(buf, key)
        if s == 0:
            out["grad_norms"] = (noise_free_norms(
                buf, seed, noise_terms(mix, 1), denom=denom)
                if step_fn.private else out["clean_norms"])
        p = step_fn.update(p, buf, np.float32(denom))
        prev = denom
    del buf
    out["change_norms"] = noise_free_norms(p, seed, noise_terms(mix, steps),
                                           base=draw())
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
