"""Algorithm 1 of the paper, as composable JAX gradient transformations.

``make_noisy_grad_fn(loss_fn, dp, grad_accum)`` returns

    fn(params, batch, key) -> (grads, metrics)

for ``dp.algo`` in:

* ``"sgd"``       — non-private baseline (paper §II-B): mean-loss gradient.
* ``"dpsgd"``     — vanilla DP-SGD (lines 15–25): per-example grads via
                    vmap(grad) under a scan over microbatches, explicit
                    norm/clip/reduce post-processing, Gaussian noise.
* ``"dpsgd_r"``   — reweighted DP-SGD(R) (lines 27–42, the paper's baseline):
                    pass 1 = per-example norms via the DPContext side-channel
                    (no per-example grad materialization); pass 2 = backprop
                    of the clip-reweighted loss; noise.
* ``"dpsgd_r1f"`` — single-forward DP-SGD(R): one vjp, two pullbacks.

Masked variable batches (Poisson subsampling, lines 15–17): a batch may
carry a ``"mask"`` key — ``(B,)`` bool/0-1 example-validity flags for a
right-padded fixed-capacity batch (data/pipeline.py ``poisson_batch_for``).
The mask is threaded by *seeding every backward pass with the masked
per-example loss cotangents*: padded rows receive an exactly-zero cotangent,
so their activation grads, per-example norms² (through ``DPContext``, every
``norms.py`` rule, and the Pallas kernel paths — 0-valued ``gy`` rows reduce
to exact 0), clip contributions and clipped-sum terms are all exact zeros.
A masked batch therefore produces the same update as the physically
compacted batch.  Without a ``"mask"`` key, all rows are real (fixed-size
mode) and nothing changes.

``grad_accum > 1`` scans the per-algorithm *clipped-sum* over microbatches
(per-example clipping is self-contained per microbatch, so accumulation is
exact); the mask is chunked alongside the data.  Noise is added once per
step, after the full-batch reduction — identical privacy accounting and
identical update to grad_accum=1.

``expected_batch_size``: the normalizer of the private update, counted in
*examples* (privacy units).  Defaults to the physical example count
(fixed-size mode); under Poisson sampling the trainer passes the
*expected* sample size q·N (Algorithm 1 line 24's lot size) — never the
realized draw, which would leak the sample size.

Augmentation multiplicity (``dp.augmult = K > 1``): every batch leaf
carries B·K rows — K augmented views of each example, b-major/k-minor
(view k of example b at row b·K + k; data/pipeline.py ``augment_expand``)
— and the ``"mask"`` leaf is broadcast over K (an example is present with
all its views or none).  The per-example gradient is the **mean over the
K views**, clipped once per example: the algos implement this by seeding
every backward pass with ``m/K``-scaled loss cotangents, so the pulled-
back parameter cotangent of example b is exactly its K-averaged gradient
and — through the augmult-aware site rules (core/sites.py, which fold the
K views into each rule's contraction axis) — the side-channel accumulator
holds ‖mean-over-K grad‖² per *example*, shape (B,).  ``augmult=1`` is
bit-identical to the single-view dataflow.  The clipped-sum contract is
therefore: ``losses`` stay per-row (B·K,), ``nsq`` is per-example (B,).

Adaptive clipping: a batch may carry a ``"clip_norm"`` leaf — a traced
scalar overriding ``dp.clip_norm`` (injected by ``make_noisy_grad_fn``
from the trainer's clip state; core/adaptive_clip.py).  ``split_clip``
below is the single place the override is resolved, so registered algos
stay free of adaptive-clip conditionals.

All four produce gradients in the same tree/dtype (f32), so the optimizer
is agnostic.  The three private algos produce *identical* updates for the
same (params, batch, key) — property-tested in tests/test_dp_core.py and,
under random masks, tests/test_dp_properties.py.

``dp.norm_strategy`` flows into the pass-1 ``DPContext`` untouched: the
side-channel algos (``dpsgd_r``/``dpsgd_r1f``) work identically under
``"materialize"``/``"gram"``/``"auto"`` and under ``"fused"``, where each
site's backward produces the activation gradient and the norm² in one
sweep (core/sites.py ``fused_bwd``; kernels/fused_bwd.py) instead of
rule-after-backward — identity across strategies is pinned in
tests/test_fused_norms.py.  ``"dpsgd"`` never consults the strategy (it
materializes per-example grads by construction).

Pipeline parallelism (``Model.pp_stages > 1``): the loss_fn the algos
differentiate may internally run its block stack on a stage-sliced,
microbatch-interleaved schedule (models/transformer.py
``_blocks_pipelined``).  This is transparent here by construction: the
(B,) ``acc`` side-channel rides the pipeline's shifting buffer with its
microbatch, so each stage's norm² partials are deposited on the acc
*cotangent* and summed across stage boundaries by the buffer-shift
transpose — the full per-example norm² exists before any algo forms a
clip factor, for materialize/gram/fused alike.  The only numerical
difference is grad_accum-style reassociation of the summed weight
gradients over microbatches (``stage_microbatches`` below owns the
example-aligned split contract).

loss_fn contract: ``loss_fn(params, batch, ctx) -> (per_example_losses, ctx)``
with ``per_example_losses: (B,) float32``.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DPConfig
from repro.core import clipping, noise
from repro.core.context import DPContext

MASK_KEY = "mask"
CLIP_KEY = "clip_norm"


def _batch_size(batch) -> int:
    return jax.tree.leaves(batch)[0].shape[0]


def split_mask(batch) -> Tuple[dict, Optional[jax.Array]]:
    """Split the optional ``"mask"`` (and ``"clip_norm"``) leaves off a
    batch.  Returns (model inputs, f32 (B·K,) 0/1 mask or None)."""
    data, mask, _ = split_clip(batch)
    return data, mask


def split_clip(batch):
    """(model inputs, mask or None, clip-norm override or None): strips
    both auxiliary leaves so model code never sees them."""
    if not isinstance(batch, dict):
        return batch, None, None
    aux = {MASK_KEY, CLIP_KEY}
    if not (aux & set(batch)):
        return batch, None, None
    data = {k: v for k, v in batch.items() if k not in aux}
    mask = batch.get(MASK_KEY)
    if mask is not None:
        mask = mask.astype(jnp.float32)
    return data, mask, batch.get(CLIP_KEY)


def _ones_if_none(mask, B: int) -> jax.Array:
    return jnp.ones((B,), jnp.float32) if mask is None else mask


def _views(dp: DPConfig) -> int:
    return max(1, int(getattr(dp, "augmult", 1)))


def _example_mask(m_rows: jax.Array, k: int) -> jax.Array:
    """(B·K,) row mask -> (B,) per-example mask (views share the mask:
    an example is present with all K views or with none)."""
    if k == 1:
        return m_rows
    return m_rows.reshape(-1, k)[:, 0]


def _view_seed(m_rows: jax.Array, k: int) -> jax.Array:
    """Loss-cotangent seed: the row mask scaled 1/K so pulled-back grads
    (and the side-channel norms²) are means over the K views.  K=1 keeps
    the mask untouched (bit-identity)."""
    return m_rows if k == 1 else m_rows / k


def _expand_rows(c_ex: jax.Array, k: int) -> jax.Array:
    """(B,) per-example weights -> (B·K,) row weights carrying the 1/K
    view averaging (pass-2 seeds: Σ_b c_b · mean_k L_bk)."""
    return c_ex if k == 1 else jnp.repeat(c_ex, k) / k


def stage_microbatches(n_examples: int, n_stages: int,
                       requested: int = 0) -> int:
    """Per-call microbatch count for the pipeline-parallel block stack
    (models/transformer.py ``_blocks_pipelined``).

    The pipeline's microbatch split must respect the same batch contracts
    the algos rely on: a microbatch is a contiguous chunk of *examples*,
    never of rows, so under augmult the K b-major/k-minor views of one
    example always travel through the stages together and the (B,)
    ``ctx.acc`` chunks stay aligned with the activation chunks.  M must
    therefore divide the example count: the request (0 = one microbatch
    per stage, the minimum that fills the pipeline) is clamped to the
    largest divisor of ``n_examples``.  Under vmap-per-example ``dpsgd``
    (and grad_accum chunks of one example) this degrades to M = 1 — a
    stage-sequential schedule with identical numerics and no benefit,
    which is why the autotuner charges pipelining per *chunk* examples,
    not per global batch (launch/autotune.py)."""
    want = max(1, requested or n_stages)
    m = max(1, min(want, n_examples))
    while n_examples % m:
        m -= 1
    return m


def _metrics(losses, nsq, clip_norm, mask_rows, mask_ex):
    """Mask-weighted metrics: padded rows/examples carry exact-zero norms²
    but garbage losses, so every mean/frac is taken over real entries only.
    ``losses``/``mask_rows`` are per-row (B·K,); ``nsq``/``mask_ex`` are
    per-example (B,)."""
    n = jnp.sqrt(jnp.maximum(nsq, 0.0))
    count_rows = jnp.maximum(jnp.sum(mask_rows), 1.0)
    count_ex = jnp.maximum(jnp.sum(mask_ex), 1.0)
    return {
        "loss": jnp.sum(losses * mask_rows) / count_rows,
        "grad_norm_mean": jnp.sum(n * mask_ex) / count_ex,
        "grad_norm_max": jnp.max(n * mask_ex),
        "clipped_frac": jnp.sum((n > clip_norm).astype(jnp.float32) * mask_ex)
                        / count_ex,
        "realized_batch": jnp.sum(mask_ex),
    }


# ---------------------------------------------------------------------------
# per-algorithm clipped-sum kernels:  (params, microbatch[+mask]) ->
#   (Σ_i m_i c_i g_i  [f32 tree],  (losses (b,), nsq (b,)))
# ---------------------------------------------------------------------------

def _sgd_sum(loss_fn):
    def fn(params, batch):
        data, mask = split_mask(batch)
        b = _batch_size(data)
        m = _ones_if_none(mask, b)

        def sum_loss(p):
            losses, _ = loss_fn(p, data, DPContext.off())
            return jnp.sum(m * losses), losses

        (_, losses), grads = jax.value_and_grad(sum_loss, has_aux=True)(params)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, (losses, jnp.zeros((b,), jnp.float32))
    return fn


def _dpsgd_sum(loss_fn, dp: DPConfig):
    def fn(params, batch):
        data, mask, clip = split_clip(batch)
        R = _batch_size(data)
        K = _views(dp)
        assert R % K == 0, (R, K)
        B = R // K                         # examples (privacy units)
        m = _ones_if_none(mask, R)
        me = _example_mask(m, K)
        C = dp.clip_norm if clip is None else clip
        # microbatch counts *examples* (each example carries its K views)
        mbe = dp.microbatch or B
        assert B % mbe == 0, (B, mbe, K)

        def one_example_grad(p, ex, mi):
            # ex leaves: (K, ...) — the K views of one example
            def l(p_):
                losses, _ = loss_fn(p_, ex, DPContext.off())
                # mask at the loss: padded rows backprop an exact-zero
                # cotangent -> zero per-example grad, zero norm; mean over
                # the K views = the augmult-averaged per-example grad
                return mi * jnp.mean(losses), losses
            (_, raw), g = jax.value_and_grad(l, has_aux=True)(p)
            return raw, g

        def microbatch_step(acc, chunk):
            cdata, cm = chunk
            losses, gb = jax.vmap(
                lambda ex, mi: one_example_grad(params, ex, mi))(cdata, cm)
            with jax.named_scope("dp_clip"):
                summed, nsq = clipping.clip_and_sum(gb, C, mask=cm)
            acc = jax.tree.map(lambda a, s: a + s.astype(jnp.float32),
                               acc, summed)
            return acc, (losses, nsq)

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # (B, K, ...) example-major chunks: scan over microbatches of
        # examples, vmap per example, each example carrying its K views
        chunks = jax.tree.map(
            lambda a: a.reshape((B // mbe, mbe, K) + a.shape[1:]), data)
        summed, (losses, nsq) = jax.lax.scan(
            microbatch_step, zeros,
            (chunks, me.reshape(B // mbe, mbe)))
        return summed, (losses.reshape(-1), nsq.reshape(-1))
    return fn


def _dpsgd_r_sum(loss_fn, dp: DPConfig):
    def fn(params, batch):
        data, mask, clip = split_clip(batch)
        R = _batch_size(data)
        K = _views(dp)
        assert R % K == 0, (R, K)
        B = R // K
        m = _ones_if_none(mask, R)
        me = _example_mask(m, K)
        C = dp.clip_norm if clip is None else clip
        seed = _view_seed(m, K)

        # ---- pass 1: per-example grad norms via the side-channel --------
        # Seeding Σ (mᵢ/K)·Lᵢ (not Σ Lᵢ) makes every padded row's gy — and
        # hence its norms² through all DPContext sites — an exact zero, and
        # scales the cotangents so the (B,) accumulator holds the squared
        # norm of each example's K-averaged gradient.
        def pass1(p, acc0):
            ctx = DPContext(acc=acc0, mode="norm", strategy=dp.norm_strategy,
                            use_kernels=dp.use_kernels, augmult=K)
            losses, ctx = loss_fn(p, data, ctx)
            return (jnp.sum(seed * losses), ctx.acc), losses

        acc0 = jnp.zeros((B,), jnp.float32)
        _, pull, losses = jax.vjp(pass1, params, acc0, has_aux=True)
        # params cotangent is discarded -> its weight-grad GEMMs are DCE'd.
        _, nsq = pull((jnp.ones(()), jnp.zeros((B,), jnp.float32)))

        with jax.named_scope("dp_clip"):
            c = clipping.clip_factors(nsq, C) * me             # line 35
        crow = _expand_rows(c, K)          # Σ_b c_b · mean_k L_bk

        # ---- pass 2: backprop of the reweighted loss --------------------
        def reweighted_loss(p):
            ls, _ = loss_fn(p, data, DPContext.off())
            return jnp.sum(jax.lax.stop_gradient(crow) * ls)   # line 36

        grads = jax.grad(reweighted_loss)(params)              # line 39
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, (losses, nsq)
    return fn


def _dpsgd_r1f_sum(loss_fn, dp: DPConfig):
    """Single-forward DP-SGD(R) (beyond-paper, EXPERIMENTS.md §Perf).

    The paper's DP-SGD(R) runs backpropagation twice, each with its own
    forward pass.  But pass 2's forward is bit-identical to pass 1's, so we
    take ONE ``jax.vjp`` and pull back twice through the shared residuals:

      pullback(m_B, 0)  -> norm-channel cotangent  = per-example norms²
                           (param cotangents discarded -> wgrad GEMMs DCE'd;
                            the mask seed zeroes padded rows exactly)
      pullback(m·c, 0)  -> param cotangents of Σ mᵢcᵢLᵢ = clipped grad sum
                           (norm-channel cotangent discarded -> norm-rule
                            einsums DCE'd)

    One forward (+ remat recompute inside each pullback) instead of two —
    identical update to ``dpsgd_r``/``dpsgd`` (tested to equality).
    """
    def fn(params, batch):
        data, mask, clip = split_clip(batch)
        R = _batch_size(data)
        K = _views(dp)
        assert R % K == 0, (R, K)
        B = R // K
        m = _ones_if_none(mask, R)
        me = _example_mask(m, K)
        C = dp.clip_norm if clip is None else clip

        def both(p, acc0):
            ctx = DPContext(acc=acc0, mode="norm", strategy=dp.norm_strategy,
                            use_kernels=dp.use_kernels, augmult=K)
            losses, ctx = loss_fn(p, data, ctx)
            return (losses, ctx.acc), losses

        acc0 = jnp.zeros((B,), jnp.float32)
        _, pull, losses = jax.vjp(both, params, acc0, has_aux=True)
        zero_acc = jnp.zeros((B,), jnp.float32)
        _, nsq = pull((_view_seed(m, K), zero_acc))
        with jax.named_scope("dp_clip"):
            c = clipping.clip_factors(nsq, C) * me
        grads, _ = pull((jax.lax.stop_gradient(_expand_rows(c, K)), zero_acc))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, (losses, nsq)
    return fn


# ---------------------------------------------------------------------------
# algorithm registry (mirrors the site registry in core/sites.py)
# ---------------------------------------------------------------------------

_ALGOS: dict = {}


def register_algo(name: str, builder: Callable, *,
                  private: bool = True, overwrite: bool = False) -> None:
    """Register a clipped-sum algorithm.

    ``builder(loss_fn, dp) -> fn(params, batch) -> (summed, (losses, nsq))``
    — the per-microbatch clipped-sum kernel (see the builtins above for the
    exact contract).  ``private=False`` marks the algorithm as adding no
    noise (``make_noisy_grad_fn`` then mean-normalizes instead).  Adding a
    DP algorithm is one call here, not an if-chain edit.
    """
    if name in _ALGOS and not overwrite:
        raise ValueError(f"dp.algo {name!r} already registered "
                         f"(registered algos: {sorted(_ALGOS)}); "
                         f"pass overwrite=True to replace it")
    _ALGOS[name] = (builder, bool(private))


def unregister_algo(name: str) -> None:
    _ALGOS.pop(name, None)


def list_algos() -> list:
    return sorted(_ALGOS)


def algo_is_private(name: str, enabled: bool = True) -> bool:
    if not enabled:
        return False
    _lookup_algo(name)
    return _ALGOS[name][1]


def _lookup_algo(name: str):
    try:
        return _ALGOS[name][0]
    except KeyError:
        raise ValueError(f"unknown dp.algo {name!r}; registered algos: "
                         f"{sorted(_ALGOS)}") from None


register_algo("sgd", lambda loss_fn, dp: _sgd_sum(loss_fn), private=False)
register_algo("dpsgd", _dpsgd_sum)
register_algo("dpsgd_r", _dpsgd_r_sum)
register_algo("dpsgd_r1f", _dpsgd_r1f_sum)


def make_clipped_sum_fn(loss_fn: Callable, dp: DPConfig) -> Callable:
    if not dp.enabled:
        return _sgd_sum(loss_fn)
    return _lookup_algo(dp.algo)(loss_fn, dp)


# ---------------------------------------------------------------------------
# top level: accumulate -> noise -> scale
# ---------------------------------------------------------------------------

def make_noisy_grad_fn(loss_fn: Callable, dp: DPConfig,
                       grad_accum: int = 1,
                       expected_batch_size: Optional[float] = None) -> Callable:
    """Build fn(params, batch, key) -> (grads, metrics).

    ``expected_batch_size``: private-update normalizer.  None (default)
    uses the physical batch size — correct for fixed-size batches.  Under
    ``DPConfig.sampling="poisson"`` pass q·N (= the configured batch size,
    by construction of the sampler's rate) — Algorithm 1 line 24 divides by
    the lot size, NOT the realized sample size.
    """
    csum = make_clipped_sum_fn(loss_fn, dp)
    private = algo_is_private(dp.algo, dp.enabled)
    K = _views(dp)

    def fn(params, batch, key, clip_norm=None):
        """``clip_norm``: optional traced override of ``dp.clip_norm`` —
        the trainer's adaptive-clip state (core/adaptive_clip.py).  It is
        injected into the (chunked) batch as the ``"clip_norm"`` leaf, so
        registered algos pick it up through ``split_clip`` with no
        signature change.  When given under ``dp.adaptive_clip``, metrics
        additionally carry clip_norm / clip_frac_below / clip_norm_next."""
        _, mask = split_mask(batch)
        R = _batch_size(batch)
        assert R % K == 0, (R, K)
        full_mask = _ones_if_none(mask, R)
        mask_ex = _example_mask(full_mask, K)
        adaptive = dp.adaptive_clip and private and clip_norm is not None
        if adaptive:
            key, clip_key = jax.random.split(key)

        def with_clip(b):
            if clip_norm is None:
                return b
            assert isinstance(b, dict), "clip_norm override needs dict batches"
            return dict(b, **{CLIP_KEY: clip_norm})

        if grad_accum == 1:
            summed, (losses, nsq) = csum(params, with_clip(batch))
        else:
            assert R % grad_accum == 0, (R, grad_accum)
            assert (R // grad_accum) % K == 0, (R, grad_accum, K)
            chunks = jax.tree.map(
                lambda a: a.reshape((grad_accum, R // grad_accum)
                                    + a.shape[1:]), batch)

            def body(acc, chunk):
                s, (l, n) = csum(params, with_clip(chunk))
                return jax.tree.map(jnp.add, acc, s), (l, n)

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            summed, (losses, nsq) = jax.lax.scan(body, zeros, chunks)
            losses, nsq = losses.reshape(-1), nsq.reshape(-1)

        if private:
            C = dp.clip_norm if clip_norm is None else clip_norm
            denom = (float(expected_batch_size)
                     if expected_batch_size is not None else R // K)
            with jax.named_scope("dp_noise"):
                grads = noise.add_noise(summed, key, dp.noise_multiplier,
                                        C, denom)              # lines 24/41
            metrics = _metrics(losses, nsq, C, full_mask, mask_ex)
            if adaptive:
                from repro.core import adaptive_clip
                state, frac = adaptive_clip.update(
                    {"clip_norm": C}, nsq, mask_ex, dp, denom, clip_key)
                metrics["clip_norm"] = jnp.asarray(C, jnp.float32)
                metrics["clip_frac_below"] = frac
                metrics["clip_norm_next"] = state["clip_norm"]
        else:
            count = jnp.maximum(jnp.sum(full_mask), 1.0)
            grads = jax.tree.map(lambda g: g / count, summed)
            metrics = {"loss": jnp.sum(losses * full_mask) / count,
                       "realized_batch": jnp.sum(full_mask)}
        return grads, metrics

    return fn
