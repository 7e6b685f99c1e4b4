"""Pluggable private-site registry — the extension point of the DP core.

DiVa's contribution is fusing per-example gradient-norm computation into
backprop for *arbitrary layer types*.  This module is that claim as an API:
a **site** is any parameterized op whose per-example weight-gradient norm
the DP-SGD(R) side-channel must observe, described by one self-contained
registry entry instead of if-chains spread across context/norms/kernels/
costs::

    register_site("conv2d",
                  fwd=...,                                   # the plain op
                  nsq_rules={"materialize": ..., "gram": ...},  # exact rules
                  kernel_route={...},     # optional fused Pallas variants
                  flops={...},            # per-rule cost formulas
                  bwd=...)                # optional custom backward

``DPContext.site(kind, *operands)`` (core/context.py) then routes through
the generic ``site_call`` custom_vjp below: forward is the plain op
(identity on the ``(B,)`` norm accumulator), backward adds the site's
per-example squared-grad-norm to the accumulator's cotangent.

Contracts every entry must satisfy (tests/test_sites_registry.py):

* **Exactness** — each rule in ``nsq_rules`` returns the exact squared L2
  norm of the per-example gradient of the site's *parameters* as a ``(B,)``
  float32 array (``rule(spec, operands, gy) -> (B,)``).
* **Masked-batch invariant** — a rule must map an all-zero ``gy`` row to an
  *exactly*-zero norm².  core/algo.py seeds padded Poisson rows with zero
  loss cotangents, so this is what makes masked batches equal compacted
  ones; any rule that is a sum of products each containing a ``gy`` factor
  satisfies it for free.
* **Strategy selection** — when a site has several rules, ``"auto"`` picks
  the cheapest by the entry's own ``flops`` formulas (the paper's
  Book-Keeping trick, generalized beyond the dense einsum shape).  The
  formulas are also what launch/costs.py and benchmarks/paper_figs.py read
  for analytic norm-rule accounting.

Built-in sites: ``dense`` / ``moe_dense`` / ``embed`` / ``tap`` (the
transformer stack) plus ``conv2d`` (im2col materialize + spatial ghost
norm) and ``bias`` — the CNN workload of models/cnn.py — and the
parameter-free ``attention`` site that carries the fused flash-backward
kernel route (norm_strategy="fused"; see the entry below).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import norms

F32 = jnp.float32

# The checkpoint_name tag DPContext puts on every operand a site's norm
# rules consume (SiteDef.save_operands).  remat="sites"
# (jax.checkpoint_policies.save_only_these_names(SAVE_SITE_NAME)) then
# saves exactly these values as residuals and recomputes everything else —
# the per-example-norm backward never re-runs the forward just to rebuild
# a site input, while non-site intermediates (attention scores, activation
# functions, norm statistics) stay transient.
SAVE_SITE_NAME = "dp_site_operand"


def name_saved_operands(site: "SiteDef", operands: tuple) -> tuple:
    """Tag the operands ``site.save_operands`` names with
    ``jax.ad_checkpoint.checkpoint_name(..., SAVE_SITE_NAME)``.

    A no-op unless an enclosing ``jax.checkpoint`` uses a name-aware
    policy (models/layers.py ``remat_wrap(..., "sites")``); under any
    other policy the name primitive is identity and fuses away."""
    if not site.save_operands:
        return operands
    from jax.ad_checkpoint import checkpoint_name
    ops = list(operands)
    for i in site.save_operands:
        ops[i] = checkpoint_name(ops[i], SAVE_SITE_NAME)
    return tuple(ops)


# ---------------------------------------------------------------------------
# Spec & registry entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SiteSpec:
    """Static per-site-call config (hashable; passed via nondiff_argnums).

    ``meta`` carries per-call static extras a site's callbacks may need
    (e.g. ``tap``'s ``(nexp, batch)``, ``conv2d``'s ``(stride, padding)``).

    ``augmult`` is the augmentation-multiplicity K of the batch contract:
    operands carry B·K rows (b-major, k-minor) while the norm accumulator
    stays (B,).  Rules must return the squared norm of the **K-averaged**
    per-example gradient — mean-over-K *then* norm², never norm² over B·K
    rows.  The algos pre-scale the loss cotangents by 1/K, so a rule
    implements this by folding the K views into its contraction axis
    (``norms.fold_views4``) — the K-averaged wgrad is then the ordinary
    wgrad of the folded problem and every existing rule/kernel applies
    unchanged.  ``augmult=1`` must be bit-identical to the pre-K contract.
    """
    kind: str
    strategy: str = "auto"
    use_kernels: bool = False
    meta: tuple = ()
    augmult: int = 1


@dataclasses.dataclass(frozen=True)
class SiteDef:
    """One registered site type.  See module docstring for the contracts.

    ``fwd(spec, *operands) -> y`` — the plain op.
    ``nsq_rules[name](spec, operands, gy) -> (B,) f32`` — exact norm rules.
    ``bwd(spec, operands, gy) -> operand cotangents`` — optional; ``None``
      autodiffs ``fwd`` (``nondiff_operands`` get a ``None`` cotangent).
    ``kernel_route[name]`` — Pallas-kernel variant of the same-named rule,
      used when ``SiteSpec.use_kernels`` (falls back to ``nsq_rules``).
    ``fused_bwd[name](spec, operands, gy) -> (grads, nsq)`` — optional
      *joint* backward for the same-named strategy: one callback produces
      the operand cotangents AND the per-example norm² together, replacing
      the separate ``bwd``-then-``nsq_rules`` dispatch in
      ``_site_call_bwd``.  This is how ``"fused"`` routes into the
      single-sweep kernels (kernels/fused_bwd.py, flash_attn.py) instead
      of a second pass.  Must satisfy the same exactness and masked-batch
      contracts as the rules.
    ``flops[name](operand_shapes, gy_shape) -> float`` — analytic FLOPs of
      the same-named rule; drives ``"auto"`` strategy resolution and the
      cost/benchmark tooling.
    ``save_operands`` — operand indices the norm rules consume (and the
      ``remat="sites"`` policy must therefore keep resident as residuals;
      see ``SAVE_SITE_NAME``).  Defaults to ``(0,)`` — the activation of
      an ``(x, w)``-shaped site; parameters should never be listed (they
      are jaxpr inputs, already resident, and naming a scanned per-layer
      parameter slice would duplicate it in the residuals).
    """
    kind: str
    fwd: Callable
    nsq_rules: Mapping[str, Callable]
    bwd: Optional[Callable] = None
    kernel_route: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    fused_bwd: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    flops: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    nondiff_operands: Tuple[int, ...] = ()
    save_operands: Tuple[int, ...] = (0,)


_REGISTRY: Dict[str, SiteDef] = {}
_ALIASES = ("auto",)   # strategy names that are never literal rule names


def register_site(kind: str, *, fwd: Callable,
                  nsq_rules: Mapping[str, Callable],
                  bwd: Optional[Callable] = None,
                  kernel_route: Optional[Mapping[str, Callable]] = None,
                  fused_bwd: Optional[Mapping[str, Callable]] = None,
                  flops: Optional[Mapping[str, Callable]] = None,
                  nondiff_operands: Sequence[int] = (),
                  save_operands: Sequence[int] = (0,),
                  overwrite: bool = False) -> SiteDef:
    """Register a site type.  Third-party callers (models, tests, plugins)
    use exactly this entry point — the builtins below claim no special
    machinery.  Returns the ``SiteDef`` for introspection."""
    if not nsq_rules:
        raise ValueError(f"site {kind!r} needs at least one nsq rule")
    for bad in set(nsq_rules) & set(_ALIASES):
        raise ValueError(f"site {kind!r}: {bad!r} is a reserved strategy name")
    if kind in _REGISTRY and not overwrite:
        raise ValueError(f"site kind {kind!r} already registered "
                         f"(registered kinds: {sorted(_REGISTRY)}); "
                         f"pass overwrite=True to replace it")
    site = SiteDef(kind=kind, fwd=fwd, nsq_rules=dict(nsq_rules), bwd=bwd,
                   kernel_route=dict(kernel_route or {}),
                   fused_bwd=dict(fused_bwd or {}),
                   flops=dict(flops or {}),
                   nondiff_operands=tuple(nondiff_operands),
                   save_operands=tuple(save_operands))
    for field_name, mapping in (("kernel_route", site.kernel_route),
                                ("fused_bwd", site.fused_bwd),
                                ("flops", site.flops)):
        unknown = set(mapping) - set(site.nsq_rules)
        if unknown:
            raise ValueError(
                f"site {kind!r}: {field_name} names {sorted(unknown)} have "
                f"no matching nsq rule {sorted(site.nsq_rules)}")
    _REGISTRY[kind] = site
    return site


def unregister_site(kind: str) -> None:
    """Remove a registration (tests / plugin teardown)."""
    _REGISTRY.pop(kind, None)


def get_site(kind: str) -> SiteDef:
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise KeyError(f"unknown site kind {kind!r}; registered site kinds: "
                       f"{sorted(_REGISTRY)}") from None


def list_sites() -> list:
    return sorted(_REGISTRY)


def list_strategies(kind: str) -> list:
    return sorted(get_site(kind).nsq_rules)


# ---------------------------------------------------------------------------
# Strategy resolution (generalized Book-Keeping trick)
# ---------------------------------------------------------------------------

def resolve_strategy(kind: str, strategy: str, operand_shapes, gy_shape) -> str:
    """Resolve a strategy name to a registered rule of ``kind``.

    ``"auto"`` picks the cheapest rule by the site's own ``flops`` formulas;
    a named strategy must exist for the site *unless* the site has a single
    rule (the context-wide strategy setting then simply doesn't apply —
    e.g. ``embed``/``tap`` under ``strategy="gram"``)."""
    site = get_site(kind)
    rules = site.nsq_rules
    if strategy in rules:
        return strategy
    if len(rules) == 1:
        return next(iter(rules))
    if strategy == "auto":
        best, best_cost = None, None
        for name in rules:             # ties -> first-registered rule
            if name not in site.flops:
                continue
            cost = site.flops[name](operand_shapes, gy_shape)
            if best is None or cost < best_cost:
                best, best_cost = name, cost
        return best if best is not None else next(iter(rules))
    raise ValueError(
        f"unknown norm strategy {strategy!r} for site {kind!r}; "
        f"registered strategies: {sorted(rules)} (or 'auto')")


def site_flops(kind: str, strategy: str, operand_shapes, gy_shape) -> float:
    """Analytic FLOPs of ``kind``'s ``strategy`` rule at these shapes
    (resolving ``"auto"`` first).  Raises if the site declares no formula."""
    site = get_site(kind)
    strat = resolve_strategy(kind, strategy, operand_shapes, gy_shape)
    try:
        fn = site.flops[strat]
    except KeyError:
        raise KeyError(f"site {kind!r} declares no FLOP formula for rule "
                       f"{strat!r}; declared: {sorted(site.flops)}") from None
    return fn(operand_shapes, gy_shape)


def norm_scope(kind: str, rule: str):
    """``jax.named_scope`` of a site's norm work, ``dp_norm.<kind>.<rule>``
    (the resolved rule), so its ops can be told apart in compiled HLO
    metadata and in profiler traces."""
    return jax.named_scope(f"dp_norm.{kind}.{rule}")


def site_nsq(spec: SiteSpec, operands, gy) -> jax.Array:
    """Dispatch to the site's (resolved, possibly kernel-backed) norm rule."""
    site = get_site(spec.kind)
    shapes = tuple(getattr(o, "shape", ()) for o in operands)
    strat = resolve_strategy(spec.kind, spec.strategy, shapes, gy.shape)
    rule = (site.kernel_route[strat]
            if spec.use_kernels and strat in site.kernel_route
            else site.nsq_rules[strat])
    with norm_scope(spec.kind, strat):
        return rule(spec, operands, gy)


# ---------------------------------------------------------------------------
# The generic custom_vjp site
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def site_call(spec: SiteSpec, acc, *operands):
    """y, acc = site_call(spec, acc, *operands) — fwd is the plain op,
    identity on the accumulator; bwd adds the per-example norm² to the
    accumulator's cotangent (DiVa's PPU fusion, functionally)."""
    return get_site(spec.kind).fwd(spec, *operands), acc


def _site_call_fwd(spec, acc, *operands):
    return site_call(spec, acc, *operands), operands


def _site_call_bwd(spec, operands, cots):
    gy, gacc = cots
    site = get_site(spec.kind)
    shapes = tuple(getattr(o, "shape", ()) for o in operands)
    strat = resolve_strategy(spec.kind, spec.strategy, shapes, gy.shape)
    fused = site.fused_bwd.get(strat)
    if fused is not None:
        with norm_scope(spec.kind, strat):
            grads, nsq = fused(spec, operands, gy)
    else:
        grads = _operand_grads(site, spec, operands, gy)
        nsq = site_nsq(spec, operands, gy)
    return (gacc + nsq,) + tuple(grads)


site_call.defvjp(_site_call_fwd, _site_call_bwd)


def _operand_grads(site: SiteDef, spec: SiteSpec, operands, gy):
    """Operand cotangents: the site's explicit ``bwd`` if given, else
    autodiff of ``fwd`` over the differentiable operands."""
    if site.bwd is not None:
        return site.bwd(spec, operands, gy)
    diff = [i for i in range(len(operands)) if i not in site.nondiff_operands]

    def f(*diff_ops):
        ops = list(operands)
        for i, v in zip(diff, diff_ops):
            ops[i] = v
        return site.fwd(spec, *ops)

    _, pull = jax.vjp(f, *(operands[i] for i in diff))
    gdiff = pull(gy)
    grads: list = [None] * len(operands)
    for i, g in zip(diff, gdiff):
        grads[i] = g.astype(operands[i].dtype)
    return tuple(grads)


# ---------------------------------------------------------------------------
# Built-in sites: dense / moe_dense / embed / tap
# ---------------------------------------------------------------------------

def _canon4_shape(shape):
    """Shape-level twin of norms.canon4: pad to (B, G, T, d)."""
    if len(shape) == 2:
        b, d = shape
        return (b, 1, 1, d)
    if len(shape) == 3:
        b, t, d = shape
        return (b, 1, t, d)
    if len(shape) == 4:
        return tuple(shape)
    raise ValueError(f"dense site operand must be 2/3/4-D, got {shape}")


def _dense_fwd(spec, x, w):
    return jnp.einsum("...i,io->...o", x, w)


def _dense_bwd(spec, operands, gy):
    x, w = operands
    gx = jnp.einsum("...o,io->...i", gy, w).astype(x.dtype)
    gw = jnp.einsum("...i,...o->io", x, gy).astype(w.dtype)
    return gx, gw


def _moe_dense_fwd(spec, x, w):
    return jnp.einsum("beci,eio->beco", x, w)


def _moe_dense_bwd(spec, operands, gy):
    x, w = operands
    gx = jnp.einsum("beco,eio->beci", gy, w).astype(x.dtype)
    gw = jnp.einsum("beci,beco->eio", x, gy).astype(w.dtype)
    return gx, gw


def _dense_pair4(spec, operands, gy):
    """Canonicalized (x4, gy4) with the augmult views folded into the
    contraction axis: (B·K, G, T, d) -> (B, G, K·T, d).  With the algos'
    1/K-scaled cotangents, the downstream rule then computes the exact
    ‖mean-over-K wgrad‖² per example.  K=1 is the identity."""
    k = spec.augmult
    return (norms.fold_views4(norms.canon4(operands[0]), k),
            norms.fold_views4(norms.canon4(gy), k))


def _dense_rule_materialize(spec, operands, gy):
    return norms.dense_nsq_materialize(*_dense_pair4(spec, operands, gy))


def _dense_rule_gram(spec, operands, gy):
    return norms.dense_nsq_gram(*_dense_pair4(spec, operands, gy))


def _dense_kernel_materialize(spec, operands, gy):
    from repro.kernels import ops as kops
    return kops.pegrad_norm(*_dense_pair4(spec, operands, gy))


def _dense_kernel_gram(spec, operands, gy):
    from repro.kernels import ops as kops
    return kops.gram_norm(*_dense_pair4(spec, operands, gy))


def _dense_flops_materialize(operand_shapes, gy_shape):
    return norms.flops_materialize(_canon4_shape(operand_shapes[0]),
                                   _canon4_shape(gy_shape))


def _dense_flops_gram(operand_shapes, gy_shape):
    return norms.flops_gram(_canon4_shape(operand_shapes[0]),
                            _canon4_shape(gy_shape))


def _dense_flops_fused(operand_shapes, gy_shape):
    return norms.flops_fused(_canon4_shape(operand_shapes[0]),
                             _canon4_shape(gy_shape))


# --- the "fused" strategy -------------------------------------------------
#
# Same mathematics as "materialize" (the wgrad-tile sweep), but computed
# *jointly with the activation gradient* in one pass: the fused_bwd entry
# replaces the bwd-then-rule dispatch in _site_call_bwd.  With use_kernels
# it is the single-sweep Pallas kernel kernels/fused_bwd.py (x/gy read
# once, no second launch); without kernels it runs the identical XLA ops
# as the separate path, so the fused XLA route is bit-identical to
# "materialize".  The summed weight gradient stays an einsum *outside* the
# kernel so DP-SGD(R) pass 1 can DCE it.  Its FLOP entry equals
# materialize's — the extra work over plain backprop is the same wgrad-tile
# sweep — and since "auto" resolves ties to the first-registered rule by a
# strict <, "auto" never silently picks "fused": it is an explicit opt-in
# (DPConfig.norm_strategy = "fused").

def _dense_rule_fused(spec, operands, gy):
    # norm-only evaluation (site_nsq): same math as materialize
    return _dense_rule_materialize(spec, operands, gy)


def _dense_kernel_fused(spec, operands, gy):
    from repro.kernels import ops as kops
    _, nsq = kops.dense_bwd_norm(norms.canon4(operands[0]), norms.canon4(gy),
                                 operands[1])
    return nsq


def _dense_fused_bwd(spec, operands, gy):
    x, w = operands
    if spec.use_kernels:
        from repro.kernels import ops as kops
        # the kernel computes the dgrad rows AND the folded (= K-averaged)
        # norm² in one sweep; unfold restores the (B·K)-row layout
        gx4, nsq = kops.dense_bwd_norm(*_dense_pair4(spec, operands, gy), w)
        gx = norms.unfold_views4(gx4, spec.augmult).reshape(x.shape)
        gx = gx.astype(x.dtype)
    else:
        gx = jnp.einsum("...o,io->...i", gy, w).astype(x.dtype)
        nsq = _dense_rule_materialize(spec, operands, gy)
    gw = jnp.einsum("...i,...o->io", x, gy).astype(w.dtype)
    return (gx, gw), nsq


def _moe_dense_fused_bwd(spec, operands, gy):
    x, w = operands                       # x (B,E,C,di), w (E,di,do)
    if spec.use_kernels:
        from repro.kernels import ops as kops
        gx4, nsq = kops.dense_bwd_norm(*_dense_pair4(spec, operands, gy), w)
        gx = norms.unfold_views4(gx4, spec.augmult).astype(x.dtype)
    else:
        gx = jnp.einsum("beco,eio->beci", gy, w).astype(x.dtype)
        nsq = _dense_rule_materialize(spec, operands, gy)
    gw = jnp.einsum("beci,beco->eio", x, gy).astype(w.dtype)
    return (gx, gw), nsq


_DENSE_RULES = dict(materialize=_dense_rule_materialize,
                    gram=_dense_rule_gram,
                    fused=_dense_rule_fused)
_DENSE_KERNELS = dict(materialize=_dense_kernel_materialize,
                      gram=_dense_kernel_gram,
                      fused=_dense_kernel_fused)
_DENSE_FLOPS = dict(materialize=_dense_flops_materialize,
                    gram=_dense_flops_gram,
                    fused=_dense_flops_fused)

register_site("dense", fwd=_dense_fwd, bwd=_dense_bwd,
              nsq_rules=_DENSE_RULES, kernel_route=_DENSE_KERNELS,
              fused_bwd={"fused": _dense_fused_bwd},
              flops=_DENSE_FLOPS)
register_site("moe_dense", fwd=_moe_dense_fwd, bwd=_moe_dense_bwd,
              nsq_rules=_DENSE_RULES, kernel_route=_DENSE_KERNELS,
              fused_bwd={"fused": _moe_dense_fused_bwd},
              flops=_DENSE_FLOPS)


def _embed_fwd(spec, ids, table):
    return jnp.take(table, ids, axis=0)


def _embed_bwd(spec, operands, gy):
    ids, table = operands
    flat_ids = ids.reshape(-1)
    gt = jnp.zeros(table.shape, dtype=gy.dtype).at[flat_ids].add(
        gy.reshape(-1, table.shape[-1])).astype(table.dtype)
    return None, gt


def _embed_fold(spec, ids, gy):
    """Fold K views into the token axis: (B·K, T) -> (B, K·T).  Same-token
    rows across views then combine in the segment sum *before* squaring —
    exactly the K-averaged table gradient (gy arrives 1/K-scaled)."""
    k = spec.augmult
    if k == 1:
        return ids, gy
    B = ids.shape[0] // k
    return ids.reshape(B, -1), gy.reshape(B, -1, gy.shape[-1])


def _embed_rule(spec, operands, gy):
    ids, gy = _embed_fold(spec, operands[0], gy)
    return norms.embed_nsq(ids, gy, use_kernels=False)


def _embed_kernel_rule(spec, operands, gy):
    ids, gy = _embed_fold(spec, operands[0], gy)
    return norms.embed_nsq(ids, gy, use_kernels=True)


def _embed_flops(operand_shapes, gy_shape):
    # sort+segment-sum: O(B·T·d) adds (+ the O(B·T·logT) sort, omitted)
    b, t, d = gy_shape
    return 2 * b * t * d


register_site("embed", fwd=_embed_fwd, bwd=_embed_bwd,
              nsq_rules={"segment_sum": _embed_rule},
              kernel_route={"segment_sum": _embed_kernel_rule},
              flops={"segment_sum": _embed_flops},
              nondiff_operands=(0,))


def _tap_fwd(spec, p):
    nexp, batch = spec.meta
    shape = (batch,) + (1,) * nexp + p.shape
    return jnp.broadcast_to(p, (batch,) + p.shape).reshape(shape)


def _tap_bwd(spec, operands, gy):
    (p,) = operands
    nexp, batch = spec.meta
    gpb = gy.reshape((batch,) + p.shape)
    return (jnp.sum(gpb, axis=0).astype(p.dtype),)


def _tap_rule(spec, operands, gy):
    (p,) = operands
    nexp, batch = spec.meta              # batch counts rows (B·K)
    gpb = gy.reshape((batch,) + p.shape)
    if spec.augmult > 1:
        # sum the K views' param grads (gy is 1/K-scaled -> mean) first
        gpb = jnp.sum(gpb.reshape((batch // spec.augmult, spec.augmult)
                                  + p.shape), axis=1)
    return norms.tap_nsq(gpb)


def _tap_flops(operand_shapes, gy_shape):
    n = 1
    for s in gy_shape:
        n *= int(s)
    return 2 * n


# tap's only operand is the parameter itself and its rule consumes only gy,
# so the sites remat policy has nothing to save here
register_site("tap", fwd=_tap_fwd, bwd=_tap_bwd,
              nsq_rules={"direct": _tap_rule},
              flops={"direct": _tap_flops},
              save_operands=())


# ---------------------------------------------------------------------------
# conv2d: im2col materialize + ghost norm over spatial positions
# ---------------------------------------------------------------------------
#
# y = conv2d(x, w), x: (B, H, W, Cin), w: (kh, kw, Cin, Cout) [NHWC/HWIO].
# The per-example weight gradient is gw_b = patchesᵀ_b @ gy_b with
# patches = im2col(x): (B, P, kh·kw·Cin) and gy flattened to (B, P, Cout),
# P the number of output positions — i.e. *exactly a dense site* with
# T = P, d_in = kh·kw·Cin, d_out = Cout.  Both dense rules (and both dense
# Pallas kernels) therefore apply verbatim to the patch tensors, and the
# masked-batch invariant is inherited (zero gy rows annihilate).

_CONV_DN = ("NHWC", "HWIO", "NHWC")


def _conv_meta(spec):
    stride, padding = spec.meta if spec.meta else (1, "SAME")
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    return s, padding


def _conv2d_fwd(spec, x, w):
    s, padding = _conv_meta(spec)
    return jax.lax.conv_general_dilated(x, w, window_strides=s,
                                        padding=padding,
                                        dimension_numbers=_CONV_DN)


def _conv_patches(spec, x, w):
    """(B, H', W', kh·kw·Cin) im2col patches matching ``_conv2d_fwd``'s
    output positions.  Feature ordering is irrelevant: both norm rules are
    invariant to permutations of the contraction axis."""
    s, padding = _conv_meta(spec)
    kh, kw = w.shape[0], w.shape[1]
    return jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=s, padding=padding,
        dimension_numbers=_CONV_DN)


def _conv_pair4(spec, operands, gy):
    x, w = operands[0], operands[1]
    pat = _conv_patches(spec, x, w)
    # fold the K views into the position axis (a plain reshape: rows are
    # b-major/k-minor and G == 1) -> per-example K-averaged norm²
    B = x.shape[0] // spec.augmult
    x4 = pat.reshape(B, 1, -1, pat.shape[-1])
    gy4 = gy.reshape(B, 1, -1, gy.shape[-1])
    return x4, gy4


def _conv_rule_materialize(spec, operands, gy):
    return norms.dense_nsq_materialize(*_conv_pair4(spec, operands, gy))


def _conv_rule_gram(spec, operands, gy):
    return norms.dense_nsq_gram(*_conv_pair4(spec, operands, gy))


def _conv_kernel_materialize(spec, operands, gy):
    from repro.kernels import ops as kops
    return kops.pegrad_norm(*_conv_pair4(spec, operands, gy))


def _conv_kernel_gram(spec, operands, gy):
    from repro.kernels import ops as kops
    return kops.gram_norm(*_conv_pair4(spec, operands, gy))


def conv_norm_dims(operand_shapes, gy_shape):
    """(B, P, d_in, d_out) of the conv site's implied dense problem."""
    x_shape, w_shape = operand_shapes[0], operand_shapes[1]
    b = x_shape[0]
    p = 1
    for s in gy_shape[1:-1]:
        p *= int(s)
    d_in = int(w_shape[0]) * int(w_shape[1]) * int(w_shape[2])
    return b, p, d_in, int(gy_shape[-1])


def _conv_flops_materialize(operand_shapes, gy_shape):
    b, p, d_in, d_out = conv_norm_dims(operand_shapes, gy_shape)
    return norms.flops_materialize((b, 1, p, d_in), (b, 1, p, d_out))


def _conv_flops_gram(operand_shapes, gy_shape):
    b, p, d_in, d_out = conv_norm_dims(operand_shapes, gy_shape)
    return norms.flops_gram((b, 1, p, d_in), (b, 1, p, d_out))


def _conv_flops_fused(operand_shapes, gy_shape):
    b, p, d_in, d_out = conv_norm_dims(operand_shapes, gy_shape)
    return norms.flops_fused((b, 1, p, d_in), (b, 1, p, d_out))


# conv "fused": the im2col view makes the conv site *exactly* a dense site,
# so the fused dense kernel applies: one sweep over the patch tensors
# yields the patch-space activation gradient AND the per-example norm²;
# dx is then the patches-extraction transpose (col2im, an XLA scatter) of
# that patch gradient, and dw the usual patch einsum (DCE'd in pass 1).
# conv_general_dilated_patches orders the feature axis Cin-major —
# (Cin, kh, kw) — so the flat weight view must match for y == pat @ wf.

def _conv_wflat(w):
    kh, kw, cin, cout = w.shape
    return w.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)


def _conv_rule_fused(spec, operands, gy):
    return _conv_rule_materialize(spec, operands, gy)


def _conv_kernel_fused(spec, operands, gy):
    from repro.kernels import ops as kops
    _, nsq = kops.dense_bwd_norm(*_conv_pair4(spec, operands, gy),
                                 _conv_wflat(operands[1]))
    return nsq


def _conv_fused_bwd(spec, operands, gy):
    x, w = operands
    if not spec.use_kernels:
        # identical XLA ops as the separate route: autodiff grads +
        # materialize rule (bit-identical to strategy="materialize")
        grads = _operand_grads(get_site(spec.kind), spec, operands, gy)
        return tuple(grads), _conv_rule_materialize(spec, operands, gy)
    from repro.kernels import ops as kops
    pat = _conv_patches(spec, x, w)
    # folded layout (see _conv_pair4): K views share an example row, so the
    # kernel's norm accumulates the K-averaged wgrad; the patch gradient is
    # layout-identical either way (G == 1 -> plain reshape)
    B, cout = x.shape[0] // spec.augmult, gy.shape[-1]
    pat4 = pat.reshape(B, 1, -1, pat.shape[-1])
    gy4 = gy.reshape(B, 1, -1, cout)
    gpat4, nsq = kops.dense_bwd_norm(pat4, gy4, _conv_wflat(w))
    _, pull = jax.vjp(lambda xx: _conv_patches(spec, xx, w), x)
    (gx,) = pull(gpat4.reshape(pat.shape).astype(pat.dtype))
    kh, kw, cin = w.shape[0], w.shape[1], w.shape[2]
    gwf = jnp.einsum("bpi,bpo->io", pat4[:, 0], gy4[:, 0])
    gw = gwf.reshape(cin, kh, kw, cout).transpose(1, 2, 0, 3).astype(w.dtype)
    return (gx.astype(x.dtype), gw), nsq


register_site("conv2d", fwd=_conv2d_fwd,
              nsq_rules={"materialize": _conv_rule_materialize,
                         "gram": _conv_rule_gram,
                         "fused": _conv_rule_fused},
              kernel_route={"materialize": _conv_kernel_materialize,
                            "gram": _conv_kernel_gram,
                            "fused": _conv_kernel_fused},
              fused_bwd={"fused": _conv_fused_bwd},
              flops={"materialize": _conv_flops_materialize,
                     "gram": _conv_flops_gram,
                     "fused": _conv_flops_fused})


# ---------------------------------------------------------------------------
# attention: parameter-free site carrying the fused flash-backward kernel
# ---------------------------------------------------------------------------
#
# Attention owns no parameters, so its per-example norm² contribution is
# *exactly zero* — registering it as a site changes no norm and trivially
# satisfies the masked-batch contract.  What the site buys is dataflow:
# under norm_strategy="fused" models/layers.py routes attention through
# here, and the backward dispatches to the Pallas flash-attention backward
# (kernels/flash_attn.py) that recomputes the (bq, bk) probability tiles
# online from the saved row logsumexp — no B×L×L materialization, no
# second pass — instead of the blocked-XLA autodiff backward.  Layouts:
# q (B, T, KV, rep, hd); k/v (B, S, KV, hd); meta = (causal, block_q,
# remat) mirroring models/layers.attn_apply.

def _attn_meta(spec):
    causal, block_q, remat = spec.meta if spec.meta else (True, 512, "block")
    return bool(causal), int(block_q), str(remat)


def _attention_fwd(spec, q, k, v):
    causal, block_q, remat = _attn_meta(spec)
    from repro.kernels import ops as kops
    if kops.USE_FLASH:
        from repro.dist import runtime
        flash = runtime.attn_local(
            lambda qq, kk, vv: kops.flash_attention(qq, kk, vv, causal),
            k.shape[2])
        return flash(q, k, v)
    from repro.models.layers import _causal_blocked_attention, _full_attention
    if not causal:
        return _full_attention(q, k, v)    # bidirectional (ViT) XLA path
    return _causal_blocked_attention(q, k, v, block_q, remat)


def _attention_rule_fused(spec, operands, gy):
    return jnp.zeros((operands[0].shape[0] // spec.augmult,), F32)


def _attention_fused_bwd(spec, operands, gy):
    q, k, v = operands
    causal, _, _ = _attn_meta(spec)
    nsq = jnp.zeros((q.shape[0] // spec.augmult,), F32)
    if spec.use_kernels:
        from repro.kernels import ops as kops
        dq, dk, dv = kops.flash_attention_bwd(q, k, v, gy, causal)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype)), nsq
    grads = _operand_grads(get_site(spec.kind), spec, operands, gy)
    return tuple(grads), nsq


def _attention_flops(operand_shapes, gy_shape):
    return 0.0     # no parameters -> no incremental norm-rule FLOPs


# rules consume nothing (norm² ≡ 0): nothing for the sites remat policy to
# save — q/k/v stay transient exactly as on the non-site attention path
register_site("attention", fwd=_attention_fwd,
              nsq_rules={"fused": _attention_rule_fused},
              fused_bwd={"fused": _attention_fused_bwd},
              flops={"fused": _attention_flops},
              save_operands=())


# ---------------------------------------------------------------------------
# bias: y = x + b, b broadcast over every non-channel dim
# ---------------------------------------------------------------------------

def _bias_fwd(spec, x, b):
    return x + b.astype(x.dtype)


def _bias_bwd(spec, operands, gy):
    x, b = operands
    gb = jnp.sum(gy, axis=tuple(range(gy.ndim - 1))).astype(b.dtype)
    return gy.astype(x.dtype), gb


def _bias_rule(spec, operands, gy):
    if spec.augmult > 1:
        # fold views into the (summed-over) position axis: per-example bias
        # grad = Σ over views and positions of the 1/K-scaled gy
        gy = gy.reshape((gy.shape[0] // spec.augmult, -1, gy.shape[-1]))
    return norms.bias_nsq(gy)


def _bias_flops(operand_shapes, gy_shape):
    n = 1
    for s in gy_shape:
        n *= int(s)
    return 2 * n


# the bias rule consumes only gy — nothing for the sites policy to save
register_site("bias", fwd=_bias_fwd, bwd=_bias_bwd,
              nsq_rules={"direct": _bias_rule},
              flops={"direct": _bias_flops},
              save_operands=())
