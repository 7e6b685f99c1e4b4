"""Sharding rules: logical param axes -> mesh PartitionSpecs.

The mesh axis vocabulary is fixed (launch/mesh.py):

* ``data`` (and ``pod`` when multi-pod) carry the **batch** dimension —
  DP-SGD is embarrassingly data-parallel up to the final clipped-gradient
  all-reduce, which GSPMD inserts from these specs.
* ``model`` carries one weight dimension per param, picked from the
  *logical* axis names attached to every param by the model spec
  (models/layers.py ``P``): ``expert`` (expert parallelism) is preferred,
  then ``heads``/``kv`` (Megatron-style attention TP), then ``mlp``,
  then ``vocab`` (parallel embedding/LM head).  A dim is only sharded when
  its size is divisible by the mesh axis size, else the rule falls through
  to the next candidate (e.g. grok's 8 experts on a 16-way model axis fall
  through to its 32768-wide ``mlp`` dim).

``fsdp=True`` (ZeRO-3-lite, per-arch ``use_fsdp``) additionally shards the
first remaining weight dim over ``data``; ``state_shardings(zero1=True)``
does the same for optimizer-state leaves only (ZeRO-1).

Everything here is shape arithmetic on ``mesh.axis_names`` /
``mesh.devices.shape`` — it never touches device state, so the rules are
unit-testable with a fake mesh (tests/test_costs_sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

# mesh axes that carry the batch dimension, outermost first
BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"
# pipeline-parallel axis: carries the scan-stacked "layers" dim, so each
# device group holds only its stage's contiguous layer slice — matching the
# stage-major execution order of transformer._blocks_pipelined (stage s owns
# layer groups [s·reps/S, (s+1)·reps/S))
STAGE_AXIS = "stage"
# logical-axis priority for the model mesh axis (first divisible match wins)
MODEL_PRIORITY = ("expert", "heads", "kv", "mlp", "vocab")
# logical axes never sharded over data/model (the scan-stacked layer dim is
# only ever sharded over the dedicated stage axis)
_NEVER_SHARD = ("layers",)


def mesh_from_config(cfg) -> Mesh:
    """Build a device mesh from a ``MeshConfig`` (configs/base.py)."""
    return make_mesh(cfg.shape, cfg.axes)


def _axis_size(mesh, name: str) -> int:
    """Size of a named mesh axis (1 if absent).  Works on any object with
    ``axis_names`` + ``devices.shape`` (real Mesh or a test fake)."""
    names = tuple(mesh.axis_names)
    if name not in names:
        return 1
    return int(mesh.devices.shape[names.index(name)])


def batch_axis_width(mesh) -> int:
    """Total device product of the mesh's batch-carrying axes — the
    divisor a physical batch size must satisfy for ``batch_pspec`` to use
    full data parallelism (launchers round Poisson padded capacities to a
    multiple of this; train/trainer.py ``physical_batch_size``)."""
    w = 1
    for a in BATCH_AXES:
        w *= _axis_size(mesh, a)
    return w


def stage_axis_width(mesh) -> int:
    """Device width of the pipeline ``stage`` axis (1 when absent).  The
    launcher validates this divides the model's ``pp_stages`` layer slices
    so each stage's params land wholly inside one stage device group."""
    return _axis_size(mesh, STAGE_AXIS)


def batch_pspec(mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the batch dim shards over: the ``BATCH_AXES`` subset (in
    order) with the largest device product that divides the batch — i.e.
    maximum data parallelism, dropping axes that don't fit (a 16-wide data
    axis beats pod+data when only one divides).  Returns None when nothing
    divides (e.g. batch 1 long-context decode)."""
    present = [a for a in BATCH_AXES if a in tuple(mesh.axis_names)]
    best: Tuple[str, ...] = ()
    best_prod = 1
    for mask in range(1, 2 ** len(present)):
        combo = tuple(a for i, a in enumerate(present) if mask >> i & 1)
        prod = 1
        for a in combo:
            prod *= _axis_size(mesh, a)
        if global_batch % prod == 0 and prod > best_prod:
            best, best_prod = combo, prod
    return best or None


def spec_for_param(axes: Sequence[Optional[str]], shape: Sequence[int],
                   mesh, fsdp: bool = False) -> P:
    """PartitionSpec for one param from its logical axes + shape.

    One dim gets the ``model`` mesh axis, chosen by ``MODEL_PRIORITY`` with
    divisibility fall-through; a ``layers`` dim (the scan-stacked block
    axis) is sharded over the ``stage`` axis when present and divisible —
    pipeline parallelism: each stage device group materializes only its
    contiguous layer slice; with ``fsdp`` the first remaining named dim
    divisible by the ``data`` axis is sharded over it.  Undivisible or
    unnamed dims stay replicated.
    """
    entries: list = [None] * len(shape)
    if STAGE_AXIS in tuple(mesh.axis_names):
        ssz = _axis_size(mesh, STAGE_AXIS)
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if ax == "layers" and dim % ssz == 0:
                entries[i] = STAGE_AXIS
                break
    if MODEL_AXIS in tuple(mesh.axis_names):
        msz = _axis_size(mesh, MODEL_AXIS)
        for logical in MODEL_PRIORITY:
            placed = False
            for i, (ax, dim) in enumerate(zip(axes, shape)):
                if ax == logical and dim % msz == 0:
                    entries[i] = MODEL_AXIS
                    placed = True
                    break
            if placed:
                break
    if fsdp and "data" in tuple(mesh.axis_names):
        dsz = _axis_size(mesh, "data")
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if (entries[i] is None and ax is not None
                    and ax not in _NEVER_SHARD and dim % dsz == 0):
                entries[i] = "data"
                break
    return P(*entries)


def _zip_spec_tree(shapes, axes, fn):
    """Map fn(ShapeDtypeStruct, logical_axes_tuple) over the parallel trees
    produced by ``model.abstract_params()`` / ``model.logical_axes()``.
    Recursion is guided by the *shapes* side so axes tuples (leaves) are
    never mistaken for containers."""
    if isinstance(shapes, dict):
        return {k: _zip_spec_tree(shapes[k], axes[k], fn) for k in shapes}
    if isinstance(shapes, (list, tuple)):
        out = [_zip_spec_tree(s, a, fn) for s, a in zip(shapes, axes)]
        return tuple(out) if isinstance(shapes, tuple) else out
    return fn(shapes, axes)


def param_shardings(mesh, model, fsdp: Optional[bool] = None):
    """NamedSharding tree for ``model``'s params.  ``fsdp=None`` uses the
    arch's ``use_fsdp`` flag; pass False to force it off (serving without
    FSDP, dryrun --no-serve-fsdp)."""
    if fsdp is None:
        fsdp = bool(getattr(model.arch, "use_fsdp", False))
    return _zip_spec_tree(
        model.abstract_params(), model.logical_axes(),
        lambda leaf, ax: NamedSharding(
            mesh, spec_for_param(ax, leaf.shape, mesh, fsdp=fsdp)))


def batch_shardings(mesh, abs_tree, global_batch: int):
    """NamedSharding tree for a batch pytree: dim 0 over the batch axes,
    everything else replicated."""
    bax = batch_pspec(mesh, global_batch)

    def mk(leaf):
        if bax is None or leaf.ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(bax, *(None,) * (leaf.ndim - 1)))

    return jax.tree.map(mk, abs_tree)


def state_shardings(mesh, model, state_abs, zero1: bool = True):
    """NamedSharding tree for a ``TrainState`` (train/state.py).

    Params follow ``param_shardings``.  Optimizer-state leaves that are
    param-shaped (m/v/master/momentum/error-feedback residuals) inherit the
    param's logical axes; with ``zero1`` they are additionally sharded over
    the ``data`` axis (ZeRO-1: grads are averaged over data anyway, so
    per-shard optimizer math is exact).  Unrecognized leaves (quantized
    8-bit moment blocks, scalars) stay replicated.
    """
    p_sh = param_shardings(mesh, model)

    # param pytree path -> (axes, shape).  Optimizer-state leaves are matched
    # by *path suffix* + shape, not shape alone: same-shape params routinely
    # differ in logical axes (wq/wk/wv vs wo whenever d_model == H*hd), and a
    # shape-keyed lookup would shard their moments on the transposed dim,
    # forcing a param<->state reshard every step.
    param_at: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            model.abstract_params())[0]:
        param_at[_norm_path(path)] = leaf.shape
    axes_at = {_norm_path(p): ax for p, ax in
               jax.tree_util.tree_flatten_with_path(
                   model.logical_axes(),
                   is_leaf=lambda x: isinstance(x, tuple)
                   and all(isinstance(a, (str, type(None))) for a in x))[0]}

    def opt_leaf(path, leaf):
        key = _norm_path(path)
        for n in range(len(key) - 1, 0, -1):     # longest param-path suffix
            suffix = key[-n:]
            if param_at.get(suffix) == tuple(leaf.shape):
                return NamedSharding(mesh, spec_for_param(
                    axes_at[suffix], leaf.shape, mesh, fsdp=zero1))
        return NamedSharding(mesh, P())

    return dataclasses.replace(
        state_abs,
        step=NamedSharding(mesh, P()),
        params=p_sh,
        opt_state=jax.tree_util.tree_map_with_path(
            opt_leaf, state_abs.opt_state))


def cache_shardings(mesh, cache_abs, global_batch: int):
    """NamedSharding tree for a ``model.init_cache`` abstract tree: the batch
    dim (dim 0 for prelude layers, dim 1 for the scan-stacked blocks, which
    carry a leading layer dim) over the batch axes; everything else
    replicated."""
    bax = batch_pspec(mesh, global_batch)

    def mk(path, leaf):
        if bax is None or leaf.ndim == 0:
            return NamedSharding(mesh, P())
        bdim = 1 if (path and getattr(path[0], "key", None) == "blocks"
                     and leaf.ndim > 1) else 0
        entries = [None] * leaf.ndim
        entries[bdim] = bax
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(mk, cache_abs)


def _norm_path(path) -> tuple:
    """Normalize a jax key path to hashable (str|int, ...) for comparison."""
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(int(k.idx))
        else:
            out.append(str(k))
    return tuple(out)
