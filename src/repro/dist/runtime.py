"""Batch-local execution: run per-example ops under ``shard_map``.

Why this exists: under plain GSPMD, scatter/segment ops whose result is
purely per-example (MoE sort-based dispatch, the embedding sort+segment-sum
norm rule) get partitioned conservatively — XLA replicates the scatter and
all-reduces a full-tensor result.  Wrapping just those ops in ``shard_map``
over the batch axes makes them provably local: each device runs the op on
its batch shard and no collective is emitted.  DP-SGD makes this safe by
construction — every quantity the norm side-channel produces is per-example
until the final clipped-gradient sum, which is a plain ``psum``.

The layout is ambient, not threaded through call sites: launchers activate
``layout(mesh, batch_axes)`` around tracing, and ``batch_local`` /
``attn_local`` become identity wrappers when no layout is active, so the
same model code runs single-device (tests, quickstart) and sharded
(launch/dryrun.py --local-ops) unchanged.

Exactness contract (tests/test_dist_runtime.py): for any per-example
``fn``, ``batch_local(fn, n)`` under an active layout equals the plain call
to float tolerance; with ``reduce_out=True`` the outputs are ``psum``-med
over the batch axes — the cross-device aggregation DP-SGD's
clip -> noise -> average step needs to be exact under data parallelism.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as _sh


class _Layout(threading.local):
    def __init__(self):
        self.mesh = None
        self.batch_axes: Optional[Tuple[str, ...]] = None


_ACTIVE = _Layout()


@contextlib.contextmanager
def layout(mesh, batch_axes):
    """Activate batch-local execution while tracing: inside this context,
    ``batch_local``-wrapped ops run under shard_map with arg dim 0 sharded
    over ``batch_axes``.  A falsy ``batch_axes`` (batch not shardable) is a
    no-op, so ``layout(mesh, batch_pspec(mesh, B))`` is always safe."""
    if not batch_axes:
        yield
        return
    prev = (_ACTIVE.mesh, _ACTIVE.batch_axes)
    _ACTIVE.mesh, _ACTIVE.batch_axes = mesh, tuple(batch_axes)
    try:
        yield
    finally:
        _ACTIVE.mesh, _ACTIVE.batch_axes = prev


def active() -> Optional[Tuple]:
    """The ambient (mesh, batch_axes), or None outside any ``layout``."""
    if _ACTIVE.mesh is None:
        return None
    return _ACTIVE.mesh, _ACTIVE.batch_axes


def _n_shards(mesh, bax) -> int:
    n = 1
    for a in bax:
        n *= _sh._axis_size(mesh, a)
    return n


def batch_local(fn: Callable, n_batch_args: int,
                reduce_out: bool = False) -> Callable:
    """Wrap ``fn`` to run batch-locally under the ambient layout.

    The first ``n_batch_args`` positional args are sharded on dim 0 over the
    batch axes; any remaining args are replicated.  Outputs are batch-sharded
    on dim 0, or ``psum``-med over the batch axes when ``reduce_out`` (for
    cross-device sums such as the clipped-gradient reduction).  Outside a
    layout — or when the call's batch dim doesn't divide across the shards,
    e.g. a gradient-accumulation microbatch — this is ``fn`` itself.
    """
    state = active()
    if state is None:
        return fn
    mesh, bax = state
    n_shards = _n_shards(mesh, bax)

    def wrapped(*args):
        if args[0].shape[0] % n_shards:
            return fn(*args)
        in_specs = tuple(
            P(bax, *(None,) * (a.ndim - 1)) if i < n_batch_args else P()
            for i, a in enumerate(args))
        out_abs = jax.eval_shape(fn, *args)
        if reduce_out:
            out_specs = jax.tree.map(lambda s: P(), out_abs)

            def inner(*a):
                return jax.tree.map(lambda y: jax.lax.psum(y, bax), fn(*a))
        else:
            out_specs = jax.tree.map(
                lambda s: P() if s.ndim == 0
                else P(bax, *(None,) * (s.ndim - 1)), out_abs)
            inner = fn
        return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    return wrapped


def init_fingerprint(params) -> int:
    """Deterministic crc32 fingerprint of a param tree.

    Reuses the process-stable crc32 path keying that seeds init
    (models/transformer.py ``path_key``: crc32, never ``hash()``, which is
    salted per process): every leaf contributes crc32 of its path chained
    with its shape/dtype record, and — when the leaf's data is fully
    addressable from this process (single-process, or replicated shards)
    — the raw bytes.  Partially-addressable leaves (cross-process sharded)
    contribute structure only: the bytes live on other hosts, and the
    structural drift this check exists to catch (a host building a
    different tree, shape, dtype or path set from the "same" config/seed)
    is visible without them."""
    import zlib

    import numpy as np

    total = 0
    for path, leaf in sorted(
            jax.tree_util.tree_flatten_with_path(params)[0],
            key=lambda kv: str(kv[0])):
        rec = f"{jax.tree_util.keystr(path)}:{tuple(leaf.shape)}:{leaf.dtype}"
        c = zlib.crc32(rec.encode())
        if not isinstance(leaf, jax.Array) or leaf.is_fully_addressable:
            c = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(), c)
        total = zlib.crc32(c.to_bytes(4, "little"), total)
    return total & 0xFFFFFFFF


def verify_init_consistency(params, tag: str = "init") -> int:
    """Multi-process init verification: every process fingerprints its view
    of ``params`` and the fingerprints are allgathered and compared —
    catching the classic multi-controller failure where one host inits
    from a different seed/config and GSPMD silently mixes the two.
    Single-process this is just the fingerprint (no collective).  Raises
    ``RuntimeError`` naming the disagreeing processes."""
    fp = init_fingerprint(params)
    if jax.process_count() > 1:
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        all_fp = multihost_utils.process_allgather(jnp.uint32(fp))
        import numpy as np
        vals = np.asarray(all_fp).reshape(-1)
        if not (vals == vals[0]).all():
            bad = {i: hex(int(v)) for i, v in enumerate(vals)}
            raise RuntimeError(
                f"{tag} fingerprint mismatch across processes: {bad} — "
                f"hosts disagree on the initialized state (seed/config "
                f"drift); refusing to train on silently mixed params")
    return fp


def attn_local(fn: Callable, n_kv: int) -> Callable:
    """Wrap a flash-attention call ``fn(q, k, v)`` (q: (B,T,KV,rep,hd),
    k/v: (B,S,KV,hd)) to run under shard_map: batch over the batch axes and,
    when the KV head count divides the ``model`` axis, heads over ``model``
    — so the Pallas kernel sees only its local (batch, head) tile.  Identity
    outside a layout."""
    state = active()
    if state is None:
        return fn
    mesh, bax = state
    n_shards = _n_shards(mesh, bax)
    kv_ax = None
    if _sh.MODEL_AXIS in tuple(mesh.axis_names):
        msz = _sh._axis_size(mesh, _sh.MODEL_AXIS)
        if msz > 1 and n_kv % msz == 0:
            kv_ax = _sh.MODEL_AXIS

    def wrapped(q, k, v):
        if q.shape[0] % n_shards:
            return fn(q, k, v)
        qs = P(bax, None, kv_ax, None, None)
        ks = P(bax, None, kv_ax, None)
        return jax.shard_map(fn, mesh=mesh, in_specs=(qs, ks, ks),
                             out_specs=qs, check_vma=False)(q, k, v)

    return wrapped
