"""Fused ghost-norm (Gram) kernel — the long-sequence variant of the DiVa
PPU fusion (DESIGN.md §2/§3).

Computes  n_b = Σ_{t,s} (x_t·x_s)(gy_t·gy_s) [· mask(t,s)]  without ever
materializing the (T, T) Gram matrices in HBM: one (bt, bs) tile of each
Gram lives in VMEM, accumulated over d-chunks on the MXU, multiplied
elementwise and reduced to a scalar on the spot.  The optional id mask
(equal-token-id pairs) makes the same kernel compute exact embedding-table
per-example norms under repeated tokens.

Grid: (BG, n_t, n_s, n_d) with d innermost (Gram accumulation), using
symmetry: tiles with s > t are skipped at the index level by mapping them
to the (t, t) diagonal tile and masking — off-diagonal tiles are counted
twice via a factor-2 weight, halving FLOPs vs the naive sweep.

Layouts obey the TPU tiling rule (no rank-1 or (1, bt) blocks): the ids
enter twice, as a (BG, T, 1) column for the t side and a (BG, 1, T) row for
the s side, so the (bt, bs) id mask is a broadcast compare with no
in-kernel transpose; each example's norm is written lane-dense into a
(1, 1, 128) output block and read back from lane 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
LANES = 128   # lane width of a TPU vreg: per-example scalars go out lane-dense


def _kernel(xt_ref, xs_ref, gt_ref, gs_ref, idt_ref, ids_ref, out_ref,
            a_ref, c_ref, *, n_d: int, use_mask: bool, square: bool):
    t, s, d = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(jnp.logical_and(t == 0, s == 0), d == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(d == 0)
    def _init_acc():
        a_ref[...] = jnp.zeros_like(a_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    skip = s > t  # symmetric: strictly-upper tiles contribute via factor 2

    @pl.when(jnp.logical_not(skip))
    def _acc():
        gt = gt_ref[0]
        gs = gs_ref[0]
        c_ref[...] += jax.lax.dot_general(
            gt, gs, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        if square:
            xt = xt_ref[0]               # (bt, bd)
            xs = xs_ref[0]               # (bs, bd)
            a_ref[...] += jax.lax.dot_general(
                xt, xs, (((1,), (1,)), ((), ())), preferred_element_type=F32)

    @pl.when(jnp.logical_and(d == n_d - 1, jnp.logical_not(skip)))
    def _drain():
        prod = a_ref[...] * c_ref[...] if square else c_ref[...]
        if use_mask:
            m = idt_ref[0] == ids_ref[0]     # (bt, 1) == (1, bs)
            prod = jnp.where(m, prod, 0.0)
        w = jnp.where(s == t, 1.0, 2.0)  # off-diagonal tiles counted twice
        out_ref[...] += w * jnp.sum(prod)


@functools.partial(jax.jit,
                   static_argnames=("bt", "bd", "interpret", "square"))
def gram_norm(x: jax.Array, gy: jax.Array, mask_ids: jax.Array | None = None,
              *, bt: int = 128, bd: int = 512,
              interpret: bool = True, square: bool = True) -> jax.Array:
    """x: (BG, T, di), gy: (BG, T, do) -> (BG,) f32 ghost norms.

    square=True  -> Σ (x_t·x_s)(gy_t·gy_s)       (dense ghost norm)
    square=False -> Σ (gy_t·gy_s)                (embedding rule; x unused)
    mask_ids: optional (BG, T) int ids; only equal-id pairs contribute
    (embedding-table rule).  Zero-padding of T/d is norm-neutral because
    padded gy rows are zero.
    """
    BG, T, di = x.shape
    do = gy.shape[-1]
    bt = min(bt, _rup(T, 8))
    xp = _pad_t(x, bt)
    gyp = _pad_t(gy, bt)
    Tp = xp.shape[1]
    bdx, bdg = min(bd, _rup(di, 128)), min(bd, _rup(do, 128))
    xp = _pad_d(xp, bdx)
    gyp = _pad_d(gyp, bdg)
    # unify d chunk count: pad both to the same number of chunks
    n_dx, n_dg = xp.shape[2] // bdx, gyp.shape[2] // bdg
    n_d = max(n_dx, n_dg)
    xp = _pad_chunks(xp, bdx, n_d)
    gyp = _pad_chunks(gyp, bdg, n_d)
    n_t = Tp // bt

    use_mask = mask_ids is not None
    if use_mask:
        ids = _pad_ids(mask_ids, bt, sentinel=-1)
    else:
        ids = jnp.zeros((BG, Tp), jnp.int32)
    ids_col, ids_row = ids[:, :, None], ids[:, None, :]

    out = pl.pallas_call(
        functools.partial(_kernel, n_d=n_d, use_mask=use_mask, square=square),
        grid=(BG, n_t, n_t, n_d),
        in_specs=[
            pl.BlockSpec((1, bt, bdx), lambda b, t, s, d: (b, t, d)),
            pl.BlockSpec((1, bt, bdx), lambda b, t, s, d: (b, jnp.minimum(s, t), d)),
            pl.BlockSpec((1, bt, bdg), lambda b, t, s, d: (b, t, d)),
            pl.BlockSpec((1, bt, bdg), lambda b, t, s, d: (b, jnp.minimum(s, t), d)),
            pl.BlockSpec((1, bt, 1), lambda b, t, s, d: (b, t, 0)),
            pl.BlockSpec((1, 1, bt),
                         lambda b, t, s, d: (b, 0, jnp.minimum(s, t))),
        ],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda b, t, s, d: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BG, 1, LANES), F32),
        scratch_shapes=[_vmem((bt, bt), F32), _vmem((bt, bt), F32)],
        interpret=interpret,
        name="gram_norm_kernel",
    )(xp, xp, gyp, gyp, ids_col, ids_row)
    return out[:, 0, 0]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _rup(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_t(a, bt):
    BG, T, d = a.shape
    Tp = _rup(T, bt)
    return a if Tp == T else jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0)))


def _pad_d(a, bd):
    BG, T, d = a.shape
    dp = _rup(d, bd)
    return a if dp == d else jnp.pad(a, ((0, 0), (0, 0), (0, dp - d)))


def _pad_chunks(a, bd, n_d):
    BG, T, d = a.shape
    want = bd * n_d
    return a if d == want else jnp.pad(a, ((0, 0), (0, 0), (0, want - d)))


def _pad_ids(ids, bt, sentinel):
    BG, T = ids.shape
    Tp = _rup(T, bt)
    if Tp == T:
        return ids.astype(jnp.int32)
    return jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, Tp - T)),
                   constant_values=sentinel)
