"""Fused per-example weight-gradient norm — the DiVa outer-product engine +
PPU, adapted to the TPU MXU (DESIGN.md §2).

For each example (row of the leading BG dim) the kernel forms the
per-example weight gradient G_b = X_bᵀ · GY_b **tile by tile in VMEM** —
an output-stationary outer-product accumulation over the T (sequence)
dimension, exactly DiVa's dataflow — and reduces each finished (di, do)
tile to a squared-Frobenius partial sum on the spot.  The weight-shaped
G_b never reaches HBM: the only HBM traffic is reading X/GY once and
writing B scalars (the paper's "99% reduction in off-chip data movement
during gradient post-processing").

Grid: (BG, n_di, n_do, n_t) with t innermost so the VMEM accumulator tile
is live across exactly the t-loop.  Block shapes are MXU-aligned
(128 lanes; t-tile a multiple of 8).  Each example's norm² is written
lane-dense into a (1, 1, 128) output block (every lane holds the same
partial sum), because the TPU tiling rule admits no (1,) block over a
(BG,) array; the wrapper reads lane 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
LANES = 128   # lane width of a TPU vreg: per-example scalars go out lane-dense


def _kernel(x_ref, gy_ref, out_ref, acc_ref, *, n_t: int, n_i: int, n_j: int):
    i, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, j == 0), t == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # outer-product accumulation: (bt, di)ᵀ @ (bt, do) -> (di, do) in VMEM
    x = x_ref[0]                     # (bt, di)
    gy = gy_ref[0]                   # (bt, do)
    acc_ref[...] += jax.lax.dot_general(
        x, gy, (((0,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(t == n_t - 1)
    def _drain():                    # the PPU: reduce the finished tile
        g = acc_ref[...]
        out_ref[...] += jnp.sum(g * g)


@functools.partial(jax.jit, static_argnames=("bt", "bi", "bj", "interpret"))
def pegrad_norm(x: jax.Array, gy: jax.Array, *, bt: int = 128, bi: int = 128,
                bj: int = 128, interpret: bool = True) -> jax.Array:
    """x: (BG, T, di), gy: (BG, T, do) -> (BG,) f32 ‖X_bᵀGY_b‖²_F.

    Shapes are padded to tile multiples (zero padding does not change the
    norm).  ``interpret=True`` executes the kernel body on CPU; on a real
    TPU pass ``interpret=False``.
    """
    BG, T, di = x.shape
    do = gy.shape[-1]
    bt, bi, bj = min(bt, _rup(T, 8)), min(bi, _rup(di, 128)), min(bj, _rup(do, 128))
    xp = _pad3(x, bt, bi)
    gyp = _pad3(gy, bt, bj)
    Tp, dip, dop = xp.shape[1], xp.shape[2], gyp.shape[2]
    n_t, n_i, n_j = Tp // bt, dip // bi, dop // bj

    out = pl.pallas_call(
        functools.partial(_kernel, n_t=n_t, n_i=n_i, n_j=n_j),
        grid=(BG, n_i, n_j, n_t),
        in_specs=[
            pl.BlockSpec((1, bt, bi), lambda b, i, j, t: (b, t, i)),
            pl.BlockSpec((1, bt, bj), lambda b, i, j, t: (b, t, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda b, i, j, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BG, 1, LANES), F32),
        scratch_shapes=[_vmem((bi, bj), F32)],
        interpret=interpret,
    )(xp, gyp)
    return out[:, 0, 0]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _rup(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad3(a: jax.Array, bt: int, bd: int) -> jax.Array:
    BG, T, d = a.shape
    Tp, dp = _rup(T, bt), _rup(d, bd)
    if (Tp, dp) == (T, d):
        return a
    return jnp.pad(a, ((0, 0), (0, Tp - T), (0, dp - d)))
