"""Fused dense backward: activation gradient + per-example norm² in ONE
grid sweep — the DiVa dataflow proper (DESIGN.md §2, ROADMAP item 1).

``kernels/pegrad_norm.py`` computes ‖X_bᵀGY_b‖² as a *separate pass after*
the backward: XLA produces the activation gradient GX = GY·Wᵀ, then the
norm kernel re-reads X and GY from HBM.  DiVa's point is that the norm is
a by-product of tiles backprop already streams.  This kernel emits both in
a single sweep over the same (t, j) tiles:

    grid (BG, n_i, n_t, n_j), j innermost.  At cell (b, i, t, j):
      gx_acc(bt, bi)  += GY[t,j] · W[i,j]ᵀ          (dgrad term)
      slab(bi, j·bj:) += X[t,i]ᵀ · GY[t,j]          (wgrad tile column)
    j == n_j-1            -> write gx block (b, t, i)   [visited once]
    t == n_t-1, j == n_j-1 -> nsq[b] += Σ slab²         [i-th row strip of
                                                         ‖G_b‖²_F done]

X and GY are read **once** (pegrad alone re-reads both), the per-example
weight gradient G_b never reaches HBM (only its running squared-Frobenius
reduction, B scalars), and there is no second kernel launch.  The summed
weight gradient is *not* produced here on purpose: in DP-SGD(R) pass 1 the
parameter cotangents are discarded, so keeping gw an XLA einsum outside
the kernel lets dead-code elimination remove it (core/context.py).

Output-revisit discipline (valid on real TPUs, not just interpret mode):
the gx block (b, t, i) is written exactly once; the nsq block (b,) is
revisited only across the contiguous (i, t, j) inner loops of a fixed b.
The nsq output is lane-dense, (BG, 1, 128) with every lane holding the
running sum (the TPU tiling rule admits no (1,) block); the wrapper reads
lane 0.

VMEM budget: the slab holds one (bi, do_pad) f32 row strip of G_b —
``bi * do_pad * 4`` bytes (4 MB at bi=128, do=8192), beside the (bt, bi)
gx accumulator.  For wider layers shrink ``bi``; the norm is exact for any
tiling.

``dense_dgrad`` below is the same dgrad loop *without* the norm slab — the
separate-pass baseline (dgrad kernel + pegrad_norm kernel, two launches)
that benchmarks/kernel_bench.py times the fusion against.

Grouped weights (moe_dense): pass w as (E, di, do); row b of x uses group
``b % E`` — matching ``x4.reshape(B*E, C, di)`` row order.  Plain dense is
E = 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
LANES = 128   # lane width of a TPU vreg: per-example scalars go out lane-dense


def _fused_kernel(x_ref, gy_ref, w_ref, gx_ref, nsq_ref, gxacc_ref, slab_ref,
                  *, bj: int, n_t: int, n_j: int):
    i, t, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = jnp.logical_and(t == 0, j == 0)

    @pl.when(jnp.logical_and(i == 0, first))
    def _init_nsq():
        nsq_ref[...] = jnp.zeros_like(nsq_ref)

    @pl.when(j == 0)
    def _init_gx():
        gxacc_ref[...] = jnp.zeros_like(gxacc_ref)

    @pl.when(first)
    def _init_slab():
        slab_ref[...] = jnp.zeros_like(slab_ref)

    x = x_ref[0]                     # (bt, bi)
    gy = gy_ref[0]                   # (bt, bj)
    w = w_ref[0]                     # (bi, bj)

    # dgrad: gx tile accumulates GY · Wᵀ over the j sweep
    gxacc_ref[...] += jax.lax.dot_general(
        gy, w, (((1,), (1,)), ((), ())), preferred_element_type=F32)
    # wgrad row strip: the (bi, bj) tile of G_b = XᵀGY, j-th column block
    slab_ref[:, pl.ds(j * bj, bj)] += jax.lax.dot_general(
        x, gy, (((0,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(j == n_j - 1)
    def _drain_gx():
        gx_ref[0] = gxacc_ref[...].astype(gx_ref.dtype)

    @pl.when(jnp.logical_and(t == n_t - 1, j == n_j - 1))
    def _drain_nsq():                # the PPU: reduce the finished strip
        g = slab_ref[...]
        nsq_ref[...] += jnp.sum(g * g)


def _dgrad_kernel(gy_ref, w_ref, gx_ref, gxacc_ref, *, n_j: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        gxacc_ref[...] = jnp.zeros_like(gxacc_ref)

    gxacc_ref[...] += jax.lax.dot_general(
        gy_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=F32)

    @pl.when(j == n_j - 1)
    def _drain():
        gx_ref[0] = gxacc_ref[...].astype(gx_ref.dtype)


def _tiles(T, di, do, bt, bi, bj):
    bt = min(bt, _rup(T, 8))
    bi = min(bi, _rup(di, 128))
    bj = min(bj, _rup(do, 128))
    return bt, bi, bj


@functools.partial(jax.jit, static_argnames=("bt", "bi", "bj", "interpret"))
def dense_bwd_norm(x: jax.Array, gy: jax.Array, w: jax.Array, *,
                   bt: int = 128, bi: int = 128, bj: int = 128,
                   interpret: bool = True):
    """x: (BG, T, di), gy: (BG, T, do), w: (E, di, do) with row b using
    group ``b % E`` -> (gx (BG, T, di) x.dtype, nsq (BG,) f32).

    ``gx = gy @ w[b % E]ᵀ`` and ``nsq_b = ‖x_bᵀ gy_b‖²_F`` from one fused
    sweep.  Shapes are padded to tile multiples (zero padding changes
    neither output).  All-zero gy rows yield exact-zero gx rows and an
    exact-zero norm² (the masked-Poisson contract).
    """
    BG, T, di = x.shape
    do = gy.shape[-1]
    E = w.shape[0]
    bt, bi, bj = _tiles(T, di, do, bt, bi, bj)
    xp = _pad3(x, bt, bi)
    gyp = _pad3(gy, bt, bj)
    wp = _padw(w, bi, bj)
    Tp, dip, dop = xp.shape[1], xp.shape[2], gyp.shape[2]
    n_t, n_i, n_j = Tp // bt, dip // bi, dop // bj

    gx, nsq = pl.pallas_call(
        functools.partial(_fused_kernel, bj=bj, n_t=n_t, n_j=n_j),
        grid=(BG, n_i, n_t, n_j),
        in_specs=[
            pl.BlockSpec((1, bt, bi), lambda b, i, t, j: (b, t, i)),
            pl.BlockSpec((1, bt, bj), lambda b, i, t, j: (b, t, j)),
            pl.BlockSpec((1, bi, bj), lambda b, i, t, j: (b % E, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bi), lambda b, i, t, j: (b, t, i)),
            pl.BlockSpec((1, 1, LANES), lambda b, i, t, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BG, Tp, dip), x.dtype),
            jax.ShapeDtypeStruct((BG, 1, LANES), F32),
        ],
        scratch_shapes=[_vmem((bt, bi), F32), _vmem((bi, dop), F32)],
        interpret=interpret,
    )(xp, gyp, wp)
    return gx[:, :T, :di], nsq[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("bt", "bi", "bj", "interpret"))
def dense_dgrad(gy: jax.Array, w: jax.Array, *, bt: int = 128, bi: int = 128,
                bj: int = 128, interpret: bool = True) -> jax.Array:
    """gy: (BG, T, do), w: (E, di, do) -> gx (BG, T, di) = gy @ w[b % E]ᵀ.

    The dgrad half alone — paired with ``pegrad_norm`` it forms the
    two-launch separate-pass baseline for the fusion benchmark."""
    BG, T, do = gy.shape
    E, di = w.shape[0], w.shape[1]
    bt, bi, bj = _tiles(T, di, do, bt, bi, bj)
    gyp = _pad3(gy, bt, bj)
    wp = _padw(w, bi, bj)
    Tp, dip, dop = gyp.shape[1], wp.shape[1], gyp.shape[2]
    n_t, n_i, n_j = Tp // bt, dip // bi, dop // bj

    gx = pl.pallas_call(
        functools.partial(_dgrad_kernel, n_j=n_j),
        grid=(BG, n_i, n_t, n_j),
        in_specs=[
            pl.BlockSpec((1, bt, bj), lambda b, i, t, j: (b, t, j)),
            pl.BlockSpec((1, bi, bj), lambda b, i, t, j: (b % E, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bt, bi), lambda b, i, t, j: (b, t, i)),
        out_shape=jax.ShapeDtypeStruct((BG, Tp, dip), gy.dtype),
        interpret=interpret,
        scratch_shapes=[_vmem((bt, bi), F32)],
    )(gyp, wp)
    return gx[:, :T, :di]


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


def _padw(w: jax.Array, bi: int, bj: int) -> jax.Array:
    E, di, do = w.shape
    dip, dop = _rup(di, bi), _rup(do, bj)
    if (dip, dop) == (di, do):
        return w
    return jnp.pad(w, ((0, 0), (0, dip - di), (0, dop - do)))
