"""Fused clip-scale + batch-reduce kernel (vanilla DP-SGD post-processing,
Algorithm 1 lines 23–24).

Computes  out = Σ_b c_b · g_b  over per-example gradients g: (B, N) without
materializing the clipped copies ḡ_b in HBM — each (bb, bn) tile is scaled
by its clip factors and accumulated into the output tile in VMEM.  This is
the kernel DiVa's PPU datapath performs between the GEMM engine drain and
the DRAM writeback.

Layouts obey the TPU tiling rule (no rank-1 blocks): the clip factors enter
as a (B, 1) column and the reduction leaves as a (1, N) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(g_ref, c_ref, out_ref):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(F32)           # (bb, bn)
    c = c_ref[...].astype(F32)           # (bb, 1)
    out_ref[...] += jnp.sum(g * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bb", "bn", "interpret"))
def clip_reduce(g: jax.Array, c: jax.Array, *, bb: int = 8, bn: int = 1024,
                interpret: bool = True) -> jax.Array:
    """g: (B, N) per-example grads, c: (B,) clip factors -> (N,) f32."""
    B, N = g.shape
    bb = min(bb, _rup(B, 8))
    bn = min(bn, _rup(N, 128))
    Bp, Np = _rup(B, bb), _rup(N, bn)
    gp = jnp.pad(g, ((0, Bp - B), (0, Np - N)))
    cp = jnp.pad(c, (0, Bp - B))[:, None]
    out = pl.pallas_call(
        _kernel,
        grid=(Np // bn, Bp // bb),
        in_specs=[
            pl.BlockSpec((bb, bn), lambda n, b: (b, n)),
            pl.BlockSpec((bb, 1), lambda n, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda n, b: (0, n)),
        out_shape=jax.ShapeDtypeStruct((1, Np), F32),
        interpret=interpret,
        name="clip_reduce_kernel",
    )(gp, cp)
    return out[0, :N]


def _rup(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
