"""Flash attention (forward) as a Pallas TPU kernel.

Causal online-softmax attention with the score matrix resident in VMEM —
the (bq, bk) tile is produced on the MXU, folded into the running
(m, l, acc) state, and never written to HBM.  This removes the dominant
HBM-traffic term of the blocked-XLA attention (EXPERIMENTS.md §Perf) and,
on real TPUs, `pl.when`-predicated fully-masked tiles skip their DMA+MXU
work, halving causal FLOPs.

Two backward passes coexist behind ``ops.flash_attention``'s custom_vjp:
the original blocked pure-jnp recompute (exact, memory-bounded, default),
and the Pallas kernels below (``flash_attn_bwd``) — the fused DP route.
Both recompute the (bq, bk) probability tile online from the saved row
logsumexp; the Pallas pair keeps it in VMEM and is what the ``"fused"``
norm strategy's attention site dispatches to (core/sites.py), since
attention itself is parameter-free and contributes an exact zero to the
per-example norm² side-channel.

Layouts obey the TPU tiling rule: the row statistics (logsumexp, and delta
in the backward) travel as (BH, 1, T) rows in (1, 1, bq) blocks, and the
forward's running max/sum live in 2-D (bq, 1) scratch.  The forward turns
its (bq, 1) logsumexp column into the output row with a diagonal select and
a sublane sum (exact: one nonzero per column).  The backward kernels work
on transposed (bk, bq) score tiles, so the rows broadcast as they are and
no tile is ever transposed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, seq_k: int, causal: bool,
            scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    run = jnp.logical_or(not causal, ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0]                                     # (bq, hd)
        k = k_ref[0]                                     # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        mask = kpos < seq_k                              # padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=F32))
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _drain():
        l = jnp.maximum(l_ref[...], 1e-30)               # (bq, 1)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(m_ref[...] + jnp.log(l))


def _col_to_row(col):
    """(n, 1) -> (1, n) without a transpose: select the diagonal of the
    column's broadcast and sum over sublanes."""
    n = col.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("causal", "rep", "bq", "bk", "interpret"))
def flash_attn_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, rep: int = 1, bq: int = 128,
                   bk: int = 128, interpret: bool = True):
    """q: (BH, T, hd); k/v: (BH // rep, S, hd) (GQA: rep query heads share
    one kv head — handled by index mapping, never materialized).  Returns
    (o (BH,T,hd), lse (BH,T) f32 row logsumexp).  Tiles padded internally.
    """
    BH, T, hd = q.shape
    S = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    bq = min(bq, _rup(T, 8))
    bk = min(bk, _rup(S, 8))
    hdp = _rup(hd, 128)
    qp = _pad(q, _rup(T, bq), hdp)
    kp = _pad(k, _rup(S, bk), hdp)
    vp = _pad(v, _rup(S, bk), hdp)
    Tp, Sp = qp.shape[1], kp.shape[1]
    n_q, n_k = Tp // bq, Sp // bk

    o, lse = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_k=n_k, seq_k=S,
                          causal=causal, scale=scale),
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hdp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hdp), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bk, hdp), lambda b, i, j: (b // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hdp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, hdp), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tp), F32),
        ],
        scratch_shapes=[_vmem((bq, 1), F32), _vmem((bq, 1), F32),
                        _vmem((bq, hdp), F32)],
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :T, :hd], lse[:, 0, :T]


# ---------------------------------------------------------------------------
# backward: dk/dv kernel (k-stationary) + dq kernel (q-stationary)
# ---------------------------------------------------------------------------
#
# Standard flash backward from the saved row logsumexp:
#   p  = exp(s - lse),  ds = p ∘ (do·vᵀ - delta) · scale,  delta = Σ do∘o.
# The p/ds tiles live only in VMEM, transposed to (bk, bq) so the (1, bq)
# lse/delta rows broadcast over them — no B×L×L materialization and no
# second pass over the scores.  Masking: key-side padding and causality
# are folded into s (as in the forward); query-side padding columns are
# zeroed on pᵀ directly (their lse slots are meaningless, so exp(s - lse)
# must not feed the accumulators).  All-zero do rows (masked Poisson examples)
# annihilate delta, dp, ds and hence all three gradients exactly.


def _p_ds_t(q, k, v, do, lse, delta, qi, ki, *, bq, bk, seq_q, seq_k,
            causal, scale):
    """The shared tile recompute, transposed: (pᵀ, dsᵀ) as (bk, bq) tiles,
    query-padding columns zeroed.  ``lse``/``delta`` are (1, bq) rows."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    mask = kpos < seq_k
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    s = jnp.where(mask, s, NEG)
    p = jnp.where(qpos < seq_q, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)
    ds = p * (dp - delta) * scale
    return p, ds


def _bwd_kv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dk_ref, dv_ref, dkacc_ref, dvacc_ref, *, bq: int, bk: int,
                   n_q: int, seq_q: int, seq_k: int, causal: bool,
                   scale: float):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dkacc_ref[...] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[...] = jnp.zeros_like(dvacc_ref)

    run = jnp.logical_or(not causal, ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q, do = q_ref[0], do_ref[0]
        pt, dst = _p_ds_t(q, k_ref[0], v_ref[0], do, lse_ref[0],
                          delta_ref[0], qi, ki, bq=bq, bk=bk, seq_q=seq_q,
                          seq_k=seq_k, causal=causal, scale=scale)
        dvacc_ref[...] += jax.lax.dot_general(
            pt, do.astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        dkacc_ref[...] += jax.lax.dot_general(
            dst, q.astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)

    @pl.when(qi == n_q - 1)
    def _drain():
        dk_ref[0] = dkacc_ref[...]
        dv_ref[0] = dvacc_ref[...]


def _bwd_q_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                  dq_ref, dqacc_ref, *, bq: int, bk: int, n_k: int,
                  seq_q: int, seq_k: int, causal: bool, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dqacc_ref[...] = jnp.zeros_like(dqacc_ref)

    run = jnp.logical_or(not causal, ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        k = k_ref[0]
        _, dst = _p_ds_t(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0],
                         delta_ref[0], qi, ki, bq=bq, bk=bk, seq_q=seq_q,
                         seq_k=seq_k, causal=causal, scale=scale)
        dqacc_ref[...] += jax.lax.dot_general(
            dst, k.astype(F32), (((0,), (0,)), ((), ())),
            preferred_element_type=F32)

    @pl.when(ki == n_k - 1)
    def _drain():
        dq_ref[0] = dqacc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("causal", "rep", "bq", "bk", "interpret"))
def flash_attn_bwd(q: jax.Array, k: jax.Array, v: jax.Array, o: jax.Array,
                   lse: jax.Array, do: jax.Array, *, causal: bool = True,
                   rep: int = 1, bq: int = 128, bk: int = 128,
                   interpret: bool = True):
    """q/o/do: (BH, T, hd); k/v: (BH // rep, S, hd); lse: (BH, T) f32 from
    ``flash_attn_fwd``.  Returns f32 (dq (BH,T,hd), dk, dv (BH//rep,S,hd));
    GQA partial dk/dv are computed per query head and rep-summed here.
    """
    BH, T, hd = q.shape
    S = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)      # (BH, T)
    bq = min(bq, _rup(T, 8))
    bk = min(bk, _rup(S, 8))
    hdp = _rup(hd, 128)
    qp, dop = _pad(q, _rup(T, bq), hdp), _pad(do, _rup(T, bq), hdp)
    kp, vp = _pad(k, _rup(S, bk), hdp), _pad(v, _rup(S, bk), hdp)
    Tp, Sp = qp.shape[1], kp.shape[1]
    lsep = _pad2(lse.astype(F32), Tp)[:, None, :]       # (BH, 1, Tp) rows
    deltap = _pad2(delta, Tp)[:, None, :]
    n_q, n_k = Tp // bq, Sp // bk
    kw = dict(bq=bq, bk=bk, seq_q=T, seq_k=S, causal=causal, scale=scale)

    qspec = pl.BlockSpec((1, bq, hdp), lambda b, x, y: (b, y, 0))
    rspec = pl.BlockSpec((1, 1, bq), lambda b, x, y: (b, 0, y))
    kspec = pl.BlockSpec((1, bk, hdp), lambda b, x, y: (b // rep, x, 0))
    dkh, dvh = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, n_q=n_q, **kw),
        grid=(BH, n_k, n_q),
        in_specs=[qspec, qspec, rspec, rspec, kspec, kspec],
        out_specs=[pl.BlockSpec((1, bk, hdp), lambda b, x, y: (b, x, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((BH, Sp, hdp), F32)] * 2,
        scratch_shapes=[_vmem((bk, hdp), F32)] * 2,
        interpret=interpret,
    )(qp, dop, lsep, deltap, kp, vp)

    qspec2 = pl.BlockSpec((1, bq, hdp), lambda b, x, y: (b, x, 0))
    rspec2 = pl.BlockSpec((1, 1, bq), lambda b, x, y: (b, 0, x))
    kspec2 = pl.BlockSpec((1, bk, hdp), lambda b, x, y: (b // rep, y, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, n_k=n_k, **kw),
        grid=(BH, n_q, n_k),
        in_specs=[qspec2, qspec2, rspec2, rspec2, kspec2, kspec2],
        out_specs=pl.BlockSpec((1, bq, hdp), lambda b, x, y: (b, x, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tp, hdp), F32),
        scratch_shapes=[_vmem((bq, hdp), F32)],
        interpret=interpret,
    )(qp, dop, lsep, deltap, kp, vp)

    dk = dkh[:, :S, :hd].reshape(BH // rep, rep, S, hd).sum(axis=1)
    dv = dvh[:, :S, :hd].reshape(BH // rep, rep, S, hd).sum(axis=1)
    return dq[:, :T, :hd], dk, dv


def _pad2(a, t):
    BH, T = a.shape
    if t == T:
        return a
    return jnp.pad(a, ((0, 0), (0, t - T)))


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _rup(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad(a, t, d):
    BH, T, hd = a.shape
    if (t, d) == (T, hd):
        return a
    return jnp.pad(a, ((0, 0), (0, t - T), (0, d - hd)))
