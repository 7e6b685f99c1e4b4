"""jit'd public wrappers for the Pallas kernels.

Each wrapper decides at trace time how its kernel runs (``interpret()``):
compiled by Mosaic when the default backend is a TPU, in the Pallas
interpreter on any other backend (the CPU test suite).  There is no switch
that runs the interpreter on a TPU.

Mosaic kernels cannot be partitioned by GSPMD, so under an active batch
layout (dist/runtime.py ``layout``, set by the launchers around tracing)
every per-example wrapper runs its kernel batch-locally under
``shard_map``: each device sees only its examples, and ``clip_reduce``'s
batch sum is a ``psum``.  Outside a layout the wrappers call the kernels
directly.

The DP core reaches these through the site registry: each site kind's
``kernel_route`` (core/sites.py) maps its named norm strategies onto these
wrappers — dense/moe_dense route ``materialize -> pegrad_norm`` and
``gram -> gram_norm``, conv2d routes its im2col patch tensors through the
same two kernels, embed routes to the id-masked ``gram_norm`` — selected
at trace time by ``DPConfig.use_kernels``.  New sites pick kernels by
registering a route, not by editing this file.

Poisson-masked batches (core/algo.py): padded examples arrive as all-zero
``gy`` rows, which every kernel annihilates to an exact-zero norm² /
reduction term — kernel-vs-compacted parity is tested in
tests/test_kernels.py, so the mask needs no explicit kernel argument.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.dist import runtime
from repro.kernels import clip_reduce as _cr
from repro.kernels import fused_bwd as _fb
from repro.kernels import gram_norm as _gn
from repro.kernels import pegrad_norm as _pn


def interpret() -> bool:
    """Whether the kernels run in the Pallas interpreter: only off-TPU.
    Called while tracing, never at import, so it sees the backend the
    computation is traced for."""
    return jax.default_backend() != "tpu"


def pegrad_norm(x4: jax.Array, gy4: jax.Array) -> jax.Array:
    """(B,G,T,di),(B,G,T,do) -> (B,) fused per-example grad norms²."""
    return runtime.batch_local(_pegrad_norm, 2)(x4, gy4)


def _pegrad_norm(x4, gy4):
    B, G, T, di = x4.shape
    do = gy4.shape[-1]
    out = _pn.pegrad_norm(x4.reshape(B * G, T, di), gy4.reshape(B * G, T, do),
                          interpret=interpret())
    return out.reshape(B, G).sum(axis=1)


def gram_norm(x4: jax.Array, gy4: jax.Array,
              mask_ids: jax.Array | None = None,
              square: bool = True) -> jax.Array:
    """(B,G,T,di),(B,G,T,do)[, ids (B,T)] -> (B,) ghost norms²."""
    def run(x4, gy4, *mask_ids):
        B, G, T, di = x4.shape
        do = gy4.shape[-1]
        ids = None
        if mask_ids:
            assert G == 1, "id mask only used for embeddings (G == 1)"
            ids = mask_ids[0].reshape(B, T)
        out = _gn.gram_norm(x4.reshape(B * G, T, di),
                            gy4.reshape(B * G, T, do), ids,
                            interpret=interpret(), square=square)
        return out.reshape(B, G).sum(axis=1)

    args = (x4, gy4) if mask_ids is None else (x4, gy4, mask_ids)
    return runtime.batch_local(run, len(args))(*args)


def clip_reduce(g: jax.Array, c: jax.Array) -> jax.Array:
    """(B, N), (B,) -> (N,) Σ_b c_b g_b."""
    return runtime.batch_local(
        lambda g, c: _cr.clip_reduce(g, c, interpret=interpret()), 2,
        reduce_out=True)(g, c)


def dense_bwd_norm(x4: jax.Array, gy4: jax.Array, w: jax.Array):
    """Fused dense backward (norm_strategy="fused", use_kernels=True):
    (B,G,T,di), (B,G,T,do), w (di,do) or (G,di,do) ->
    (gx4 (B,G,T,di), nsq (B,) f32) in one kernel sweep
    (kernels/fused_bwd.py)."""
    return runtime.batch_local(_dense_bwd_norm, 2)(x4, gy4, w)


def _dense_bwd_norm(x4, gy4, w):
    B, G, T, di = x4.shape
    do = gy4.shape[-1]
    wE = w if w.ndim == 3 else w[None]
    gx, nsq = _fb.dense_bwd_norm(x4.reshape(B * G, T, di),
                                 gy4.reshape(B * G, T, do), wE,
                                 interpret=interpret())
    return gx.reshape(x4.shape), nsq.reshape(B, G).sum(axis=1)


def dense_dgrad(gy4: jax.Array, w: jax.Array) -> jax.Array:
    """Separate-pass dgrad baseline: (B,G,T,do), w (di,do)|(G,di,do) ->
    gx4 (B,G,T,di).  Paired with ``pegrad_norm`` in
    benchmarks/kernel_bench.py as the two-launch baseline the fusion is
    gated against."""
    return runtime.batch_local(_dense_dgrad, 1)(gy4, w)


def _dense_dgrad(gy4, w):
    B, G, T, do = gy4.shape
    wE = w if w.ndim == 3 else w[None]
    gx = _fb.dense_dgrad(gy4.reshape(B * G, T, do), wE,
                         interpret=interpret())
    return gx.reshape(B, G, T, wE.shape[1])


# ---------------------------------------------------------------------------
# flash attention: Pallas forward + blocked-jnp backward (custom_vjp)
# ---------------------------------------------------------------------------

# model layers route attention through the flash kernel when True (set by
# launchers / REPRO_USE_FLASH=1); default off so the paper-faithful XLA
# baseline stays measurable.
USE_FLASH = os.environ.get("REPRO_USE_FLASH", "0") == "1"

from functools import partial as _partial

from repro.kernels import flash_attn as _fa

F32 = jnp.float32


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, bwd_block: int = 512):
    """q: (B,T,KV,rep,hd); k/v: (B,S,KV,hd) -> o: (B,T,KV,rep,hd)."""
    o, _ = _flash_fwd_impl(q, k, v, causal)
    return o


def _flash_fwd_impl(q, k, v, causal):
    B, T, KV, rep, hd = q.shape
    S = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * KV * rep, T, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    o, lse = _fa.flash_attn_fwd(qf, kf, vf, causal=causal, rep=rep,
                                interpret=interpret())
    o = o.reshape(B, KV, rep, T, hd).transpose(0, 3, 1, 2, 4)
    lse = lse.reshape(B, KV, rep, T)
    return o, lse


def _flash_vjp_fwd(q, k, v, causal, bwd_block):
    o, lse = _flash_fwd_impl(q, k, v, causal)
    return o, (q, k, v, o, lse)


# which backward implements flash_attention's custom_vjp: "jnp" (blocked
# pure-jnp, default) or "pallas" (the kernels in flash_attn.py — same math,
# VMEM-resident tiles).  The fused norm strategy reaches the Pallas pair
# directly via flash_attention_bwd below regardless of this flag.
FLASH_BWD = os.environ.get("REPRO_FLASH_BWD", "jnp")


def _flash_bwd_pallas(q, k, v, o, lse, do, causal):
    """5-D layout shim over flash_attn.flash_attn_bwd.  Returns f32
    (dq, dk, dv) with dq: (B,T,KV,rep,hd), dk/dv: (B,S,KV,hd)."""
    B, T, KV, rep, hd = q.shape
    S = k.shape[1]
    flat_q = lambda a: a.transpose(0, 2, 3, 1, 4).reshape(B * KV * rep, T, hd)
    flat_kv = lambda a: a.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    dqf, dkf, dvf = _fa.flash_attn_bwd(
        flat_q(q), flat_kv(k), flat_kv(v), flat_q(o),
        lse.reshape(B * KV * rep, T), flat_q(do), causal=causal, rep=rep,
        interpret=interpret())
    dq = dqf.reshape(B, KV, rep, T, hd).transpose(0, 3, 1, 2, 4)
    dk = dkf.reshape(B, KV, S, hd).transpose(0, 2, 1, 3)
    dv = dvf.reshape(B, KV, S, hd).transpose(0, 2, 1, 3)
    return dq, dk, dv


def flash_attention_bwd(q, k, v, do, causal: bool = True):
    """One-call Pallas flash backward: recomputes (o, lse) with the forward
    kernel, then runs the dk/dv and dq kernels.  The attention site's
    ``"fused"`` route (core/sites.py) — per-example norm² contribution of
    the parameter-free attention op is exactly zero, so the fused content
    here is the kernelized backward itself.  Layouts as in
    ``flash_attention``; returns f32 grads."""
    def run(q, k, v, do):
        o, lse = _flash_fwd_impl(q, k, v, causal)
        return _flash_bwd_pallas(q, k, v, o, lse, do, causal)

    return runtime.batch_local(run, 4)(q, k, v, do)


def _flash_vjp_bwd(causal, bwd_block, res, do):
    """Standard flash-attention backward, blocked over query chunks in pure
    jnp (exact recompute from the saved row logsumexp); the Pallas kernel
    pair when FLASH_BWD == "pallas"."""
    q, k, v, o, lse = res
    if FLASH_BWD == "pallas":
        dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, do, causal)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    B, T, KV, rep, hd = q.shape
    S = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    bq = _fa._rup(min(bwd_block, T), 1)
    while T % bq:
        bq -= 1
    nq = T // bq
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (B,T,KV,rep)

    kpos = jnp.arange(S)

    def one_chunk(carry, i):
        dk_acc, dv_acc = carry
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=1)
        qi, doi = sl(q), sl(do)
        lsei, deltai = sl(lse.transpose(0, 3, 1, 2)), sl(delta)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.einsum("btkrh,bskh->bkrts", qi, k,
                       preferred_element_type=F32) * scale
        if causal:
            m = kpos[None, :] <= qpos[:, None]
            s = jnp.where(m[None, None, None], s, -1e30)
        p = jnp.exp(s - lsei.transpose(0, 2, 3, 1)[..., None])   # (B,KV,rep,bq,S)
        dv = jnp.einsum("bkrts,btkrh->bskh", p, doi.astype(F32))
        dp = jnp.einsum("btkrh,bskh->bkrts", doi.astype(F32), v.astype(F32))
        ds = p * (dp - deltai.transpose(0, 2, 3, 1)[..., None]) * scale
        dq = jnp.einsum("bkrts,bskh->btkrh", ds, k.astype(F32))
        dk = jnp.einsum("bkrts,btkrh->bskh", ds, qi.astype(F32))
        return (dk_acc + dk, dv_acc + dv), dq

    zeros_kv = jnp.zeros((B, S, KV, hd), F32)
    (dk, dv), dqs = jax.lax.scan(
        jax.checkpoint(one_chunk), (zeros_kv, zeros_kv), jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, KV, rep, hd)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
