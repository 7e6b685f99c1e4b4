"""Plain float32 reference of phi3-mini-3.8b.l2, written from the Phi-3
report (arXiv:2404.14219) and its config.json, not from the program:
token embedding, pre-norm decoder layers of causal multi-head attention
with rotary embedding (half-split, theta 1e4, over the whole head) and a
SwiGLU MLP, a final RMSNorm and an untied head, mean cross-entropy over
the sequence.

Departures, which are the program's and are kept here: q, k, v and the
two MLP inputs are separate matrices (the published checkpoint fuses
qkv_proj and gate_up_proj; the maths is the same), the embedding and the
head are padded to 32256 rows (padded logits take no probability), and
only two of the 32 layers are present.

Every matmul goes through ``mm`` (bench/reference.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, theta):
    T, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs      # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]      # (T,1,half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_losses(params, batch, conf, mm):
    """(R,) mean next-token cross-entropy of each sequence."""
    H, hd, V = conf["num_attention_heads"], conf["head_dim"], conf["vocab_size"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    tok = batch["tokens"]
    inp, labels = tok[:, :-1], tok[:, 1:]
    x = params["embed"][inp].astype(jnp.float32)
    R, T, _ = x.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    blk = params["blocks"][0]
    for l in range(conf["num_hidden_layers"]):
        a = jax.tree.map(lambda t: t[l], blk)
        h = _rms(x, a["ln1"], eps)
        q = _rope(mm.dot(h, a["attn"]["wq"]).reshape(R, T, H, hd), theta)
        k = _rope(mm.dot(h, a["attn"]["wk"]).reshape(R, T, H, hd), theta)
        v = mm.dot(h, a["attn"]["wv"]).reshape(R, T, H, hd)
        s = mm.einsum("rqhd,rkhd->rhqk", q, k) / jnp.sqrt(float(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = mm.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1), v)
        x = x + mm.dot(o.reshape(R, T, H * hd), a["attn"]["wo"])
        h = _rms(x, a["ln2"], eps)
        m = a["mlp"]
        x = x + mm.dot(jax.nn.silu(mm.dot(h, m["w1"])) * mm.dot(h, m["w3"]),
                       m["w2"])
    x = _rms(x, params["final_norm"], eps)
    logits = mm.dot(x, params["head"][:, :V])
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -jnp.mean(ll, -1)
