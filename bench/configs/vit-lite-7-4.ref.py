"""Plain float32 reference of ViT-Lite-7/4 as the program builds it,
written from the architecture and not from the program: patch embedding
as a matmul over 4x4x3 patches, pre-norm blocks of bidirectional
multi-head attention and a GELU MLP, a final norm, mean pooling over the
patches and a linear head.

Departures from the paper's ViT-Lite, which are the program's and are
kept here: RMSNorm with a learned scale in place of LayerNorm, no class
token (the head reads the mean of the patch tokens), no biases in the
attention and MLP matmuls, zero-initialised learned position embedding,
GELU in its tanh form.

Every matmul goes through ``mm`` (bench/reference.py): float32 at the
highest precision for the reference, scaled float8 for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def row_losses(params, batch, conf, mm):
    """(R,) cross-entropy of each row (augmented view) of the batch."""
    p, c, H, hd = (conf["patch_size"], conf["in_channels"], conf["n_heads"],
                   conf["head_dim"])
    eps = conf["norm_eps"]
    img = batch["images"].astype(jnp.float32)
    R, S = img.shape[0], img.shape[1]
    g = S // p
    patches = img.reshape(R, g, p, g, p, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(R, g * g, p * p * c)
    w = params["patch"]["w"].reshape(p * p * c, -1)
    x = mm.dot(patches, w) + params["patch"]["b"] + params["pos"]
    T = x.shape[1]
    for bp in params["blocks"]:
        a = bp["attn"]
        h = _rms(x, bp["ln1"], eps)
        q = mm.dot(h, a["wq"]).reshape(R, T, H, hd)
        k = mm.dot(h, a["wk"]).reshape(R, T, H, hd)
        v = mm.dot(h, a["wv"]).reshape(R, T, H, hd)
        s = mm.einsum("rqhd,rkhd->rhqk", q, k) / jnp.sqrt(float(hd))
        o = mm.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1), v)
        x = x + mm.dot(o.reshape(R, T, H * hd), a["wo"])
        h = _rms(x, bp["ln2"], eps)
        x = x + mm.dot(jax.nn.gelu(mm.dot(h, bp["mlp"]["w1"])),
                       bp["mlp"]["w2"])
    x = _rms(x, params["final_norm"], eps)
    logits = mm.dot(jnp.mean(x, axis=1), params["head"]["w"])
    logits = logits + params["head"]["b"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], -1)[:, 0]
