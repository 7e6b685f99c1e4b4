"""Weights drawn by the benchmark from ``--seed``.

A configuration's ``layout(conf)`` gives the program's parameter tree with
(shape, dtype, init) leaves; ``make`` draws every leaf on the device in one
jitted call, in the type it is served in, straight into the shardings it
is given: no device holds a whole leaf that its sharding splits.  JAX's
random draws do not depend on how their output is sharded, so the numbers
are the same bits on any mesh.  The correctness reference calls the same
function, so it starts from the same numbers without taking them from the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WEIGHTS_TAG = 0x5EED


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def key_of(seed: int, tag: int) -> jax.Array:
    """A PRNG key from all bits of a seed of up to 62 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(k, tag)


def make(layout, seed: int, shardings=None):
    """The weights of ``layout`` from ``seed``; ``shardings``, a tree of
    the layout's structure, places each leaf (the default device without
    it)."""
    leaves, tree = jax.tree.flatten(layout, is_leaf=is_leaf)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, dtype, init) in zip(keys, leaves):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                x = init * jax.random.normal(k, shape, jnp.float32)
                out.append(x.astype(dtype))
        return jax.tree.unflatten(tree, out)

    placed = jax.jit(draw) if shardings is None else \
        jax.jit(draw, out_shardings=shardings)
    return placed(key_of(seed, WEIGHTS_TAG))


def abstract(layout):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l[0], jnp.dtype(l[1])),
                        layout, is_leaf=is_leaf)
