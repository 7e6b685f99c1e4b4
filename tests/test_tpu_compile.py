"""Compile every Pallas kernel for a described TPU v5e chip.

The interpret-mode parity tests (test_kernels.py, test_fused_norms.py)
never check what the Mosaic lowering refuses: blocks that break the TPU
tiling rule (last two block dims divisible by 8 and 128, or equal to the
array's), rank-1 blocks, and kernels that overflow VMEM.  Here each kernel
is compiled with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology, at the widths of ``vit-cifar10`` and ``stablelm-3b``; no chip is
needed.  A compile is not a run: nothing here says anything about results
or times.

This is the only test file that loads the TPU compiler.  The topology is
described inside a module-scoped fixture, never at import time, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads libtpu.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_arch
from repro.kernels import clip_reduce as _cr
from repro.kernels import flash_attn as _fa
from repro.kernels import fused_bwd as _fb
from repro.kernels import gram_norm as _gn
from repro.kernels import pegrad_norm as _pn

BF16 = jnp.bfloat16


def _widths(name):
    """Per-chip kernel operand widths of a configuration: rows (examples
    per call), tokens, d_model, d_ff, query heads, kv heads, head dim, and
    whether its attention is causal."""
    arch = get_arch(name)
    if arch.family == "vit":
        v = arch.vit
        rows, tokens = SHAPES["train_4k"].global_batch, \
            (v.image_size // v.patch_size) ** 2
    else:
        rows, tokens = 2, SHAPES["train_4k"].seq_len
    return dict(rows=rows, T=tokens, d=arch.d_model, ff=arch.d_ff,
                heads=arch.n_heads, kv=arch.n_kv_heads, hd=arch.hd,
                causal=arch.family != "vit")


ARCHS = ["vit-cifar10", "stablelm-3b"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes) -> str:
    """The compiled module's HLO text, which must hold a Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _kernel_names(hlo: str) -> list:
    """Instruction names of the ``tpu_custom_call``s, without the ``.N``
    suffix XLA adds: the ``name`` each ``pallas_call`` gives its kernel,
    which device traces then show as the op's name."""
    return [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+?)(?:\.\d+)?\s*=", line)
            .group(1) for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("arch", ARCHS)
def test_pegrad_norm_compiles(arch, one_chip):
    w = _widths(arch)
    hlo = _compile(functools.partial(_pn.pegrad_norm, interpret=False),
                   one_chip, ((w["rows"], w["T"], w["d"]), BF16),
                   ((w["rows"], w["T"], w["ff"]), BF16))
    assert _kernel_names(hlo) == ["pegrad_norm_kernel"]


@pytest.mark.parametrize("arch", ARCHS)
def test_gram_norm_compiles(arch, one_chip):
    """The dense ghost norm, and the id-masked embedding rule (the
    ``segment_sum`` kernel route: x = gy, square=False)."""
    w = _widths(arch)
    hlo = _compile(functools.partial(_gn.gram_norm, interpret=False),
                   one_chip, ((w["rows"], w["T"], w["d"]), BF16),
                   ((w["rows"], w["T"], w["ff"]), BF16))
    assert _kernel_names(hlo) == ["gram_norm_kernel"]
    hlo = _compile(lambda gy, ids: _gn.gram_norm(gy, gy, ids, square=False,
                                                 interpret=False), one_chip,
                   ((w["rows"], w["T"], w["d"]), BF16),
                   ((w["rows"], w["T"]), jnp.int32))
    assert _kernel_names(hlo) == ["gram_norm_kernel"]


@pytest.mark.parametrize("arch", ARCHS)
def test_clip_reduce_compiles(arch, one_chip):
    """Per-example flattened grads of one FFN weight, (B, d·d_ff)."""
    w = _widths(arch)
    hlo = _compile(functools.partial(_cr.clip_reduce, interpret=False),
                   one_chip, ((w["rows"], w["d"] * w["ff"]), jnp.float32),
                   ((w["rows"],), jnp.float32))
    assert _kernel_names(hlo) == ["clip_reduce_kernel"]


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("site", ["ffn-up", "ffn-down"])
def test_dense_bwd_norm_compiles(arch, site, one_chip):
    w = _widths(arch)
    di, do = (w["d"], w["ff"]) if site == "ffn-up" else (w["ff"], w["d"])
    hlo = _compile(functools.partial(_fb.dense_bwd_norm, interpret=False),
                   one_chip, ((w["rows"], w["T"], di), BF16),
                   ((w["rows"], w["T"], do), BF16), ((1, di, do), BF16))
    # the benchmark's roofline.dense_bwd_norm and norm_kernel_share read
    # this kernel by this exact name
    assert _kernel_names(hlo) == ["_fused_kernel"]


@pytest.mark.parametrize("arch", ARCHS)
def test_dense_dgrad_compiles(arch, one_chip):
    w = _widths(arch)
    hlo = _compile(functools.partial(_fb.dense_dgrad, interpret=False),
                   one_chip, ((w["rows"], w["T"], w["ff"]), BF16),
                   ((1, w["d"], w["ff"]), BF16))
    assert _kernel_names(hlo) == ["dgrad_kernel"]


def _flash_shapes(w):
    bh, bkv = w["rows"] * w["heads"], w["rows"] * w["kv"]
    return ((bh, w["T"], w["hd"]), BF16), ((bkv, w["T"], w["hd"]), BF16)


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_fwd_compiles(arch, one_chip):
    w = _widths(arch)
    q, kv = _flash_shapes(w)
    rep = w["heads"] // w["kv"]
    hlo = _compile(functools.partial(_fa.flash_attn_fwd, causal=w["causal"],
                                     rep=rep, interpret=False),
                   one_chip, q, kv, kv)
    assert _kernel_names(hlo) == ["flash_fwd_kernel"]


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_bwd_compiles(arch, one_chip):
    w = _widths(arch)
    q, kv = _flash_shapes(w)
    rep = w["heads"] // w["kv"]
    lse = ((q[0][0], w["T"]), jnp.float32)
    hlo = _compile(functools.partial(_fa.flash_attn_bwd, causal=w["causal"],
                                     rep=rep, interpret=False), one_chip,
                   q, kv, kv, q, lse, q)
    assert sorted(_kernel_names(hlo)) == ["flash_bwd_kv_kernel",
                                          "flash_bwd_q_kernel"]
