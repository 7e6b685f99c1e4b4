"""repro.dist: sharding-rule round-trips, batch-local runtime equivalence
(8 fake CPU devices, subprocess), and compression error-feedback.  Plain
asserts only — no hypothesis dependency."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.dist import runtime
from repro.dist.sharding import (_axis_size, batch_pspec, batch_shardings,
                                 param_shardings, spec_for_param,
                                 state_shardings)
from repro.launch.mesh import make_mesh


class _FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


# ---------------------------------------------------------------------------
# pure shape arithmetic (no devices needed)
# ---------------------------------------------------------------------------

def test_axis_size_and_batch_pspec():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert _axis_size(mesh, "pod") == 2
    assert _axis_size(mesh, "data") == 16
    assert _axis_size(mesh, "absent") == 1
    assert batch_pspec(mesh, 256) == ("pod", "data")
    assert batch_pspec(mesh, 16) == ("data",)      # 16-way beats pod-only
    assert batch_pspec(mesh, 2) == ("pod",)
    assert batch_pspec(mesh, 1) is None
    single = _FakeMesh({"data": 16, "model": 16})
    assert batch_pspec(single, 256) == ("data",)
    assert batch_pspec(single, 8) is None


def test_spec_for_param_priority_and_fallthrough():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # heads preferred over mlp
    assert spec_for_param(("embed", "heads"), (1024, 4096), mesh) \
        == P(None, "model")
    # kv not divisible -> nothing else named -> replicated
    assert spec_for_param(("embed", "kv"), (1024, 24), mesh) == P(None, None)
    # vocab-parallel head
    assert spec_for_param(("embed", "vocab"), (1024, 32256), mesh) \
        == P(None, "model")
    # layers dim never sharded, even under fsdp
    assert spec_for_param(("layers", "embed", "mlp"), (32, 4096, 11008),
                          mesh, fsdp=True) == P(None, "data", "model")


def test_spec_roundtrip_all_archs():
    """Every param of every (reduced) arch gets a spec that is valid for its
    shape: at most one mesh axis per dim, and sharded dims divide evenly."""
    from repro.models.transformer import build_model
    mesh = _FakeMesh({"data": 2, "model": 4})

    for name in ("stablelm-3b", "deepseek-moe-16b", "mamba2-1.3b",
                 "jamba-1.5-large-398b"):
        model = build_model(reduced(ARCHS[name]))

        def check(leaf, axes, spec):
            assert len(spec) <= len(leaf.shape)
            used = [a for a in spec if a is not None]
            assert len(used) == len(set(used)), (name, spec)
            for dim, entry in zip(leaf.shape, spec):
                if entry is not None:
                    assert dim % _axis_size(mesh, entry) == 0, \
                        (name, leaf.shape, spec)

        from repro.dist.sharding import _zip_spec_tree
        _zip_spec_tree(
            model.abstract_params(), model.logical_axes(),
            lambda leaf, ax: check(
                leaf, ax, spec_for_param(ax, leaf.shape, mesh, fsdp=True)))


def test_batch_local_identity_without_layout():
    """Outside any layout, batch_local/attn_local return fn itself."""
    fn = lambda x: x * 2
    assert runtime.batch_local(fn, 1) is fn
    assert runtime.attn_local(fn, 4) is fn
    assert runtime.active() is None


def test_single_device_shardings_run(key):
    """batch/state shardings built on the trivial 1-device mesh place
    arrays without error and leave values unchanged."""
    from repro.models.transformer import build_model
    from repro.optim import make_optimizer
    from repro.configs.base import OptimConfig
    from repro.train.state import TrainState

    mesh = make_mesh((1,), ("data",))
    arch = reduced(ARCHS["stablelm-3b"])
    model = build_model(arch, param_dtype="float32", compute_dtype="float32")
    params = model.init(key)
    opt = make_optimizer(OptimConfig(name="adamw"))
    state = TrainState.create(params, opt.init(params))
    sh = state_shardings(mesh, model, jax.eval_shape(lambda: state))
    placed = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    batch = {"tokens": jnp.zeros((4, 9), jnp.int32)}
    bsh = batch_shardings(mesh, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch), 4)
    jax.tree.map(lambda a, s: jax.device_put(a, s), batch, bsh)


# ---------------------------------------------------------------------------
# compression (plain-assert convergence; the hypothesis-free core property)
# ---------------------------------------------------------------------------

def test_compress_roundtrip_small_error(key):
    from repro.dist.compress import compress_grads, init_error_state
    g = {"a": jax.random.normal(key, (300,)) * 0.05,
         "b": jax.random.normal(jax.random.fold_in(key, 1), (64, 8))}
    out, err = compress_grads(g, init_error_state(g))
    for k in g:
        e = np.abs(np.asarray(g[k] - out[k]))
        bucket = np.abs(np.asarray(g[k])).max() / 127.0
        assert e.max() <= bucket + 1e-6, k
        np.testing.assert_allclose(np.asarray(err[k]),
                                   np.asarray(g[k] - out[k]), atol=1e-6)


def test_compress_error_feedback_converges(key):
    """Cumulative transmitted signal tracks the cumulative true signal."""
    from repro.dist.compress import compress_grads, init_error_state
    g0 = 0.01 * jax.random.normal(key, (513,))   # non-block-aligned
    err = init_error_state({"w": g0})
    sent = np.zeros(513)
    true = np.zeros(513)
    for step in range(30):
        g = {"w": g0 * np.cos(0.3 * step)}       # sign-flipping signal
        out, err = compress_grads(g, err)
        sent += np.asarray(out["w"])
        true += np.asarray(g["w"])
        # residual bounded by one quantization bucket of the current input
        bucket = (np.abs(np.asarray(g["w"])).max()
                  + np.abs(np.asarray(err["w"])).max()) / 127.0
        assert np.abs(np.asarray(err["w"])).max() <= bucket + 1e-5
    assert np.abs(sent - true).max() <= 5e-4


# ---------------------------------------------------------------------------
# multi-device equivalence (8 fake CPU devices in a subprocess — XLA locks
# the device count at first init, so it cannot run in this process)
# ---------------------------------------------------------------------------

_EQUIV_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import norms, make_noisy_grad_fn
from repro.configs import ARCHS, reduced
from repro.configs.base import DPConfig
from repro.dist import runtime, batch_shardings
from repro.dist.sharding import batch_pspec
from repro.launch.mesh import make_mesh
from repro.models.transformer import build_model

assert jax.device_count() == 8
mesh = make_mesh((8,), ("data",))
bax = batch_pspec(mesh, 8)
assert bax == ("data",)

def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)

key = jax.random.PRNGKey(0)
B, T, d = 8, 16, 12

# --- embed_nsq: sharded batch-local vs plain ------------------------------
ids = jax.random.randint(key, (B, T), 0, 11)
gy = jax.random.normal(jax.random.fold_in(key, 1), (B, T, d))
ref = norms.embed_nsq(ids, gy)                       # no layout -> plain
with runtime.layout(mesh, bax):
    sharded = norms.embed_nsq(ids, gy)               # shard_map path
r1 = rel(sharded, ref)
assert r1 < 1e-5, f"embed_nsq mismatch {r1}"

# --- dense_nsq (both strategies) under batch_local ------------------------
x = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, T, d))
gyd = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, T, d + 4))
for strat in ("materialize", "gram"):
    ref = norms.dense_nsq(x, gyd, strat)
    with runtime.layout(mesh, bax):
        fn = runtime.batch_local(
            lambda a, b, s=strat: norms.dense_nsq(a, b, s), 2)
        sharded = fn(x, gyd)
    r = rel(sharded, ref)
    assert r < 1e-5, f"dense_nsq[{strat}] mismatch {r}"

# --- psum aggregation: clipped-grad sum reduced across shards -------------
c = jnp.minimum(1.0, 1.0 / jnp.sqrt(ref))            # clip factors (B,)
gb = jax.random.normal(jax.random.fold_in(key, 4), (B, 40))  # per-ex grads
ref_sum = jnp.einsum("b,bn->n", c, gb)
with runtime.layout(mesh, bax):
    fn = runtime.batch_local(lambda cc, gg: jnp.einsum("b,bn->n", cc, gg),
                             2, reduce_out=True)
    psummed = fn(c, gb)
r2 = rel(psummed, ref_sum)
assert r2 < 1e-5, f"psum clipped-sum mismatch {r2}"

# --- attn_local: flash attention with batch AND KV-head sharding ----------
from repro.kernels import ops as kops
mesh42 = make_mesh((4, 2), ("data", "model"))
KV, rep, hd = 2, 2, 8
q = jax.random.normal(jax.random.fold_in(key, 8), (B, T, KV, rep, hd))
kk = jax.random.normal(jax.random.fold_in(key, 9), (B, T, KV, hd))
vv = jax.random.normal(jax.random.fold_in(key, 10), (B, T, KV, hd))
ref = kops.flash_attention(q, kk, vv, True)
with runtime.layout(mesh42, batch_pspec(mesh42, B)):
    fn = runtime.attn_local(
        lambda a, b, c: kops.flash_attention(a, b, c, True), KV)
    sharded = fn(q, kk, vv)
r3 = rel(sharded, ref)
assert r3 < 1e-5, f"attn_local flash mismatch {r3}"

# --- end-to-end: DP train-step grads, sharded vs single-device ------------
arch = reduced(ARCHS["stablelm-3b"])
model = build_model(arch, param_dtype="float32", compute_dtype="float32")
params = model.init(jax.random.PRNGKey(5))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(6), (B, T + 1),
                                      0, arch.vocab)}
grad_fn = make_noisy_grad_fn(model.loss_fn, DPConfig(algo="dpsgd_r"))
nkey = jax.random.PRNGKey(7)

ref_grads, ref_metrics = grad_fn(params, batch, nkey)   # single device

bsh = batch_shardings(mesh, jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch), B)
batch_s = jax.tree.map(lambda a, s: jax.device_put(a, s), batch, bsh)
with mesh:
    sh_grads, sh_metrics = jax.jit(grad_fn, in_shardings=(None, bsh, None))(
        params, batch_s, nkey)

worst = max(rel(a, b) for a, b in zip(jax.tree.leaves(sh_grads),
                                      jax.tree.leaves(ref_grads)))
assert worst < 1e-5, f"sharded DP grads mismatch {worst}"
rl = rel(sh_metrics["loss"], ref_metrics["loss"])
rn = rel(sh_metrics["grad_norm_mean"], ref_metrics["grad_norm_mean"])
assert rl < 1e-5 and rn < 1e-5, (rl, rn)
print(f"DIST_EQUIV_OK embed={r1:.2e} psum={r2:.2e} attn={r3:.2e} "
      f"grads={worst:.2e}")
"""


def test_sharded_matches_single_device_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _EQUIV_CODE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "DIST_EQUIV_OK" in out.stdout, \
        (out.stdout[-2000:], out.stderr[-3000:])
