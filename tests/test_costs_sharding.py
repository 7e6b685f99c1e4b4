"""Cost accounting (jaxpr flop counter, HLO collective parser) and
sharding-rule unit tests + an 8-device pjit integration test."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.costs import hlo_collective_bytes, jaxpr_costs
from repro.dist.sharding import spec_for_param


# ---------------------------------------------------------------------------
# jaxpr flop counter
# ---------------------------------------------------------------------------

def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((32, 128), jnp.bfloat16)
    c = jaxpr_costs(lambda x, y: x @ y, a, b)
    assert c["dot_flops_by_dtype"]["bfloat16"] == 2 * 64 * 32 * 128


def test_scan_multiplies_flops():
    a = jax.ShapeDtypeStruct((16, 16), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = jaxpr_costs(f, a)
    assert c["dot_flops_by_dtype"]["float32"] == 10 * 2 * 16 * 16 * 16


def test_remat_counts_recompute():
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(x):
        g = jax.checkpoint(lambda u: jnp.sin(u @ u) @ u)
        return jax.grad(lambda u: g(u).sum())(x)

    base = jaxpr_costs(lambda x: jnp.sin(x @ x) @ x, a)
    withgrad = jaxpr_costs(f, a)
    # grad-of-remat must cost strictly more than 2x the forward dots
    assert (withgrad["dot_flops_by_dtype"]["float32"]
            > 2 * base["dot_flops_by_dtype"]["float32"])


def test_batched_dot_flops():
    a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    c = jaxpr_costs(lambda x, y: jnp.einsum("bij,bjk->bik", x, y), a, b)
    assert c["dot_flops_by_dtype"]["float32"] == 2 * 4 * 8 * 16 * 32


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

FAKE_HLO = """
HloModule test

%loop_cond (p: (s32[], f32[8])) -> pred[] {
  %iter = s32[] get-tuple-element(...), index=0
  %trip = s32[] constant(12)
  ROOT %lt = pred[] compare(s32[] %iter, s32[] %trip), direction=LT
}

%loop_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %x = f32[8]{0} get-tuple-element(...), index=1
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups=[16,32]<=[512]
  ROOT %t = (s32[], f32[8]) tuple(...)
}

ENTRY %main (a: f32[128,64]) -> f32[128,64] {
  %a = f32[128,64]{1,0} parameter(0)
  %ag = f32[128,64]{1,0} all-gather(f32[128,16]{1,0} %a), replica_groups={{0,1,2,3}}, dimensions={1}
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), condition=%loop_cond, body=%loop_body
  ROOT %r = f32[128,64]{1,0} copy(%ag)
}
"""


def test_collective_parser_scales_while_bodies():
    out, top = hlo_collective_bytes(FAKE_HLO, 512)
    # all-gather: 128*64*4 bytes * 3/4
    assert out["all-gather"] == pytest.approx(128 * 64 * 4 * 3 / 4)
    # all-reduce inside while: 8*4 bytes * 2*(31/32) * 12 trips
    assert out["all-reduce"] == pytest.approx(8 * 4 * 2 * (31 / 32) * 12)
    assert out["total"] == out["all-gather"] + out["all-reduce"]
    assert top[0]["kind"] in ("all-gather", "all-reduce")


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


def test_spec_model_priority():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # mlp dim divisible -> model there
    assert spec_for_param(("embed", "mlp"), (3072, 8192), mesh) \
        == P(None, "model")
    # expert preferred over mlp when divisible
    assert spec_for_param(("expert", "embed", "mlp"), (64, 2048, 1408),
                          mesh) == P("model", None, None)
    # expert NOT divisible -> falls through to mlp (grok case)
    assert spec_for_param(("expert", "embed", "mlp"), (8, 6144, 32768),
                          mesh) == P(None, None, "model")
    # nothing divisible -> replicated
    assert spec_for_param((None,), (5,), mesh) == P(None)


def test_spec_fsdp_adds_data_axis():
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert spec_for_param(("embed", "mlp"), (8192, 22016), mesh,
                          fsdp=True) == P("data", "model")
    # embed not divisible by data -> no data sharding
    assert spec_for_param(("embed", "mlp"), (8191, 22016), mesh,
                          fsdp=True) == P(None, "model")


def test_pjit_train_step_on_8_fake_devices():
    """Integration: a reduced arch's full DP train step lowers AND RUNS
    under a (2, 4) mesh using the production sharding rules."""
    import subprocess, sys, os
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS, reduced
from repro.configs.base import DPConfig, OptimConfig
from repro.core import make_noisy_grad_fn
from repro.dist import batch_shardings, state_shardings
from repro.launch.mesh import make_mesh
from repro.models.transformer import build_model
from repro.optim import make_optimizer
from repro.train.state import TrainState

arch = reduced(ARCHS["chatglm3-6b"])
model = build_model(arch, param_dtype="float32", compute_dtype="float32")
mesh = make_mesh((2, 4), ("data", "model"))
grad_fn = make_noisy_grad_fn(model.loss_fn, DPConfig(algo="dpsgd_r"))
opt = make_optimizer(OptimConfig(name="adamw"))

def train_step(state, batch, key):
    grads, metrics = grad_fn(state.params, batch, key)
    p, o = opt.apply(grads, state.opt_state, state.params, state.step)
    return TrainState(step=state.step + 1, params=p, opt_state=o), metrics

with mesh:
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState.create(params, opt.init(params))
    st_sh = state_shardings(mesh, model, jax.eval_shape(lambda: state))
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_sh)
    B, T = 8, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, T + 1),
                                          0, arch.vocab)}
    b_sh = batch_shardings(mesh, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch), B)
    batch = jax.tree.map(lambda a, s: jax.device_put(a, s), batch, b_sh)
    fn = jax.jit(train_step, in_shardings=(st_sh, b_sh, None),
                 out_shardings=(st_sh, None))
    state2, metrics = fn(state, batch, jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1
print("PJIT_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PJIT_OK" in out.stdout, out.stderr[-3000:]
