"""Checkpoint manager + data pipeline: atomicity, retention, resharding,
determinism, shard consistency, resume."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.data import MemmapSource, SyntheticSource, batch_for, make_source
from repro.launch.mesh import make_mesh
from repro.train.checkpoint import CheckpointManager


def _state(key):
    return {"params": {"w": jax.random.normal(key, (8, 4)),
                       "b": jnp.zeros((4,), jnp.bfloat16)},
            "step": jnp.asarray(3, jnp.int32)}


def test_roundtrip_and_dtypes(tmp_path, key):
    cm = CheckpointManager(str(tmp_path), keep=2, use_async=False)
    st = _state(key)
    cm.save(st, 3)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st)
    back = cm.restore(like)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_keep_k_and_latest(tmp_path, key):
    cm = CheckpointManager(str(tmp_path), keep=2, use_async=False)
    st = _state(key)
    for s in (1, 2, 3, 4):
        cm.save(st, s)
    assert cm.steps() == [3, 4]
    assert cm.latest_step() == 4


def test_async_save_then_restore(tmp_path, key):
    cm = CheckpointManager(str(tmp_path), keep=3, use_async=True)
    st = _state(key)
    cm.save(st, 1)
    cm.wait()
    assert cm.latest_step() == 1


def test_no_partial_checkpoints_visible(tmp_path, key):
    cm = CheckpointManager(str(tmp_path), keep=3, use_async=False)
    st = _state(key)
    cm.save(st, 5)
    # simulate a crashed writer: a stale tmp dir must not count
    os.makedirs(tmp_path / ".tmp_step_6" )
    assert cm.steps() == [5]


def test_restore_with_shardings(tmp_path, key):
    """Reshard-on-restore: explicit (single-device) shardings path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    cm = CheckpointManager(str(tmp_path), use_async=False)
    st = _state(key)
    cm.save(st, 1)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st)
    sh = jax.tree.map(lambda a: NamedSharding(mesh, P()), like)
    back = cm.restore(like, shardings=sh)
    np.testing.assert_array_equal(np.asarray(back["params"]["w"]),
                                  np.asarray(st["params"]["w"]))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_determinism():
    s = SyntheticSource(vocab=100, seed=7)
    a = s.batch(step=5, n=8, seq_len=16)
    b = s.batch(step=5, n=8, seq_len=16)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = s.batch(step=6, n=8, seq_len=16)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_shards_compose_to_global_batch():
    """Elasticity: any shard layout materializes the same global batch."""
    s = SyntheticSource(vocab=100, seed=7)
    full = s.batch(step=3, n=8, seq_len=16)
    parts = [s.batch(step=3, n=8, seq_len=16, shard=i, n_shards=4)
             for i in range(4)]
    np.testing.assert_array_equal(
        np.concatenate([p["tokens"] for p in parts]), full["tokens"])


def test_embed_stub_batches():
    from repro.configs import ARCHS, reduced
    arch = reduced(ARCHS["musicgen-medium"])
    s = SyntheticSource(vocab=arch.vocab, seed=0)
    shape = ShapeConfig("t", 16, 4, "train")
    b = batch_for(s, arch, shape, step=0)
    assert b["embeds"].shape == (4, 16, arch.d_model)
    assert b["labels"].shape == (4, 16)
    assert b["labels"].max() < arch.vocab


def test_memmap_source(tmp_path):
    data = np.arange(10_000, dtype=np.int32) % 50
    path = tmp_path / "toks.bin"
    data.tofile(path)
    s = make_source(f"memmap:{path}", vocab=50, seed=1)
    a = s.batch(step=2, n=4, seq_len=32)
    b = s.batch(step=2, n=4, seq_len=32)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 33)
    assert a["tokens"].max() < 50
    assert s.dataset_size == 10_000
