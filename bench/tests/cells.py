"""What the benchmark's tests share: each configuration shrunk to a size
a CPU test run can hold, and the faults planted in the timed path (each a
function ``plant(trainer)`` that ``harness.run_cell`` calls on the
program's trainer before set-up)."""
import jax
import jax.numpy as jnp
import numpy as np

SMALL = {
    "vit": {"conf": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                         head_dim=16, d_ff=128, image_size=8, patch_size=2),
            "mix": dict(batch=8, augmult=2, reference_block=4)},
    "phi3": {"conf": dict(num_hidden_layers=2, hidden_size=64,
                          intermediate_size=128, num_attention_heads=4,
                          num_key_value_heads=4, head_dim=16, vocab_size=256,
                          padded_vocab=256),
             "mix": dict(batch=4, seq_len=16, reference_block=1)},
}


def small(name):
    return SMALL[name.split(".")[0]]


def unchanged(trainer):
    """A step that returns its state unchanged."""
    step = trainer.step_fn

    def returns_input(state, batch, key):
        _, metrics = step(state, batch, key)
        return state, metrics
    trainer.step_fn = returns_input


def half(trainer, keep_normaliser=False):
    """Half of the batch left out, the mean taken over the rest (or, with
    ``keep_normaliser``, the full batch's normaliser kept)."""
    from repro.train.trainer import make_train_step
    K = trainer.cfg.dp.augmult
    poisson = trainer.sampling == "poisson"
    B = trainer.shape.global_batch
    step = jax.jit(make_train_step(
        trainer.model, trainer.cfg,
        expected_batch_size=B if keep_normaliser else
        B / 2 if poisson else None))

    def half_batch(state, batch, key):
        if "mask" in batch:
            me = batch["mask"].reshape(-1, K)[:, 0]
            keep = me & (jnp.cumsum(me) <= (jnp.sum(me) + 1) // 2)
            batch = dict(batch, mask=jnp.repeat(keep, K))
        else:
            R = len(jax.tree.leaves(batch)[0])
            batch = jax.tree.map(lambda a: a[:R // 2], batch)
        return step(state, batch, key)
    trainer.step_fn = half_batch


def half_sum(trainer):
    half(trainer, keep_normaliser=True)


def altered(trainer):
    """A token or label altered where the feed produces it."""
    make = trainer.make_batch

    def altered_batch(step):
        b = {k: np.array(v) for k, v in make(step).items()}
        if "labels" in b:
            b["labels"][0] = (b["labels"][0] + 1) % 10
        else:
            b["tokens"][0, 1] = (b["tokens"][0, 1] + 1) % 256
        return b
    trainer.make_batch = altered_batch
