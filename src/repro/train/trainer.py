"""Training runtime: jitted DP train step + fault-tolerant loop.

Fault-tolerance model (1000+-node design, DESIGN.md §5):
* SIGTERM/SIGINT (preemption notice) -> finish current step, checkpoint,
  exit cleanly; resume is exact because data + noise are (seed, step)-keyed.
* Transient step failure -> retry the step (bit-identical update).  The
  jitted step deliberately does NOT donate ``state``: donation deletes the
  input buffers even when the call fails, so a "retry" would dereference
  dead arrays.  Instead the old state is released by refcount only after
  the step has completed successfully (donate-on-success); failures —
  including ones raised *inside* the jitted computation, exercised via
  ``inject_inside_jit`` — leave ``state`` intact for the retry.
* Straggler watchdog: any step slower than ``watchdog_factor`` x the median
  is logged with its step index (on real fleets this feeds the scheduler).
"""
from __future__ import annotations

import dataclasses
import math
import signal
import time
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.core import PrivacyAccountant, make_noisy_grad_fn
from repro.core import adaptive_clip as _aclip
from repro.core.algo import algo_is_private
from repro.data import (augment_expand, batch_for, make_source,
                        poisson_batch_for, poisson_capacity)
from repro.optim import make_optimizer
from repro.train import telemetry
from repro.train.checkpoint import CheckpointManager
from repro.train.state import TrainState


def adaptive_clip_on(dp) -> bool:
    """Adaptive clipping is live iff configured AND the algo is private
    (there is no clip norm to adapt under plain SGD)."""
    return bool(dp.adaptive_clip) and algo_is_private(dp.algo, dp.enabled)


def physical_batch_size(train_cfg: TrainConfig, shape,
                        dataset_size: int, shards: int = 1) -> int:
    """Physical (padded) *examples* per step.  Fixed sampling: the
    configured batch.  Poisson: a step-invariant capacity >= the expected
    size q·N (+6 binomial sigmas), rounded so grad_accum and microbatch
    chunking — and the mesh's ``shards``-wide batch axes, when given —
    keep dividing evenly (data/pipeline.poisson_capacity).  Under
    ``dp.augmult = K`` the physical *row* count is K x this (augmentation
    expands after sampling; launch/memory.py sizes activations by rows)."""
    if train_cfg.dp.sampling != "poisson":
        return shape.global_batch
    mult = math.lcm(max(1, train_cfg.grad_accum)
                    * max(1, train_cfg.dp.microbatch), max(1, shards))
    return poisson_capacity(shape.global_batch,
                            shape.global_batch / dataset_size, multiple=mult)


def make_train_step(model, train_cfg: TrainConfig,
                    expected_batch_size: Optional[float] = None) -> Callable:
    """Build fn(state, batch, key) -> (state, metrics).  Pure; jit outside.

    ``expected_batch_size``: under ``dp.sampling="poisson"`` the expected
    sample size q·N that normalizes the noisy sum (Algorithm 1 line 24);
    None = physical batch size (fixed-size batches).

    With ``compress_pod_grads``: the DP-noised gradient sum is int8+error-
    feedback compressed before the cross-pod reduction (dist/compress.py);
    the error residual rides in the optimizer state so it is checkpointed.
    """
    grad_fn = make_noisy_grad_fn(model.loss_fn, train_cfg.dp,
                                 grad_accum=train_cfg.grad_accum,
                                 expected_batch_size=expected_batch_size)
    opt = make_optimizer(train_cfg.optim)
    compress = train_cfg.compress_pod_grads
    adaptive = adaptive_clip_on(train_cfg.dp)
    # either rider wraps opt_state as {"opt": ..., <rider keys>...} so the
    # extra state is checkpointed with the optimizer state
    wrapped = compress or adaptive

    def step_fn(state: TrainState, batch, key):
        opt_state = state.opt_state["opt"] if wrapped else state.opt_state
        clip = (state.opt_state[_aclip.CLIP_STATE_KEY]["clip_norm"]
                if adaptive else None)
        grads, metrics = grad_fn(state.params, batch, key, clip_norm=clip)
        if compress:
            from repro.dist.compress import compress_grads
            grads, new_err = compress_grads(grads,
                                            state.opt_state["grad_err"])
        with jax.named_scope("optim_update"):
            new_params, new_opt = opt.apply(grads, opt_state,
                                            state.params, state.step)
        if wrapped:
            new_opt = {"opt": new_opt}
            if compress:
                new_opt["grad_err"] = new_err
            if adaptive:
                new_opt[_aclip.CLIP_STATE_KEY] = \
                    {"clip_norm": metrics["clip_norm_next"]}
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, update_norm=gn)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt), metrics

    return step_fn


def make_opt_init(train_cfg: TrainConfig, opt) -> Callable:
    def init(params):
        st = opt.init(params)
        riders = {}
        if train_cfg.compress_pod_grads:
            from repro.dist.compress import init_error_state
            riders["grad_err"] = init_error_state(params)
        if adaptive_clip_on(train_cfg.dp):
            riders[_aclip.CLIP_STATE_KEY] = _aclip.init_state(train_cfg.dp)
        if riders:
            return {"opt": st, **riders}
        return st
    return init


class Trainer:
    """Single-controller training loop (the multi-pod launcher wires the
    same loop through pjit + jax.distributed, launch/train.py)."""

    def __init__(self, model, train_cfg: TrainConfig, shape,
                 jit_step: bool = True, shard_batch=None,
                 inject_failure_at: Optional[int] = None,
                 inject_inside_jit: bool = False,
                 batch_multiple: int = 1, plan=None):
        # plan: a solved launch/autotune.py LaunchPlan — applied onto the
        # config up front, it subsumes the auto-microbatch search below
        # (that search is the degenerate 1-D case of the plan space)
        self.plan = plan
        if plan is not None:
            if model.remat != plan.remat:
                raise ValueError(
                    f"model was built with remat={model.remat!r} but the "
                    f"launch plan says remat={plan.remat!r}; rebuild the "
                    f"model with the plan's policy")
            train_cfg = plan.apply(train_cfg)
        self.model = model
        self.cfg = train_cfg
        self.shape = shape
        self.source = make_source(train_cfg.data_source, model.arch.vocab,
                                  train_cfg.seed)
        self.inject_failure_at = inject_failure_at
        self.inject_inside_jit = inject_inside_jit
        self._injected = False

        # -- sampling mode (DPConfig.sampling) ---------------------------
        # poisson: variable-size (seed, step)-keyed samples, right-padded
        # to a step-invariant capacity (static shapes -> one compile); the
        # noisy sum is normalized by the *expected* batch size q.N.
        dataset_size = getattr(self.source, "dataset_size", 1_000_000)

        # -- memory plan (MemConfig) -------------------------------------
        # auto_microbatch: pick the largest microbatch (smallest grad_accum)
        # whose estimated peak fits the HBM budget, *before* the capacity /
        # step-fn construction below so the Poisson lcm rounding sees the
        # chosen grad_accum (launch/memory.py owns the search)
        self.mem_estimate = None
        if plan is None and train_cfg.mem.auto_microbatch and \
                train_cfg.mem.hbm_budget_bytes > 0:
            from repro.launch.memory import pick_grad_accum
            accum, est = pick_grad_accum(model, train_cfg, shape,
                                         dataset_size=dataset_size,
                                         shards=batch_multiple)
            if accum != train_cfg.grad_accum:
                print(f"[trainer] auto_microbatch: grad_accum "
                      f"{train_cfg.grad_accum} -> {accum} (estimated "
                      f"per-device peak "
                      f"{est['per_device_peak_bytes'] / 1e9:.3f} GB <= "
                      f"budget "
                      f"{train_cfg.mem.hbm_budget_bytes / 1e9:.3f} GB)")
            train_cfg = dataclasses.replace(train_cfg, grad_accum=accum)
            self.cfg = train_cfg
            self.mem_estimate = est

        self.sampling = train_cfg.dp.sampling
        self.sample_rate = shape.global_batch / dataset_size
        # batch_multiple: the mesh's batch-axis device width (launchers) so
        # the padded capacity stays shardable over the full data axis
        expected_batch = None
        self.capacity = physical_batch_size(train_cfg, shape, dataset_size,
                                            shards=batch_multiple)
        if self.sampling == "poisson":
            expected_batch = float(shape.global_batch)
        else:
            assert self.sampling == "fixed", self.sampling

        self.step_fn = make_train_step(model, train_cfg,
                                       expected_batch_size=expected_batch)
        if inject_failure_at is not None and inject_inside_jit:
            self.step_fn = self._with_injected_failure(self.step_fn)
        if jit_step:
            # No donate_argnums: donating `state` deletes its buffers even
            # when the jitted call fails, so the bit-identical retry in
            # run() would dereference dead arrays.  The old state is
            # instead released by refcount once the step has verifiably
            # succeeded (donate-on-success) at the cost of a transiently
            # higher in-step memory watermark.
            self.step_fn = jax.jit(self.step_fn)
        self.opt = make_optimizer(train_cfg.optim)
        self.ckpt = CheckpointManager(train_cfg.ckpt_dir,
                                      keep=train_cfg.ckpt_keep,
                                      use_async=train_cfg.ckpt_async)
        # the accountant prices the true per-step sample rate: exact under
        # poisson, the standard B/N approximation under fixed batches
        self.accountant = PrivacyAccountant(
            batch_size=shape.global_batch,
            dataset_size=dataset_size,
            noise_multiplier=train_cfg.dp.noise_multiplier,
            delta=train_cfg.dp.delta,
            sample_rate=self.sample_rate)
        # adaptive clipping's noisy below-C count is a second mechanism at
        # the same sampling rate; composing it here makes epsilon_at() the
        # joint guarantee and epsilon_breakdown() the per-mechanism split
        self.adaptive_clip = adaptive_clip_on(train_cfg.dp)
        if self.adaptive_clip:
            self.accountant.compose(
                _aclip.mechanism(train_cfg.dp, self.sample_rate))
        self.shard_batch = shard_batch or (lambda b: jax.tree.map(jnp.asarray, b))
        self._preempted = False
        self._step_times: list = []
        self.history: list = []

    def _with_injected_failure(self, fn: Callable) -> Callable:
        """Fault injection *inside* the jitted computation: the configured
        step's first execution raises from a host callback embedded in the
        step function, exercising the genuine failure mode where XLA aborts
        mid-step (tests/test_trainer_serve.py)."""
        def fail_once(step):
            if int(step) == self.inject_failure_at and not self._injected:
                self._injected = True
                raise RuntimeError("injected transient failure inside jit")
            return np.int32(0)

        def wrapped(state: TrainState, batch, key):
            # io_callback (not pure_callback): the injector is stateful and
            # raises, so it needs the executed-exactly-once, never-cached,
            # never-elided guarantee of an ordered effect
            from jax.experimental import io_callback
            token = io_callback(fail_once,
                                jax.ShapeDtypeStruct((), jnp.int32),
                                state.step, ordered=True)
            # thread the (always-zero) result into the step so the failure
            # is sequenced before the update it aborts
            state = dataclasses.replace(state, step=state.step + token)
            return fn(state, batch, key)
        return wrapped

    # -- memory ------------------------------------------------------------
    def memory_report(self, state, batch, key, compile: bool = True) -> dict:
        """Estimated vs compiled peak memory of the jitted step.

        Returns the launch/memory.py estimate dict plus, when ``compile``
        and the step is jitted, XLA's own ``memory_analysis`` numbers
        (``xla_*`` keys) and the estimate/XLA ratio — the launcher logs
        this once per launch so estimator drift is visible in every run.
        ``compile_s`` is the wall time of that compile (or of its load from
        the persistent compilation cache).

        Scale note: the estimate is *global* (pre-partitioning) while
        XLA's numbers are *per device*, so on an N-device mesh a healthy
        ratio approaches N where sharding is effective (``n_devices`` is
        included in the dict for exactly this normalization).
        """
        from repro.launch.memory import abstract_like, estimate_train_memory
        abstract = abstract_like(batch)
        expected = (float(self.shape.global_batch)
                    if self.sampling == "poisson" else None)
        est = estimate_train_memory(self.model, self.cfg, abstract,
                                    expected_batch_size=expected)
        if compile and hasattr(self.step_fn, "lower"):
            t0 = time.perf_counter()
            compiled = self.step_fn.lower(state, batch, key).compile()
            est["compile_s"] = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            if mem is not None:
                xla_total = (mem.temp_size_in_bytes
                             + mem.argument_size_in_bytes
                             + mem.output_size_in_bytes)
                est.update({
                    "xla_temp_bytes": int(mem.temp_size_in_bytes),
                    "xla_argument_bytes": int(mem.argument_size_in_bytes),
                    "xla_output_bytes": int(mem.output_size_in_bytes),
                    "xla_peak_bytes": int(xla_total),
                    "n_devices": jax.device_count(),
                    "estimate_vs_xla": est["peak_bytes"] / max(xla_total, 1),
                })
        return est

    # -- lifecycle ---------------------------------------------------------
    def init_state(self, key) -> TrainState:
        params = self.model.init(key)
        init = make_opt_init(self.cfg, self.opt)
        return TrainState.create(params, init(params))

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct tree of the TrainState — what launchers feed
        ``state_shardings`` *before* restore so a sharded checkpoint is
        assembled directly onto its destination devices."""
        return jax.eval_shape(
            lambda: self.init_state(jax.random.PRNGKey(self.cfg.seed)))

    def restore_or_init(self, key, shardings=None) -> TrainState:
        """``shardings``: optional TrainState-shaped tree of shardings for
        the *current* mesh.  Threaded through to ``ckpt.restore`` so a
        multi-device launch reshards directly from disk (each device reads
        its own shard) instead of restoring the whole state to the default
        single-device placement first — the OOM path on large states."""
        if self.ckpt.latest_step() is not None:
            like = jax.eval_shape(lambda: self.init_state(key))
            state = self.ckpt.restore(like, shardings=shardings)
            print(f"[trainer] restored step {int(state.step)} "
                  f"from {self.cfg.ckpt_dir}")
            return state
        return self.init_state(key)

    def _handle_preempt(self, signum, frame):
        self._preempted = True

    def make_batch(self, step: int):
        """The step's (seed, step)-keyed batch under the configured
        sampling mode.  Poisson batches carry a ``"mask"`` validity leaf
        and a step-invariant physical *example* count (``self.capacity``).
        Under ``dp.augmult = K > 1`` the sampled batch is then expanded to
        K deterministic views per example (capacity·K rows, b-major/
        k-minor; the mask broadcasts over K) — augmentation happens after
        sampling, so the privacy unit stays the example.

        Records the spans ``sample`` and (K > 1) ``augment`` and the
        counters ``rows`` (physical rows) and ``real_rows`` (the mask's
        sum) under ``step`` in ``telemetry``."""
        with telemetry.span("sample", step):
            if self.sampling == "poisson":
                batch = poisson_batch_for(self.source, self.model.arch,
                                          self.shape, step,
                                          capacity=self.capacity,
                                          sample_rate=self.sample_rate)
            else:
                batch = batch_for(self.source, self.model.arch, self.shape,
                                  step)
        if self.cfg.dp.augmult > 1:
            with telemetry.span("augment", step):
                batch = augment_expand(batch, self.cfg.dp.augmult,
                                       self.cfg.seed, step)
        rows = len(next(iter(batch.values())))
        telemetry.count("rows", step, rows)
        telemetry.count("real_rows", step, int(np.sum(batch["mask"]))
                        if "mask" in batch else rows)
        return batch

    # -- loop ---------------------------------------------------------------
    def run(self, state: TrainState, steps: Optional[int] = None,
            install_signals: bool = True) -> TrainState:
        cfg = self.cfg
        steps = steps if steps is not None else cfg.steps
        old_handlers = {}
        if install_signals:
            for sig in (signal.SIGTERM, signal.SIGINT):
                old_handlers[sig] = signal.signal(sig, self._handle_preempt)
        try:
            start = int(state.step)
            # since: first step of the next history record; last: last step run
            since = last = start
            for step in range(start, steps):
                last = step
                telemetry.start(step)
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    b = self.make_batch(step)
                    with telemetry.span("transfer", step):
                        batch = self.shard_batch(b)
                    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                             step)
                    t0 = time.perf_counter()
                    for attempt in range(3):   # transient-failure retry
                        try:
                            if (self.inject_failure_at == step
                                    and not self.inject_inside_jit
                                    and not self._injected):
                                self._injected = True
                                raise RuntimeError(
                                    "injected transient failure")
                            # keep `state` bound to the last good state
                            # until the step has fully completed: with async
                            # dispatch a failure inside the jitted
                            # computation can surface at the
                            # block_until_ready, after step_fn already
                            # returned poisoned arrays
                            with telemetry.span("dispatch", step):
                                new_state, metrics = self.step_fn(
                                    state, batch, key)
                            with telemetry.span("device_wait", step):
                                jax.block_until_ready(metrics["loss"])
                            state = new_state
                            break
                        except RuntimeError as e:
                            telemetry.count("retries", step)
                            print(f"[trainer] step {step} attempt {attempt} "
                                  f"failed: {e}; retrying")
                            if attempt == 2:
                                raise
                    dt = time.perf_counter() - t0
                    self._watchdog(step, dt)
                    if (step + 1) % cfg.log_every == 0 or step == steps - 1:
                        with telemetry.span("log", step):
                            self._log(step, dt, metrics, since)
                        since = step + 1
                    if (step + 1) % cfg.ckpt_every == 0 \
                            or step == steps - 1 or self._preempted:
                        with telemetry.span("checkpoint", step):
                            self.ckpt.save(state, step + 1)
                if self._preempted:
                    print(f"[trainer] preempted at step {step}; "
                          f"checkpoint saved, exiting")
                    break
            with telemetry.span("checkpoint", last):
                self.ckpt.wait()
            return state
        finally:
            for sig, h in old_handlers.items():
                signal.signal(sig, h)

    def _log(self, step: int, dt: float, metrics, since: int) -> None:
        """Append the history record of ``step`` and print its line.  The
        record carries the step's metrics, the privacy spent, and from
        ``telemetry`` the mean ms per step of each span (``<span>_ms``)
        and the counters' sums over steps ``since`` .. ``step``."""
        eps = self.accountant.epsilon_at(step + 1)
        rec = {k: float(v) for k, v in metrics.items()}
        rec.update(step=step, sec=dt, epsilon=eps,
                   expected_batch=self.shape.global_batch)
        rec.update(telemetry.RECORDER.summary(range(since, step + 1)))
        eps_str = f"eps {eps:.3f}"
        if len(self.accountant.mechanisms) > 1:
            # per-mechanism split (eps_grad / eps_clip / ...):
            # solo epsilons plus the composed total
            bd = self.accountant.epsilon_breakdown(step + 1)
            rec.update({k: float(v) for k, v in bd.items()})
            parts = " ".join(f"{k[4:]} {v:.3f}" for k, v in bd.items()
                             if k != "eps_total")
            eps_str = f"eps {bd['eps_total']:.3f} ({parts})"
        self.history.append(rec)
        realized = ""
        if self.sampling == "poisson":
            realized = (f"B {rec['realized_batch']:.0f}"
                        f"/{self.shape.global_batch} ")
        host = sum(rec.get(f"{n}_ms") or 0.0
                   for n in ("sample", "augment", "transfer"))
        print(f"[trainer] step {step:5d} loss {rec['loss']:.4f} {eps_str} "
              f"{realized}host {host:.0f} ms ({dt*1e3:.0f} ms)")

    def _watchdog(self, step: int, dt: float) -> None:
        self._step_times.append(dt)
        hist = self._step_times[-50:]
        med = float(np.median(hist))
        if len(hist) >= 5 and dt > self.cfg.watchdog_factor * med:
            telemetry.count("stragglers", step)
            print(f"[trainer] WATCHDOG straggler: step {step} took "
                  f"{dt:.2f}s (median {med:.2f}s)")
