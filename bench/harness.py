"""One run of one cell: set-up, the measured window, the check.

The entry the window drives is the program's own training loop,
``repro.train.Trainer.run``: sampling, augmult expansion, host-to-device
transfer, the jitted DP step, the optimizer and the accountant.  The
harness builds the model and the ``Trainer`` as ``repro.launch.train``
does, gives it weights drawn from ``--seed``, and wraps four methods on the
instance to time them (``make_batch``, ``shard_batch``, ``step_fn``,
``_watchdog``); it changes nothing they do.  The checkpoint manager is
replaced by one that writes nothing, so that runs do not fill the disk.

Set-up runs the first three steps one ``run`` call each (the check
compares them with the reference), then warm-up steps until both step
executables are loaded and a loop period is known.  The window is a whole
number of loop periods, from one ``make_batch`` call to a later one.  A
compilation inside it fails the run.  A traced run measures the same
window untraced, then traces ``TRACE_PERIODS`` more periods: the profiler
slows the host loop, so rates and host spans come from the untraced
window and device busy time, kernels and idle gaps from the traced one.

Everything a cell needs is found by name: ``configs/<config>.py`` and
``configs/<config>.ref.py`` and ``.json``, ``mixes/<traffic>.json``,
``metrics/<metric>.py`` and ``limits/<workload>.json``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
FIRST_STEPS = 3        # steps the reference follows
WARM_STEPS = 3         # warm-up steps after them; the period is read here
TRACE_PERIODS = 4      # periods a traced run traces, after its window


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    conf: dict
    cfg: object            # configs/<config>.py
    ref: object            # configs/<config>.ref.py
    mix: dict
    limits: dict
    chips: int
    mesh: dict             # axis -> size, in the order written
    end_to_end: list
    per_layer: list


def load_cell(spec: dict, workload: str, overrides: dict | None = None
              ) -> Cell:
    """The cell's files, found by name.  ``overrides`` ({"conf": {...},
    "mix": {...}}) shrinks a cell for the CPU tests only."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cdir = BENCH / "configs"
    conf = json.loads((ROOT / conf_entry["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())
    lim_path = BENCH / "limits" / f"{workload}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.exists() else {}
    conf.update((overrides or {}).get("conf", {}))
    mix.update((overrides or {}).get("mix", {}))
    chips = int(w["chips"])
    applies = lambda m: workload in m.get("workloads", [workload])
    return Cell(workload, conf,
                load_module(cdir / f"{w['config']}.py", "bench_cfg"),
                load_module(cdir / f"{w['config']}.ref.py", "bench_ref"),
                mix, limits, chips, mesh_of(mix, chips),
                [m for m in spec["end_to_end"] if applies(m)],
                [m for m in spec["per_layer"] if applies(m)])


def mesh_of(mix: dict, chips: int) -> dict:
    """The cell's mesh: the mix's ``mesh`` ({axis: size}, axes in the
    order written), or every chip on ``data``.  Its devices have to be
    the cell's chips."""
    mesh = {str(a): int(n) for a, n in
            (mix.get("mesh") or {"data": chips}).items()}
    if math.prod(mesh.values()) != chips:
        raise ValueError(f"the mix's mesh {mesh} spans "
                         f"{math.prod(mesh.values())} devices; the cell "
                         f"asks for {chips} chip(s)")
    return mesh


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of the loop, on the host clock, per step.  In a traced
    run each span is also a ``TraceAnnotation`` in the profiler's trace
    (``bench.<name>``), so idle gaps on the device can be tagged with what
    the host was doing."""

    def __init__(self):
        self.iter_t: dict = {}          # step -> time make_batch was called
        self.per_step: dict = {}        # step -> {span: seconds}
        self.rows: dict = {}            # step -> (physical rows, real rows)
        self.annotate = False
        self._open: dict = {}
        self.step = None

    def begin(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        self._open[name] = (time.perf_counter(), ann)

    def end(self, name: str):
        if name not in self._open:
            return
        t0, ann = self._open.pop(name)
        if ann is not None:
            ann.__exit__(None, None, None)
        d = self.per_step.setdefault(self.step, {})
        d[name] = d.get(name, 0.0) + time.perf_counter() - t0

    @contextlib.contextmanager
    def span(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)


def instrument(trainer, spans: Spans, keep_batches: dict):
    make, shard, step_fn, watchdog = (trainer.make_batch, trainer.shard_batch,
                                      trainer.step_fn, trainer._watchdog)

    def make_batch(step):
        spans.end("bookkeeping")
        spans.step = step
        spans.iter_t[step] = time.perf_counter()
        with spans.span("batch_build"):
            b = make(step)
        if step < FIRST_STEPS:
            keep_batches[step] = b
        rows = len(next(iter(b.values())))
        real = int(np.sum(b["mask"])) if "mask" in b else rows
        spans.rows[step] = (rows, real)
        return b

    def shard_batch(b):
        with spans.span("transfer"):
            return shard(b)

    def step(*args):
        with spans.span("dispatch"):
            out = step_fn(*args)
        spans.begin("device_wait")
        return out

    def wd(step, dt):
        spans.end("device_wait")
        spans.begin("bookkeeping")
        return watchdog(step, dt)

    trainer.make_batch, trainer.shard_batch = make_batch, shard_batch
    trainer.step_fn, trainer._watchdog = step, wd
    return step_fn


class NullCheckpoints:
    """Stands in for the checkpoint manager: the benchmark measures the
    loop, and a run must not write the state to disk."""

    def save(self, state, step, extra=None):
        pass

    def wait(self):
        pass

    def latest_step(self):
        return None


# ---------------------------------------------------------------------------
# compilations inside the window
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts JAX compile and cache-load events while armed."""

    def __init__(self):
        import jax
        self.armed, self.events = False, []

        def on_duration(event, duration, **kw):
            if self.armed and (event.startswith("/jax/core/compile")
                               or event.startswith("/jax/compilation_cache")):
                self.events.append(event)

        def on_event(event, **kw):
            on_duration(event, 0.0)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def make_trainer(cell: Cell, seed: int, ckpt_dir: str, mesh):
    """The program's model and ``Trainer`` for the cell on ``mesh``, as
    ``repro.launch.train`` builds them; no state yet."""
    from repro.configs.base import (DPConfig, MemConfig, OptimConfig,
                                    ShapeConfig, TrainConfig)
    from repro.dist.sharding import batch_axis_width
    from repro.models import build_model_for
    from repro.train import Trainer

    mix, conf = cell.mix, cell.conf
    arch = cell.cfg.arch(conf)
    opt = mix["optimizer"]
    cfg = TrainConfig(
        arch=arch.name, shape="bench", seed=seed, log_every=mix["log_every"],
        ckpt_every=10**9, ckpt_dir=ckpt_dir, remat=mix["remat"],
        param_dtype=mix["param_dtype"], compute_dtype=mix["compute_dtype"],
        grad_accum=mix["grad_accum"],
        dp=DPConfig(algo=mix["algo"], clip_norm=mix["clip_norm"],
                    noise_multiplier=mix["noise_multiplier"],
                    sampling=mix["sampling"],
                    norm_strategy=mix["norm_strategy"],
                    use_kernels=mix["use_kernels"], augmult=mix["augmult"]),
        optim=OptimConfig(name=opt["name"], lr=opt["lr"],
                          momentum=opt["momentum"],
                          schedule=opt["schedule"]),
        mem=MemConfig(compiled_check=False))
    shape = ShapeConfig("bench", mix["seq_len"], mix["batch"], "train")
    model = build_model_for(arch, param_dtype=cfg.param_dtype,
                            compute_dtype=cfg.compute_dtype, remat=cfg.remat,
                            pp_stages=1, pp_microbatches=0)
    trainer = Trainer(model, cfg, shape, batch_multiple=batch_axis_width(mesh))
    trainer.ckpt = NullCheckpoints()
    return trainer


def build_program(cell: Cell, seed: int, ckpt_dir: str):
    """Model, trainer, the initial state, a function that draws its
    weights again, and the mesh contexts, as ``repro.launch.train`` builds
    them, on the cell's mesh, with the benchmark's weights drawn into the
    state's shardings.  The harness keeps no copy of the first weights
    while the program runs: the change is taken from a second draw."""
    import jax
    from repro.dist import batch_shardings, runtime, state_shardings
    from repro.dist.sharding import batch_pspec
    from repro.launch.mesh import make_mesh
    from repro.train.state import TrainState
    from repro.train.trainer import make_opt_init
    from bench import weights as W

    mix, conf = cell.mix, cell.conf
    mesh = make_mesh(list(cell.mesh.values()), list(cell.mesh))
    trainer = make_trainer(cell, seed, ckpt_dir, mesh)
    model, cfg = trainer.model, trainer.cfg
    phys = trainer.capacity

    layout = cell.cfg.layout(conf, mix)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(W.abstract(layout)) or \
            jax.tree.leaves(want) != jax.tree.leaves(W.abstract(layout)):
        raise ValueError("the configuration's layout is not the program's "
                         "parameter tree")
    ctx = contextlib.ExitStack()
    ctx.enter_context(mesh)
    ctx.enter_context(runtime.layout(mesh, batch_pspec(mesh, phys)))

    def shard_batch(b):
        abs_tree = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b)
        sh = batch_shardings(mesh, abs_tree, phys)
        return jax.tree.map(lambda a, s: jax.device_put(a, s), b, sh)

    trainer.shard_batch = shard_batch
    sh = state_shardings(mesh, model, trainer.abstract_state(), zero1=cfg.zero1)
    params0 = W.make(layout, seed, sh.params)
    opt0 = jax.jit(make_opt_init(cfg, trainer.opt),
                   out_shardings=sh.opt_state)(params0)
    state = TrainState.create(params0, opt0)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    return trainer, state, lambda: W.make(layout, seed, sh.params), ctx


def _grad_from_opt(mix: dict, opt_state):
    """The first gradient as the optimizer got it, from its state after
    one step (SGD momentum starts at zero, so it holds the gradient)."""
    assert mix["optimizer"]["name"] == "sgd"
    return opt_state["mom"]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices=None,
             plant=None, overrides: dict | None = None) -> dict:
    """Set-up, window, check; returns the result line's object.

    ``devices``: the devices to run on (the caller checks that they are the
    cell's chips).  ``plant``: a function ``plant(trainer)`` that breaks
    the timed path, for the fault tests only."""
    import jax
    from bench import reference, trace as T
    from bench.peaks import peaks

    cell = load_cell(spec, workload, overrides)
    devices = devices or jax.devices()[:cell.chips]
    dev = devices[0]
    peak = peaks(dev.device_kind) if dev.platform == "tpu" else None
    watch = CompileWatch()
    spans = Spans()
    batches: dict = {}
    ckpt_dir = tempfile.mkdtemp(prefix="bench-ckpt-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        trainer, state, weights0, ctx = build_program(cell, seed, ckpt_dir)
        with ctx:
            if plant is not None:
                plant(trainer)
            step_jit = instrument(trainer, spans, batches)
            prog = {}
            for s in range(FIRST_STEPS):
                state = trainer.run(state, s + 1, install_signals=False)
                if s == 0:
                    prog["grad_norms"] = reference.noise_free_norms(
                        _grad_from_opt(cell.mix, state.opt_state), seed,
                        reference.noise_terms(cell.mix, 1))
            prog["change_norms"] = reference.noise_free_norms(
                state.params, seed,
                reference.noise_terms(cell.mix, FIRST_STEPS),
                base=weights0())
            hist = trainer.history[-FIRST_STEPS:]
            for k, hk in (("loss", "loss"), ("norm_mean", "grad_norm_mean"),
                          ("norm_max", "grad_norm_max")):
                prog[k] = [h.get(hk, np.nan) for h in hist]
            s0 = FIRST_STEPS + WARM_STEPS
            state = trainer.run(state, s0, install_signals=False)
            period = float(np.median([spans.iter_t[s + 1] - spans.iter_t[s]
                                      for s in range(FIRST_STEPS, s0 - 1)]))
            periods = max(1, int(round(seconds / period)))
            watch.armed = True
            end = s0 + periods
            state = trainer.run(state, end + 1, install_signals=False)
            t0, t1 = spans.iter_t[s0], spans.iter_t[end]
            window_s = t1 - t0
            if trace:
                # a traced stretch after the measured window: the profiler
                # slows the loop, so rates and host spans come from above
                end = _traced(trainer, spans, trace_dir, end + 1)
                state = trainer.run(state, end + 1, install_signals=False)
            watch.armed = False
            mem = [d.memory_stats() or {} for d in devices]
            # the TPU runtime keeps an executable's temporaries in its
            # reserved region, apart from the buffers counted in use
            peak_bytes = max(m.get("peak_bytes_in_use", 0)
                             + m.get("peak_bytes_reserved", 0) for m in mem)
            print(f"memory_stats: {mem[0]}", file=sys.stderr)
            hlo = None
            if trace:
                jax.profiler.stop_trace()
                b = trainer.shard_batch(trainer.make_batch(end + 1))
                key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
                compiled = step_jit.lower(state, b, key).compile()
                hlo = compiled.as_text()
                ma = compiled.memory_analysis()
                print(f"compiled step: temp {ma.temp_size_in_bytes} B, "
                      f"arguments {ma.argument_size_in_bytes} B, outputs "
                      f"{ma.output_size_in_bytes} B", file=sys.stderr)
            window_steps = range(s0, s0 + periods)
            per_step = [spans.per_step.get(s, {}) for s in window_steps]
            rows = [spans.rows[s] for s in window_steps]
            steps_run = int(state.step) == end + 1
        del state, trainer
        gc.collect()

        compiled_in_window = len(watch.events)
        ex_per_step = float(cell.mix["batch"])
        ctxm = SimpleNamespace(
            cell=cell, spans=per_step, rows=rows, window_s=window_s,
            steps=periods, examples_per_step=ex_per_step,
            flops_per_example=cell.cfg.flops_per_example(cell.conf, cell.mix),
            peak=peak, chips=len(devices), trace=None)
        if trace:
            ctxm.trace = T.reduce(T.find_xplane(trace_dir), hlo)
            print(f"trace: busy {ctxm.trace.busy_s!r} s, collectives "
                  f"{ctxm.trace.collective_s!r} s, exposed "
                  f"{ctxm.trace.exposed_collective_s!r} s of "
                  f"{ctxm.trace.window_s!r} s", file=sys.stderr)

        # -- correctness: the reference, after the program's state is freed
        t_ref = time.perf_counter()
        nums = check_against_reference(cell, seed, prog, batches, devices)
        print(f"reference: {time.perf_counter() - t_ref:.1f} s",
              file=sys.stderr)
        nums["window_compiles"] = float(compiled_in_window)
        from bench import check
        limits = dict(cell.limits, window_compiles=0)
        correct, checks = check.judge(nums, limits)
        if not steps_run:
            correct = False

        metrics = {}
        if not trace:
            vals = {"setup_s": t0 - t_start,
                    "examples_per_s": periods * ex_per_step / window_s,
                    "peak_hbm_gb": peak_bytes / 1e9}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
        else:
            for m in cell.per_layer:
                reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     "bench_metric")
                v = reader.read(ctxm)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(peak_bytes)}
        out = {"correct": bool(correct), "attempted": periods,
               "failed": 0, "metrics": metrics, "device": device}
        if trace:
            r = ctxm.trace
            device.update(busy_s=r.busy_s, window_s=r.window_s)
            out["breakdown"] = {"device_ops": r.top_ops(10),
                                "idle_gaps": r.top_gaps(10)}
        out["checks"] = checks
        return out
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _traced(trainer, spans: Spans, trace_dir: str, first: int) -> int:
    """Start the profiler and mark TRACE_PERIODS loop periods from step
    ``first`` as the traced window (``bench.window``); returns the step at
    whose ``make_batch`` the window closes."""
    import jax
    spans.annotate = True
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0           # no Python call events
    opts.host_tracer_level = 1             # annotations only
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = jax.profiler.TraceAnnotation("bench.window")
    last = first + TRACE_PERIODS
    make = trainer.make_batch

    def make_batch(step):
        if step == first:
            win.__enter__()
        elif step == last:
            win.__exit__(None, None, None)
        return make(step)

    trainer.make_batch = make_batch
    return last


def reference_weights(cell: Cell, seed: int, devices):
    """``draw()``: the benchmark's weights, where the reference keeps
    them on the cell's ``devices`` (``reference.shardings``)."""
    from bench import reference, weights as W
    layout = cell.cfg.layout(cell.conf, cell.mix)
    sh = reference.shardings(W.abstract(layout), devices)
    return lambda: W.make(layout, seed, sh)


def check_against_reference(cell: Cell, seed: int, prog: dict,
                            batches: dict, devices) -> dict:
    """Rebuild the first batches, run the reference over them from the
    benchmark's weights on the cell's ``devices``, and return the
    compared numbers."""
    from bench import check, reference
    want = [cell.cfg.batch(cell.conf, cell.mix, seed, s)
            for s in range(FIRST_STEPS)]
    got = [batches.get(s, {}) for s in range(FIRST_STEPS)]
    prog = dict(prog, batch_mismatch=check.batch_mismatch(got, want))
    ref = reference.run(cell.ref.row_losses, cell.conf, cell.mix,
                        reference_weights(cell, seed, devices), want, seed,
                        reference.Mm(), block=cell.mix["reference_block"])
    return check.numbers(prog, ref, cell.mix["algo"] != "sgd")
