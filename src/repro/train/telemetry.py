"""Host spans and counters of the training loop, kept by step number.

One recorder per process, as ``jax.monitoring`` is one registry per
process: ``Trainer`` writes to it; operators, benchmark readers and tests
read it by step number.

* ``span(name, step)`` adds the host-clock duration of its body to the
  step's record, and opens ``jax.profiler.TraceAnnotation("train.<name>")``
  so that, while a profiler trace runs, the span sits on the profiler's
  clock beside the device ops.
* ``count(name, step, n)`` adds ``n`` to a counter of the step.
* ``start(step)`` gives the step a fresh record (the loop calls it at the
  top of each iteration, so a second run over the same step numbers does
  not add to the first run's records).

Only the last ``MAX_STEPS`` steps are kept.  Every profiler event is named
under ``train.``, so none falls under ``bench.``, the prefix of the
benchmark harness's own spans.  Nothing here reads a device value or waits
for the device.  The loop writes from one thread.
"""
from __future__ import annotations

import contextlib
import time
from collections import OrderedDict
from typing import Iterable, Optional

import jax

PREFIX = "train."
MAX_STEPS = 4096


class Recorder:
    """Per-step span seconds and counter sums for the last ``max_steps``
    steps."""

    def __init__(self, max_steps: int = MAX_STEPS):
        self.max_steps = max_steps
        self._steps: OrderedDict = OrderedDict()   # step -> (spans, counts)

    def _record(self, step: int):
        rec = self._steps.get(step)
        if rec is None:
            rec = self._steps[step] = ({}, {})
            if len(self._steps) > self.max_steps:
                self._steps.popitem(last=False)
        return rec

    def start(self, step: int) -> None:
        self._steps.pop(step, None)
        self._record(step)

    @contextlib.contextmanager
    def span(self, name: str, step: int):
        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                spans = self._record(step)[0]
                spans[name] = spans.get(name, 0.0) + time.perf_counter() - t0

    def count(self, name: str, step: int, n: int = 1) -> None:
        counts = self._record(step)[1]
        counts[name] = counts.get(name, 0) + n

    def steps(self) -> list:
        return list(self._steps)

    def spans(self, step: int) -> dict:
        """{span: seconds} of one step ({} if not recorded)."""
        return dict(self._steps.get(step, ({}, {}))[0])

    def counts(self, step: int) -> dict:
        return dict(self._steps.get(step, ({}, {}))[1])

    def mean_ms(self, name: str, steps: Iterable[int]) -> Optional[float]:
        """Milliseconds of span ``name`` per recorded step among ``steps``;
        None if no such step recorded it."""
        recs = [self._steps[s][0] for s in steps if s in self._steps]
        if not any(name in r for r in recs):
            return None
        return 1e3 * sum(r.get(name, 0.0) for r in recs) / len(recs)

    def summary(self, steps: Iterable[int]) -> dict:
        """``<span>_ms`` (mean per recorded step) for every span, and the
        sum of every counter, over ``steps``."""
        steps = [s for s in steps if s in self._steps]
        names = {n for s in steps for n in self._steps[s][0]}
        out = {f"{n}_ms": self.mean_ms(n, steps) for n in sorted(names)}
        for s in steps:
            for n, v in self._steps[s][1].items():
                out[n] = out.get(n, 0) + v
        return out

    def clear(self) -> None:
        self._steps.clear()


RECORDER = Recorder()
span, count, start = RECORDER.span, RECORDER.count, RECORDER.start
