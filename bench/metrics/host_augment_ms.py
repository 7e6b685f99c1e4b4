"""Mean host time per window step in the program's own span
``train.augment`` (``repro.train.telemetry``, inside
``Trainer.make_batch``): augmult's K - 1 crop-and-flip views of each
example.  The window's steps are the untraced ones that ``host_batch_ms``
reads.  Nothing to read without augmult, or where the program keeps no
such recorder."""
from bench.harness import FIRST_STEPS, WARM_STEPS

SPAN = "augment"


def read(ctx):
    try:
        from repro.train.telemetry import RECORDER
    except ImportError:
        return None
    first = FIRST_STEPS + WARM_STEPS
    return RECORDER.mean_ms(SPAN, range(first, first + ctx.steps))
