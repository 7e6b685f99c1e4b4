"""Mean host time per window step in the program's own span
``train.sample`` (``repro.train.telemetry``, inside
``Trainer.make_batch``): the Poisson draw or fixed batch, the per-example
materialisation and the padding.  The window's steps are the untraced ones
that ``host_batch_ms`` reads.  Nothing to read where the program keeps no
such recorder."""
from bench.harness import FIRST_STEPS, WARM_STEPS

SPAN = "sample"


def read(ctx):
    try:
        from repro.train.telemetry import RECORDER
    except ImportError:
        return None
    first = FIRST_STEPS + WARM_STEPS
    return RECORDER.mean_ms(SPAN, range(first, first + ctx.steps))
