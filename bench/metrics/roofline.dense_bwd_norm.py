"""Roofline share of the fused dense backward (``_fused_kernel``): the sum
over its calls of the least time their work needs (bench/work.py) over the
sum of their measured device time.  Each call's shapes come from the
compiled step's HLO."""
from bench import work

KERNEL = "_fused_kernel"


def read(ctx):
    r = ctx.trace
    if r is None or ctx.peak is None:
        return None
    least = measured = 0.0
    for name, seconds in r.calls:
        info = r.kernels.get(name)
        if info is None or info["kernel"] != KERNEL:
            continue
        least += work.least_time(*work.from_call(info), ctx.peak)[0]
        measured += seconds
    return 100.0 * least / measured if measured > 0 else None
