"""Device time of the per-example norm kernels over device busy time: the
Mosaic custom calls whose instruction name, less XLA's ``.N`` suffix, is
``gram_norm_kernel``, ``pegrad_norm_kernel`` or ``_fused_kernel`` (the
``name`` each ``pallas_call`` gives its kernel).  The fused dense backward
computes the input gradient (dgrad) in the same kernel as the norms, so
its whole time counts here, dgrad included.  Nothing to read where no
custom call carries one of these names."""

KERNELS = ("gram_norm_kernel", "pegrad_norm_kernel", "_fused_kernel")


def read(ctx):
    r = ctx.trace
    if r is None or r.busy_s <= 0:
        return None
    s = sum(seconds for name, seconds in r.calls
            if name in r.kernels and name.rsplit(".", 1)[0] in KERNELS)
    s /= r.n_devices
    return 100.0 * s / r.busy_s if s > 0 else None
