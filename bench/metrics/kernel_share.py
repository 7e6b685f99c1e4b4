"""Device time in Mosaic custom calls (the Pallas kernels) over device
busy time."""


def read(ctx):
    r = ctx.trace
    if r is None or not r.kernels or r.busy_s <= 0:
        return None
    s = r.custom_seconds() / r.n_devices
    return 100.0 * s / r.busy_s if s > 0 else None
