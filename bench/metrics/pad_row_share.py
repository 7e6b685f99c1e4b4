"""Padded rows over physical rows in the window's batches, from the
Poisson mask; nothing to read under fixed sampling."""


def read(ctx):
    if ctx.cell.mix["sampling"] != "poisson":
        return None
    phys = sum(r for r, _ in ctx.rows)
    real = sum(k for _, k in ctx.rows)
    return 100.0 * (phys - real) / phys if phys else None
