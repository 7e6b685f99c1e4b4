"""Work of one call of the fused dense backward (``_fused_kernel``),
counted the same whatever implements it.

For x (BG, T, di), gy (BG, T, do), w (E, di, do) -> gx (BG, T, di) and one
norm² per row group:

* FLOPs: the activation-gradient matmul, 2·BG·T·di·do, plus each group's
  norm² by the cheaper exact rule over its T rows: materialise the
  (di, do) gradient and square it (2·T·di·do + 2·di·do), or the Gram form
  (2·T²·(di + do) + 2·T²).
* Bytes: x, gy and w read once, gx and the norms written once, at their
  dtypes.
* Least time: the larger of FLOPs over peak FLOP/s and bytes over peak
  bytes/s; ``bound`` says which.
"""
from __future__ import annotations

BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "f8e4m3fn": 1, "s32": 4}


def dense_bwd_norm(BG, T, di, do, E=1, x_b=2, gy_b=2, w_b=2, gx_b=2):
    """(flops, bytes) of one call."""
    dgrad = 2 * BG * T * di * do
    materialise = 2 * T * di * do + 2 * di * do
    gram = 2 * T * T * (di + do) + 2 * T * T
    flops = dgrad + BG * min(materialise, gram)
    nbytes = (BG * T * di * (x_b + gx_b) + BG * T * do * gy_b
              + E * di * do * w_b + BG * 4)
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, "compute" | "memory")."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def from_call(info: dict):
    """(flops, bytes) of a custom call from its HLO shapes:
    operands x, gy, w; results gx, norms."""
    (xd, (BG, T, di)), (gyd, (_, _, do)), (wd, (E, _, _)) = info["operands"][:3]
    gxd = info["results"][0][0]
    return dense_bwd_norm(BG, T, di, do, E, BYTES[xd], BYTES[gyd], BYTES[wd],
                          BYTES[gxd])
