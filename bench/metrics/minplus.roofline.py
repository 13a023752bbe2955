"""The min-plus kernel's share of its roofline, bounded by bytes only (no
peak of the VPU, where min-plus runs, is published): each call of the
traced window's APSP stage moves its compulsory bytes (each operand read
once, the result written once) for each of the problems of its batch, at
the HBM peak, over the summed device time of those calls."""

import roofline


def read(ctx):
    t = ctx.trace
    calls = t.kernel_batches("minplus_pallas") if t is not None else []
    secs = sum(s for _, s in calls)
    if not calls or secs <= 0:
        return None
    nbytes = sum(b for b, _ in calls) * roofline.apsp_product_bytes(
        ctx.shape["n"])
    return 100.0 * roofline.least_seconds(0.0, nbytes, ctx.peak,
                                          bytes_only=True) / secs
