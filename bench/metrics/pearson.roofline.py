"""The Pearson kernel's share of its roofline: for each call of the kernel
inside the traced window and each (n, L) -> (n, n) matrix of its batch,
the least time (2 n^2 L operations at the bf16 peak, or n L + n^2 floats
at the HBM peak, whichever is longer), over the summed device time of
those calls."""

import roofline


def read(ctx):
    t = ctx.trace
    calls = t.kernel_batches("pearson_pallas") if t is not None else []
    secs = sum(s for _, s in calls)
    if not calls or secs <= 0:
        return None
    one = roofline.least_seconds(
        *roofline.pearson_counts(ctx.shape["n"], ctx.shape["L"]), ctx.peak)
    return 100.0 * sum(b for b, _ in calls) * one / secs
