"""The top-K scan kernel's share of its roofline: the least time of each
call inside the traced window (2 n^2 L operations at the bf16 peak, or the
series read and the (n, K) table written at the HBM peak, whichever is
longer), over the summed device time of those calls.

The kernel's output is a (values, indices) tuple, which
``Trace.kernel_batches`` does not parse, so its calls are the ops named
after it: the scan opens a call, so each one lies whole inside the
window."""

import roofline
import roofline_topk
import tracing


def read(ctx):
    got = tracing.kernel_seconds(ctx.trace, "topk_rows_pallas")
    if got is None:
        return None
    calls, secs = got
    n, L = ctx.shape["n"], ctx.shape["L"]
    K = min(int(ctx.config["pipeline"]["args"]["sim_k"]), n - 1)
    one = roofline.least_seconds(*roofline_topk.topk_counts(n, L, K),
                                 ctx.peak)
    return 100.0 * calls * one / secs
