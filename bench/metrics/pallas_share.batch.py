"""Device time of the Pallas kernels over the device's busy time; the rest
is XLA: the TMFG and HAC loops, Bellman-Ford's control, the DBHT."""

import tracing


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s() <= 0:
        return None
    secs = sum(t.kernel(k)[1] for k in tracing.PALLAS_KERNELS)
    return 100.0 * secs / t.busy_s()
