"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (window length)."""

import tracing


def read(ctx):
    return tracing.idle_pct(ctx.trace)
