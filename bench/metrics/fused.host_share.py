"""Share of the fused calls' own time in which the host, not the device,
held the result: 100 x (pipeline.fused - pipeline.device) / pipeline.fused,
summed over the window's calls.  The rest is the input's transfer, the
dispatch and the assembly of the results on the host."""

import program_spans


def read(ctx):
    calls = [sp for sp in program_spans.window_calls(ctx)
             if "device_s" in sp.attrs]
    total = sum(sp.duration for sp in calls)
    if not calls or total <= 0:
        return None
    return 100.0 * sum(sp.duration - sp.attrs["device_s"]
                       for sp in calls) / total
