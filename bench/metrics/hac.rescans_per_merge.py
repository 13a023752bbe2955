"""Steps of the HAC's row-rescan loop per merge, over the window's calls
(the program's ``hac_rescans`` and ``hac_merges`` counters; a step
rescans up to eight rows)."""

import program_spans


def read(ctx):
    return program_spans.ratio(ctx, "hac_rescans", "hac_merges")
