"""Heap pops of the lazy TMFG loop per vertex it inserted, over the
window's calls (the program's ``tmfg_pops`` and ``tmfg_inserts``
counters): 1.0 would mean no pop ever found a stale gain."""

import program_spans


def read(ctx):
    return program_spans.ratio(ctx, "tmfg_pops", "tmfg_inserts")
