"""Share of the sparse lazy TMFG's pair values that the top-K table did
not hold and that were computed from the series, over the window's calls:
100 x approx_pair_misses / approx_pair_lookups."""

import program_spans


def read(ctx):
    r = program_spans.ratio(ctx, "approx_pair_misses", "approx_pair_lookups")
    return None if r is None else 100.0 * r
