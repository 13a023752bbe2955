"""Share of the sparse lazy TMFG's candidate lookups that found every
table entry of the row inserted and read the exact row instead, over the
window's calls: 100 x approx_fallbacks / approx_lookups."""

import program_spans


def read(ctx):
    r = program_spans.ratio(ctx, "approx_fallbacks", "approx_lookups")
    return None if r is None else 100.0 * r
