"""Bellman-Ford rounds the hub APSP ran to its fixed point, per problem,
over the window's calls (the program's ``apsp_rounds`` counter; the exact
program's squarings where a problem is below the hub size)."""

import program_spans


def read(ctx):
    return program_spans.ratio(ctx, "apsp_rounds", "problems")
