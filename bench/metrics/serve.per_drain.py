"""Requests resolved per ``drain()`` call: how much the micro-batcher
batches."""


def read(ctx):
    d = ctx.run.drains
    return sum(d) / len(d) if d else None
