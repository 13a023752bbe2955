"""Mean latency of every request due in the window, from its due time to
its resolved ticket.  A request the loop stopped waiting for counts until
then; a degraded one counts until it resolved (and, like it, as failed)."""


def read(ctx):
    reqs = ctx.run.requests
    if not reqs:
        return None
    ends = [ctx.run.closed if r["done"] is None else r["done"] for r in reqs]
    return sum(end - r["due"] for end, r in zip(ends, reqs)) / len(reqs)
