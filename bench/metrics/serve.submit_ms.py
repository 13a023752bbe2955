"""Mean host milliseconds inside ``ClusterService.submit`` per request:
the buffered ticks applied, the (n, n) window pulled to the host, its
content key, admission."""


def read(ctx):
    d = ctx.run.spans.durations.get("bench.submit")
    return 1000.0 * sum(d) / len(d) if d else None
