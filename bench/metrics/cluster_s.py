"""Seconds per clustering problem, series on the host to labels on the
host: the summed wall time of every call of the window, over the problems
those calls solved (a call started inside the window is finished and
counted)."""


def read(ctx):
    calls = ctx.run.calls
    problems = sum(p for _, _, p in calls)
    if not problems:
        return None
    return sum(end - start for start, end, _ in calls) / problems
