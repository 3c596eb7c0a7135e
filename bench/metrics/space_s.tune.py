"""Seconds per job in the space factory (``make_space``: the instance
and the kernel closures), from the benchmark's own span around it."""


def read(ctx):
    jobs = ctx.window.records
    return sum(j.space_s for j in jobs) / len(jobs) if jobs else None
