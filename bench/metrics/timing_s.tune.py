"""Seconds per job in the engine's timed calls: ``kernel.timing`` less
the gate run inside it."""
from harness.spans import attr_s, total_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not ctx.events:
        return None
    names = {"kernel.timing"}
    return (total_s(ctx.events, names)
            - attr_s(ctx.events, names, "gate_s")) / len(jobs)
