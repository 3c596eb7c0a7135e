"""Seconds per job in the evaluation store: ``store.open`` and
``store.append``."""
from harness.spans import total_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not ctx.events:
        return None
    return total_s(ctx.events, {"store.open", "store.append"}) / len(jobs)
