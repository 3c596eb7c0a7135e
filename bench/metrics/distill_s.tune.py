"""Seconds per job in rule distillation: ``rules.distill``."""
from harness.spans import total_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not ctx.events:
        return None
    return total_s(ctx.events, {"rules.distill"}) / len(jobs)
