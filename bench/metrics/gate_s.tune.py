"""Seconds per job in the engine's value gate: the ``gate_s``
attributes of ``kernel.compile`` and ``kernel.timing``."""
from harness.spans import attr_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not ctx.events:
        return None
    return attr_s(ctx.events, {"kernel.compile", "kernel.timing"},
                  "gate_s") / len(jobs)
