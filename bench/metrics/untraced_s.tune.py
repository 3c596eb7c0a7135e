"""Seconds per job that no layer span owns: the window per job less the
program's outermost spans per job (those with no parent on their
thread).

A program whose space factory has no span leaves it out: the remainder
would then hold the factory and read as something else."""
from harness.program import outermost_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not any(e["name"] == "space.make" for e in ctx.events):
        return None
    return (ctx.window.seconds - outermost_s(ctx.events)) / len(jobs)
