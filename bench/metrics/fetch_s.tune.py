"""Seconds per job in ``kernel.fetch``: the value gate's copies of the
candidates' outputs to the host."""
from harness.program import per_job_s


def read(ctx):
    return per_job_s(ctx, "kernel.fetch")
