"""Seconds per job in ``kernel.build``: each candidate's build, its
trace and compile (or its load from the persistent cache) and its first
execution."""
from harness.program import per_job_s


def read(ctx):
    return per_job_s(ctx, "kernel.build")
