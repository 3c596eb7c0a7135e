"""Seconds per job in ``kernel.compare``: the value gate's tolerance
checks of the candidates' outputs on the host."""
from harness.program import per_job_s


def read(ctx):
    return per_job_s(ctx, "kernel.compare")
