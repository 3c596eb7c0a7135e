"""Seconds per job in ``kernel.reference``: the value gate's plain
reference, computed once per evaluator and copied to the host."""
from harness.program import per_job_s


def read(ctx):
    return per_job_s(ctx, "kernel.reference")
