"""Seconds per job in the space factory, from the program's own
``space.make`` span: the host draw of the instance (``space.instance``),
its copy to the device (``space.put``) and the kernel closures."""
from harness.program import per_job_s


def read(ctx):
    return per_job_s(ctx, "space.make")
