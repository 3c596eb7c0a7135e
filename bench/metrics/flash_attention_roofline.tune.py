"""The flash kernel's share of its roofline over every candidate call
in the window: the summed least time of the calls (causal matmul flops
at the peak table's bf16 rate) over their summed device time.

A trace with no TPU plane (a rehearsal on the CPU) gives nothing to
read. A TPU trace in which the program's jitted entry (``jit_mha``)
never ran is an error: the kernel was renamed or left the path, and
the metric must not drop silently out of the result."""


def read(ctx):
    if not ctx.trace.devices:
        return None
    runs = [d for dev in ctx.trace.runs(ctx.unit.module) for d in dev]
    if not runs:
        raise LookupError(f"no run of {ctx.unit.module} on the device in "
                          "the traced window")
    device_s = sum(e - s for s, e in runs) / 1e9
    return 100.0 * len(runs) * ctx.unit.least_time_s() / device_s
