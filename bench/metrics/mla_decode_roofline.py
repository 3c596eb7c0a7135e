"""The MLA decode kernel's share of its roofline: the least time the
work counts allow (``harness/mla_work.py``: the live tokens' cache
vectors, the queries and the outputs at the peak table's bandwidth, or
their flops at its bf16 rate, whichever is longer) over the mean device
time of the benchmark-jitted call.

A trace with no TPU plane (a rehearsal on the CPU) gives nothing to
read, and neither does a unit without the call (a program that cannot
run the cell); a TPU trace without the benchmark's own module is an
error."""


def read(ctx):
    if not ctx.trace.devices:
        return None
    runs = ctx.trace.slowest_run_s(ctx.unit.module)
    if not runs:
        raise LookupError(f"no run of {ctx.unit.module} on the device in "
                          "the traced window")
    return 100.0 * ctx.unit.least_time_s() / (sum(runs) / len(runs))
