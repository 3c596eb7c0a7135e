"""Share of the window in which no operation ran on the device,
averaged over the chips."""


def read(ctx):
    return ctx.trace.idle_share()
