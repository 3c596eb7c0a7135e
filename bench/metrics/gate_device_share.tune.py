"""Percentage of the window's value-gate comparisons that ran on the
device: the ``kernel.compare`` spans whose ``on`` attribute is
``"device"``. None where no ``kernel.compare`` span carries ``on`` (a
program that compares on the host only, and says nothing of where)."""


def read(ctx):
    on = [e.get("args", {}).get("on") for e in ctx.events
          if e["ph"] == "E" and e["name"] == "kernel.compare"]
    on = [where for where in on if where is not None]
    if not on:
        return None
    return 100.0 * on.count("device") / len(on)
