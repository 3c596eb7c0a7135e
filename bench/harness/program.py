"""The program's own spans: in the telemetry events of a traced window,
and on the host plane of its profiler trace.

The program (``repro.obs``) mirrors each span of an enabled registry as
a ``jax.profiler.TraceAnnotation`` of the same name, so a trace holds
the program's spans beside the benchmark's ``bench.*`` annotations, on
the clock of the device events. A program that does not mirror them
leaves the trace with ``bench.*`` annotations only, and everything here
then reads as it would from those alone.
"""
from __future__ import annotations

import dataclasses

from harness import xplane
from harness.spans import total_s

# The program's layers, by the prefix of their span names.
PROGRAM = ("space.", "driver.", "engine.", "kernel.", "store.", "rules.")


def host_spans(path) -> list[tuple[int, int, str]]:
    """(start, end, name) of the program's spans on the host plane of
    the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [(int(e.start_ns), int(e.end_ns), e.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM)]


def with_program_spans(reduction: xplane.Reduction,
                       path) -> xplane.Reduction:
    """``reduction`` with the program's spans beside the benchmark's
    annotations, so that each idle gap is labelled by the innermost
    span of either kind around it."""
    return dataclasses.replace(reduction,
                               host=reduction.host + host_spans(path))


def per_job_s(ctx, name: str) -> float | None:
    """Seconds per job in the spans called ``name``; None where the
    window holds none (a program without that span)."""
    jobs = ctx.window.records
    if not jobs or not any(e["name"] == name for e in ctx.events):
        return None
    return total_s(ctx.events, {name}) / len(jobs)


def outermost_s(events) -> float:
    """Seconds in spans with no parent span on their thread."""
    depth: dict = {}
    begin: dict = {}
    total = 0.0
    for e in events:
        tid = e["tid"]
        if e["ph"] == "B":
            if not depth.get(tid):
                begin[tid] = e["ts"]
            depth[tid] = depth.get(tid, 0) + 1
        elif e["ph"] == "E" and depth.get(tid):
            depth[tid] -= 1
            if not depth[tid]:
                total += (e["ts"] - begin[tid]) / 1e6
    return total
