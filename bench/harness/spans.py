"""Readings from the program's own spans (``repro.obs`` events: ``B``
and ``E`` pairs per thread, timestamps in microseconds)."""
from __future__ import annotations


def _closed(events):
    """(name, duration_s, self_s, end attrs) of every finished span."""
    stacks: dict = {}
    out = []
    for e in events:
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append([e, 0.0])
        elif e["ph"] == "E":
            stack = stacks.get(e["tid"])
            if not stack:
                continue
            begin, child_s = stack.pop()
            dur = (e["ts"] - begin["ts"]) / 1e6
            if stack:
                stack[-1][1] += dur
            out.append((e["name"], dur, dur - child_s, e.get("args", {})))
    return out


def total_s(events, names) -> float:
    return sum(d for n, d, _, _ in _closed(events) if n in names)


def self_s(events, names) -> float:
    """Time inside spans named ``names`` less their child spans."""
    return sum(s for n, _, s, _ in _closed(events) if n in names)


def attr_s(events, names, attr: str) -> float:
    """The sum of one numeric attribute over spans named ``names``."""
    return sum(float(a.get(attr, 0.0)) for n, _, _, a in _closed(events)
               if n in names)
