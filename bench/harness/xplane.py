"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

A TPU's plane (``/device:TPU:<i>``) holds an ``XLA Modules`` line, one
event per executed program (``jit_<name>(<fingerprint>)``), and an
``XLA Ops`` line, one event per HLO operation in the order the core
runs them (``%<op> = <shape> <opcode>(...)``). The host plane
(``/host:CPU``) holds the benchmark's ``jax.profiler.TraceAnnotation``
spans, ``bench.window`` around the measured window and ``bench.*``
around each layer call. Host and device clocks in one trace agree to
about a millisecond, so device activity is never clipped to the host's
window: the profiler runs around the window alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|send|recv|collective-broadcast)")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_spmv_call(1234)`` -> ``jit_spmv_call``."""
    return event_name.split("(", 1)[0]


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Device:
    name: str
    ops: list[tuple[int, int, str, str]]      # start, end, op, module
    modules: list[tuple[int, int, str]]       # start, end, module

    def busy(self) -> list[tuple[int, int]]:
        return union((s, e) for s, e, _, _ in self.ops)

    def runs(self, module: str) -> list[tuple[int, int]]:
        return [(s, e) for s, e, m in self.modules if m == module]


@dataclasses.dataclass
class Reduction:
    devices: list[Device]
    host: list[tuple[int, int, str]]          # bench.* annotations
    window: tuple[int, int]                   # host ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(length(d.busy()) for d in self.devices) / 1e9 \
            / len(self.devices)

    def idle_share(self) -> float | None:
        """Percent of the window in which no operation ran on the
        device, averaged over devices; None where the trace holds no
        device."""
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def runs(self, module: str) -> list[list[tuple[int, int]]]:
        """Each device's executions of ``module``, in order."""
        return [d.runs(module) for d in self.devices]

    def slowest_run_s(self, module: str) -> list[float]:
        """Per execution of ``module``, its longest device time across
        the devices (the k-th run on each device is one call)."""
        per_dev = [r for r in self.runs(module) if r]
        if not per_dev:
            return []
        n = min(len(r) for r in per_dev)
        return [max(r[k][1] - r[k][0] for r in per_dev) / 1e9
                for k in range(n)]

    def exposed_collective_share(self, module: str) -> float | None:
        """Share of ``module``'s device time in which the core ran a
        collective operation and nothing else, averaged over devices."""
        shares = []
        for d in self.devices:
            runs = d.runs(module)
            total = length(runs)
            if not total:
                continue
            coll = union((s, e) for s, e, op, m in d.ops
                         if m == module and COLLECTIVE.match(op))
            other = union((s, e) for s, e, op, m in d.ops
                          if m == module and not COLLECTIVE.match(op))
            exposed = length(coll) - _overlap(coll, other)
            shares.append(exposed / total)
        return sum(shares) / len(shares) if shares else None

    def top_ops(self, n: int = 10) -> list[list]:
        """The device operations that took most time (seconds summed
        over the window, averaged over devices), by module and op."""
        tot: dict[str, float] = {}
        for d in self.devices:
            for s, e, op, m in d.ops:
                key = f"{m}/{_SUFFIX.sub('', op)}"
                tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
        nd = max(1, len(self.devices))
        return [[k, v / nd] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device time in the window, summed by the innermost
        ``bench.*`` host annotation around each gap's midpoint."""
        lo, hi = self.window
        gaps = []
        for d in self.devices:
            busy = clip(d.busy(), lo, hi)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        gaps.sort(key=lambda g: g[0] + g[1])
        tot: dict[str, float] = {}
        for (s, e), label in zip(gaps, self.labels([(s + e) // 2
                                                    for s, e in gaps])):
            tot[label] = tot.get(label, 0.0) + (e - s) / 1e9
        nd = max(1, len(self.devices))
        return [[k, v / nd] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def labels(self, times) -> list[str]:
        """For each of the ascending host ``times``, the innermost
        ``bench.*`` annotation around it other than ``bench.window``
        (``bench.window`` where there is none): one sweep, since a
        window holds thousands of gaps and annotations."""
        spans = sorted((s, e, name) for s, e, name in self.host
                       if name != "bench.window")
        out, active, i = [], [], 0
        for t in times:
            while i < len(spans) and spans[i][0] <= t:
                active.append(spans[i])
                i += 1
            active = [sp for sp in active if sp[1] > t]
            out.append(min((e - s, name) for s, e, name in active)[1]
                       if active else "bench.window")
        return out


def _overlap(a, b) -> int:
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def find(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce(path) -> Reduction:
    """Read one ``.xplane.pb`` into a :class:`Reduction`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(int(e.start_ns), int(e.end_ns),
                                module_name(e.name)) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(int(e.start_ns), int(e.end_ns), op_name(e.name))
                           for e in line.events]
            starts = [m[0] for m in modules]
            devices.append(Device(
                plane.name, [(s, e, op, _owner(modules, starts, s))
                             for s, e, op in ops], modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(int(e.start_ns), int(e.end_ns), e.name)
                         for e in line.events if e.name.startswith("bench.")]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    win = [(s, e) for s, e, name in host if name == "bench.window"]
    return Reduction(devices, host, win[0] if win else (0, 0))


def _owner(modules, starts, t: int) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < max(modules[i][1], modules[i][0] + 1):
        return modules[i][2]
    return ""
