"""The traffic generator: the loops a traffic file names, each driven by
that file's parameters.

``calls``: one caller, calls back to back, each ended by
``block_until_ready`` as an iterative solver that reads its residual
every step would run them. ``jobs``: one client, closed loop, one
tuning job after another.

Both run until ``seconds`` have passed and then finish the unit in
flight, so the window holds whole units only and every metric is taken
over all the work and all the time of the window. A seeded reservoir
keeps a sample of the window's outputs for the check.
"""
from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time

import numpy as np


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    attempted: int = 0
    failed: int = 0
    metrics: dict = dataclasses.field(default_factory=dict)
    samples: list = dataclasses.field(default_factory=list)
    records: list = dataclasses.field(default_factory=list)


class Reservoir:
    """A uniform sample of ``size`` items from a stream, drawn from a
    seed: the same seed keeps the same positions."""

    def __init__(self, size: int, seed):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.seen = 0

    def slot(self) -> int | None:
        """The slot the next item goes to, or None if it is not kept."""
        i, self.seen = self.seen, self.seen + 1
        if i < self.size:
            return i
        j = int(self.rng.integers(0, i + 1))
        return j if j < self.size else None

    def put(self, slot: int | None, item):
        """Keep ``item`` in ``slot``; returns the item it evicts."""
        if slot is None:
            return None
        if slot >= len(self.items):
            self.items.append(item)
            return None
        old, self.items[slot] = self.items[slot], item
        return old


def annotate(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def calls(unit, seconds: float, traffic: dict, seed, traced: bool) -> Window:
    """Back-to-back calls. ``call_us`` is the window over the calls;
    ``call_p95_us`` the 95th percentile of every call's latency, from
    the call to its ``block_until_ready``."""
    import jax

    keep = Reservoir(int(traffic["sample"]), [seed, 101])
    starts: list[float] = []
    lat: list[float] = []
    t0 = now = time.perf_counter()
    while now - t0 < seconds:
        with annotate(traced, "bench.call"):
            start = time.perf_counter()
            y = jax.block_until_ready(unit.call(len(lat)))
            now = time.perf_counter()
        starts.append(start - t0)
        lat.append(now - start)
        keep.put(keep.slot(), (len(lat) - 1, y))
    window, n = now - t0, len(lat)
    p95 = statistics.quantiles(lat, n=20)[-1] if n > 1 else lat[0]
    slow = sorted(range(n), key=lat.__getitem__)[-3:][::-1]
    print(f"calls: {sum(lat):.3f} s of {window:.3f} s in calls; slowest "
          + ", ".join(f"#{k} at {starts[k]:.3f} s took {lat[k] * 1e3:.3f} ms"
                      for k in slow), flush=True)
    return Window(window, n, 0,
                  {"call_us": window / n * 1e6, "call_p95_us": p95 * 1e6},
                  keep.items)


def jobs(unit, seconds: float, traffic: dict, seed, traced: bool) -> Window:
    """Closed-loop tuning jobs, one client. ``tune_s`` is the window over
    the jobs completed; a job that raises counts as failed."""
    keep = Reservoir(int(traffic["sample"]), [seed, 102])
    records, failed = [], 0
    t0 = time.perf_counter()
    j = 0
    while True:
        j += 1
        slot = keep.slot()
        with annotate(traced, "bench.job"):
            try:
                rec = unit.job(j, keep=slot is not None, traced=traced)
            except Exception as e:  # noqa: BLE001 — counted, reported
                print(f"job {j} failed: {type(e).__name__}: {e}",
                      flush=True)
                failed += 1
                rec = None
        if rec is not None:
            records.append(rec)
            evicted = keep.put(slot, rec)
            if evicted is not None:
                evicted.outputs = None
        if time.perf_counter() - t0 >= seconds:
            break
    window = time.perf_counter() - t0
    done = j - failed
    metrics = {"tune_s": window / done} if done else {}
    return Window(window, j, failed, metrics, keep.items, records)


# loop name -> (loop, the problem's class that the loop drives)
LOOPS = {"calls": (calls, "Call"), "jobs": (jobs, "Tune")}
