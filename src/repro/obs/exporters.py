"""Telemetry exporters: where the event stream lands.

Two formats, one contract — ``export(event: dict)`` per event plus a
``close()`` flush. Events are the Chrome trace-event shape the
registry emits (:mod:`repro.obs.telemetry`): ``ph`` is ``"B"``/``"E"``
(span begin/end), ``"C"`` (counter/gauge sample), or ``"i"`` (instant);
``ts`` is microseconds on the process-monotonic clock.

:class:`PerfettoExporter`
    Chrome trace-event JSON (``{"traceEvents": [...]}``) loadable
    straight into https://ui.perfetto.dev — spans nest per thread
    track, counters render as value tracks. Buffered in memory, written
    atomically at :meth:`close`.
:class:`MemoryExporter`
    In-process event list, for tests and programmatic consumers.
"""
from __future__ import annotations

import json
import os
from typing import Protocol, runtime_checkable


@runtime_checkable
class Exporter(Protocol):
    """Consumer of telemetry events."""

    def export(self, event: dict) -> None: ...

    def close(self) -> None: ...


class MemoryExporter:
    """Keep every event in a list (tests, programmatic readers)."""

    def __init__(self):
        self.events: list[dict] = []

    def export(self, event: dict) -> None:
        self.events.append(event)

    def close(self) -> None:
        pass


class PerfettoExporter:
    """Chrome trace-event / Perfetto JSON.

    Events buffer in memory and are written as one
    ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` document at
    :meth:`close` (atomic rename, so a crashed run leaves no
    half-written trace).
    """

    def __init__(self, path: "str | os.PathLike"):
        self.path = os.fspath(path)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._events: "list[dict] | None" = []

    def export(self, event: dict) -> None:
        if self._events is not None:
            self._events.append(event)

    def close(self) -> None:
        if self._events is None:
            return
        doc = {"traceEvents": self._events, "displayTimeUnit": "ms"}
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.path)
        self._events = None


def load_trace(path: "str | os.PathLike") -> list[dict]:
    """Read a trace back as an event list.

    Accepts the Perfetto document shape (``{"traceEvents": [...]}``) or
    a bare JSON array. The schema-sanity test and the CI gate read
    traces through this, so the check and the writer can never drift
    apart silently.
    """
    with open(os.fspath(path)) as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc
