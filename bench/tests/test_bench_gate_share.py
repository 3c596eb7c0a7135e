"""``gate_device_share.tune``: the share of the value gate's comparisons
that ran on the device, read from the ``on`` attribute of the program's
``kernel.compare`` spans, and reported by the rehearsed tune cells."""
from __future__ import annotations

import json
import re
import types

import pytest
from bench_spec import BENCH

from harness import cells


def _read(ctx):
    return cells.load_module(
        BENCH / "metrics" / "gate_device_share.tune.py").read(ctx)


def _compares(*on):
    """B/E pairs of ``kernel.compare`` spans, one per entry of ``on``
    (None: a span without the attribute), inside one ``kernel.timing``."""
    events = [{"name": "kernel.timing", "ph": "B", "ts": 0.0, "tid": 1,
               "args": {}}]
    for i, where in enumerate(on):
        args = {"bytes": 32} if where is None else {"bytes": 32, "on": where}
        events += [{"name": "kernel.compare", "ph": ph, "ts": 10.0 * i + t,
                    "tid": 1, "args": args} for ph, t in (("B", 1), ("E", 2))]
    return events + [{"name": "kernel.timing", "ph": "E", "ts": 1e3,
                      "tid": 1, "args": {}}]


@pytest.mark.parametrize("on,share", [
    (("device",) * 3, 100.0),
    (("host",) * 3, 0.0),
    (("device", "host", "device", "device"), 75.0),
    ((None, None), None),       # the parent: no span says where
    ((), None),
])
def test_gate_device_share_reads_the_compare_spans(on, share):
    ctx = types.SimpleNamespace(events=_compares(*on))
    assert _read(ctx) == share


@pytest.mark.parametrize("cell", ["spmv_paper.tune", "dsmoe16b_attn.tune"])
def test_traced_tune_cell_compares_on_the_device(bench, cell):
    rc, out, err = bench(cell, "--trace", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["gate_device_share.tune"] == {"value": 100.0,
                                                        "unit": "%"}
    assert re.search(r"^compiles in window: 0$", out, re.M), out[-2000:]
