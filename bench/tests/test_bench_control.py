"""The control at a size a test run holds: the plain reference, computed
one precision below the configuration's, fails the cell's limits where
the program passes them.

On the chip the same readings come from ``bench/control.py`` at each
cell's own size; ``PERF.md`` gives them with the limits set from them.
"""
from __future__ import annotations

import json

import pytest
from bench_spec import CHIPS, bench_copy


@pytest.mark.parametrize("cell", sorted(CHIPS))
def test_control_fails_where_the_program_passes(child, tmp_path, cell):
    p = child("control.py", "--workload", cell, "--seeds", "11,12",
              "--control", "2", "--seconds", "0.3", "--rehearse",
              devices=CHIPS[cell], root=bench_copy(tmp_path / "checkout"))
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    summary = rows[-1]
    limits = rows[0]["limits"]
    assert all(summary["lower"][k] <= lim for k, lim in limits.items())
    assert summary["upper"]
    assert any(v > limits[k] for k, v in summary["upper"].items())
