"""Every cell end to end at its rehearsal size on the CPU: the last
line's schema, the compile count inside the window, and the checks."""
from __future__ import annotations

import json
import re

import pytest
from bench_spec import ROOT, SPEC, TESTED, reported

CELLS = [w["name"] for w in TESTED["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _result(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(bench, cell):
    rc, out, err = bench(cell)
    assert rc == 0, err[-3000:]
    res = _result(out)
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == reported(cell, "end_to_end")
    units = {m["name"]: m["unit"] for m in TESTED["end_to_end"]}
    for name, m in res["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    # the compile count inside the window, printed before the result
    assert re.search(r"^compiles in window: 0$", out, re.M), out[-2000:]
    # every compared number beside its limit, last on standard error
    tail = err.strip().splitlines()[-len(res["checks"]):]
    for line, (name, c) in zip(tail, res["checks"].items()):
        assert line.startswith(f"check {name}: ") and "limit" in line
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", ["spmv_paper.tune", "dsmoe16b_attn.tune"])
def test_traced_tune_cell_reports_span_metrics(bench, cell):
    rc, out, err = bench(cell, "--trace", "1")
    assert rc == 0, err[-3000:]
    res = _result(out)
    assert res["correct"] is True
    # a CPU trace holds no TPU plane: the device metrics are left out,
    # never reported as 0; the program's spans are read
    got = set(res["metrics"])
    assert {"space_s.tune", "driver_s.tune", "gate_s.tune", "timing_s.tune",
            "store_s.tune", "distill_s.tune"} <= got
    assert not got & {"idle_share.tune", "flash_attention_roofline.tune"}
    assert got <= reported(cell, "per_layer")
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in res["device"] and "busy_s" in res["device"]


def test_benchmark_json_names_what_exists():
    spec = SPEC
    bench = ROOT / "bench"
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        assert reported(w["name"], "end_to_end", spec) >= {"setup_s"}
        assert len(reported(w["name"], "end_to_end", spec)) >= 2
        assert reported(w["name"], "per_layer", spec)
    for m in spec["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert m["moves"] in reported(w, "end_to_end", spec)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
