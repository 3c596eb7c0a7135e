"""The yardstick's parts: work counts, the peak table, discovery by
file name, the trace reduction on a trace recorded on the chip, and the
refusals (no TPU, no program)."""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest
from bench_spec import SPEC, bench_copy

from harness import cells, work, xplane
from harness.peaks import UnknownDevice, least_time_s, peaks

DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def test_spmv_bytes_at_the_papers_size():
    # 1.5M non-zeros x (4 B value + 4 B column) + x and y of 150,000 f32
    assert work.spmv_bytes(1_500_000, 150_000, 150_000) == 13_200_000
    assert least_time_s("TPU v5 lite", bytes_moved=13_200_000) \
        == pytest.approx(16.12e-6, rel=1e-3)
    # one of 4 ranks: 375k non-zeros, its block, its halo and its rows
    assert work.spmv_rank_bytes(375_000, 37_500) == 3_600_000


def test_causal_attention_flops_at_deepseek_moe_16b():
    flops = work.causal_attention_flops(1, 16, 128, 4096)
    assert flops == 68_736_253_952          # 4 * H * D * S(S+1)/2
    assert least_time_s("TPU v5 lite", flops=flops) \
        == pytest.approx(348.9e-6, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("cpu")


def test_band_generator_draws_the_programs_instance():
    from reference import band

    from repro.spmv.matrix import band_matrix

    a = band_matrix(4096, 40_960, seed=[7, 3])
    vals, cols = band.band_matrix(4096, 40_960, [7, 3])
    np.testing.assert_array_equal(vals, a.vals)
    np.testing.assert_array_equal(cols, a.cols)


def test_new_config_traffic_and_metric_found_by_name(tmp_path, child):
    root = bench_copy(tmp_path, SPEC)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "spmv_paper.json").read_text())
    cfg["n_rows"] = 20_000
    (b / "configs" / "spmv_small.json").write_text(json.dumps(cfg))
    (b / "traffic" / "call_two.json").write_text(json.dumps(
        {"loop": "calls", "inputs": 2, "sample": 4}))
    (b / "metrics" / "calls_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.window.attempted)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "spmv_small", "source": "test",
                            "file": "bench/configs/spmv_small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "spmv_small.call_two",
                              "config": "spmv_small", "traffic": "call_two",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "calls_seen", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "call_us",
                              "workloads": ["spmv_small.call_two"]})
    for m in spec["end_to_end"]:
        if m["name"] in ("call_us", "call_p95_us"):
            m["workloads"].append("spmv_small.call_two")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load_cell("spmv_small.call_two", root=root)
    assert cell.config["n_rows"] == 20_000 and cell.traffic["inputs"] == 2
    assert [m["name"] for m in cell.per_layer] == ["calls_seen"]
    # and a run of the copy picks all three up with no edit of its code
    kept = tmp_path / "window.xplane.pb"
    p = child(str(b / "run.py"), "--workload",
              "spmv_small.call_two", "--seed", "5", "--seconds", "0.3",
              "--trace", "1", "--rehearse", "--keep-trace", str(kept))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["metrics"]["calls_seen"]["value"] > 0
    # the window's trace, kept for a recorded test such as the one below
    assert xplane.reduce(kept).window_s > 0
    assert not (b / "_run").exists()        # per-run scratch removed


def test_trace_reduction_on_a_chip_trace():
    """A traced 0.3 s window of ``spmv_paper.call`` on one TPU v5 lite,
    with the numbers that run printed from its reduction: 27 calls
    (the run's ``attempted``), busy 0.2903 s of a 0.3114 s window."""
    want = json.loads((DATA / "spmv_call_short.json").read_text())
    r = xplane.reduce(DATA / "spmv_call_short.xplane.pb")
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    runs = r.slowest_run_s(want["module"])
    assert len(runs) == want["calls"]
    assert sum(runs) / len(runs) == pytest.approx(want["mean_run_s"],
                                                  rel=1e-9)
    assert r.exposed_collective_share(want["module"]) == 0.0
    assert r.idle_share() == pytest.approx(
        100 * (1 - want["busy_s"] / want["window_s"]), rel=1e-9)
    for got, exp in ((r.top_ops(3), want["top_ops"]),
                     (r.idle_gaps(3), want["idle_gaps"])):
        assert [name for name, _ in got] == [name for name, _ in exp]
        assert [s for _, s in got] == pytest.approx([s for _, s in exp],
                                                   rel=1e-9)


def test_exposed_collective_counts_only_bare_collectives():
    ms = 1_000_000
    dev = xplane.Device("/device:TPU:0", [
        (0, 4 * ms, "fusion", "jit_f"),
        (4 * ms, 6 * ms, "collective-permute-done", "jit_f"),
        (6 * ms, 10 * ms, "fusion.1", "jit_f")], [(0, 10 * ms, "jit_f")])
    r = xplane.Reduction([dev], [], (0, 10 * ms))
    assert r.exposed_collective_share("jit_f") == pytest.approx(0.2)
    assert r.busy_s() == pytest.approx(0.010)


def test_no_tpu_exits_nonzero_without_a_result(child):
    p = child("run.py", "--workload", "spmv_paper.call", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_bench_alone_exits_nonzero_without_a_result(tmp_path, child):
    root = bench_copy(tmp_path, SPEC)
    (root / "src").unlink()
    b = root / "bench"
    p = child(str(b / "run.py"), "--workload", "spmv_paper.call",
              "--seed", "1", "--seconds", "1", "--trace", "0",
              "--rehearse")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_call_p95_is_the_tail_of_every_call():
    """One call in ten stalls for 20 ms: the 95th percentile is a stalled
    call, though every quarter second's mean stays near 3 ms."""
    import time

    import jax.numpy as jnp

    from harness.loops import calls

    y = jnp.zeros(2)

    class Unit:
        def call(self, i):
            time.sleep(0.020 if i % 10 == 9 else 0.001)
            return y

    w = calls(Unit(), 1.0, {"sample": 2}, 7, False)
    assert w.attempted > 100
    assert w.metrics["call_p95_us"] > 15_000
    assert 2_500 < w.metrics["call_us"] < 6_000
    assert len(w.samples) == 2


def test_roofline_without_its_module_on_a_tpu_is_an_error():
    import types

    ms = 1_000_000
    dev = xplane.Device("/device:TPU:0", [(0, ms, "fusion", "jit_other")],
                        [(0, ms, "jit_other")])
    unit = types.SimpleNamespace(module="jit_mha", least_time_s=lambda: 1e-4)
    for metric in ("flash_attention_roofline.tune", "spmv_roofline"):
        read = cells.load_module(DATA.parent / "metrics" / f"{metric}.py").read
        on_tpu = types.SimpleNamespace(
            trace=xplane.Reduction([dev], [], (0, ms)), unit=unit)
        with pytest.raises(LookupError):
            read(on_tpu)
        on_cpu = types.SimpleNamespace(
            trace=xplane.Reduction([], [], (0, ms)), unit=unit)
        assert read(on_cpu) is None
