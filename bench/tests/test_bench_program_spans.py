"""The program's own spans as the benchmark reads them: the per-job
readers of the telemetry events, the remainder that no layer span owns,
idle gaps labelled by program span on a real profiler trace, and the
tune cells' traced runs reporting every span metric."""
from __future__ import annotations

import json
import time
import types

import numpy as np
import pytest
from bench_spec import BENCH

from harness import cells, program, xplane

SPAN_METRICS = {"space_make_s.tune": "space.make",
                "build_s.tune": "kernel.build",
                "reference_s.tune": "kernel.reference",
                "fetch_s.tune": "kernel.fetch",
                "compare_s.tune": "kernel.compare"}
NEW = [*SPAN_METRICS, "untraced_s.tune"]


def _read(metric):
    return cells.load_module(BENCH / "metrics" / f"{metric}.py").read


def _events(spans, tid=1):
    """B/E events of ``spans``: (name, start s, end s), each list in the
    order a thread opens them."""
    marks = [(t0, 0, "B", name) for name, t0, _ in spans] + \
        [(t1, 1, "E", name) for name, _, t1 in spans]
    return [{"name": name, "ph": ph, "ts": t * 1e6, "tid": tid, "args": {}}
            for t, _, ph, name in sorted(marks, key=lambda m: (m[0], -m[1]))]


def _ctx(events, jobs=2, seconds=10.0):
    window = types.SimpleNamespace(records=[object()] * jobs,
                                   seconds=seconds)
    return types.SimpleNamespace(events=events, window=window)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_reads_its_span_per_job(metric):
    name = SPAN_METRICS[metric]
    events = _events([("driver.run", 0.0, 9.0), (name, 1.0, 1.5),
                      ("kernel.other", 2.0, 3.0), (name, 4.0, 4.25)])
    assert _read(metric)(_ctx(events)) == pytest.approx(0.375)
    # a program without the span (the parent of the change that added
    # it) leaves the metric out, where a 0 would read as a real time
    assert _read(metric)(_ctx(_events([("driver.run", 0.0, 9.0)]))) is None
    assert _read(metric)(_ctx(events, jobs=0)) is None


def test_untraced_s_is_the_window_less_the_outermost_spans():
    # two jobs in a 10 s window; outermost spans cover 1 + 4 + 0.5 s
    # and 1 + 1 s on a second thread; a nested span counts once
    events = _events([("space.make", 0.0, 1.0),
                      ("space.instance", 0.25, 0.75),
                      ("driver.run", 2.0, 6.0),
                      ("kernel.build", 3.0, 5.0),
                      ("rules.distill", 7.0, 7.5)])
    events += _events([("store.open", 8.0, 9.0)], tid=2)
    events += _events([("space.make", 9.0, 10.0)], tid=2)
    assert program.outermost_s(events) == pytest.approx(7.5)
    read = _read("untraced_s.tune")
    assert read(_ctx(events, jobs=2, seconds=10.0)) == pytest.approx(1.25)
    # without the space factory's span the remainder would hold it
    assert read(_ctx(_events([("driver.run", 2.0, 6.0)]))) is None


def _trace(tmp_path, registry):
    """A CPU profiler trace of ``bench.window`` > ``bench.search`` >
    ``kernel.compare`` (a program span, from ``registry``), with host
    time around the inner span."""
    import jax

    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.use(registry), \
                jax.profiler.TraceAnnotation("bench.window"), \
                jax.profiler.TraceAnnotation("bench.search"):
            time.sleep(0.004)
            with obs.span("kernel.compare"):
                time.sleep(0.004)
            time.sleep(0.004)
    finally:
        jax.profiler.stop_trace()
    return xplane.find(tmp_path)


def _idle_only_in(reduction, lo: int, hi: int) -> xplane.Reduction:
    """``reduction`` with one device busy over its whole window except
    ``lo``..``hi``."""
    w0, w1 = reduction.window
    dev = xplane.Device("/device:TPU:0", [(w0, lo, "fusion", "jit_f"),
                                          (hi, w1, "fusion.1", "jit_f")],
                        [(w0, w1, "jit_f")])
    return xplane.Reduction([dev], reduction.host, reduction.window)


def test_idle_gap_is_labelled_by_the_innermost_program_span(tmp_path):
    from repro import obs

    path = _trace(tmp_path / "on", obs.Telemetry())
    spans = program.host_spans(path)
    assert [name for _, _, name in spans] == ["kernel.compare"]
    lo, hi, _ = spans[0]
    bench = _idle_only_in(xplane.reduce(path), lo + 1000, hi - 1000)
    both = program.with_program_spans(bench, path)
    assert [name for name, _ in bench.idle_gaps()] == ["bench.search"]
    assert [name for name, _ in both.idle_gaps()] == ["kernel.compare"]
    assert both.idle_gaps()[0][1] == pytest.approx(bench.idle_gaps()[0][1])

    # a program that mirrors nothing: the bench.* labels, unchanged
    path = _trace(tmp_path / "off", obs.DISABLED)
    assert program.host_spans(path) == []
    r = xplane.reduce(path)
    mid = (r.window[0] + r.window[1]) // 2
    bench = _idle_only_in(r, mid - 1000, mid + 1000)
    assert program.with_program_spans(bench, path).idle_gaps() \
        == bench.idle_gaps() == [["bench.search", pytest.approx(2e-6)]]


@pytest.mark.parametrize("cell", ["spmv_paper.tune", "dsmoe16b_attn.tune"])
def test_traced_tune_cell_reports_every_program_span_metric(bench, cell,
                                                            tmp_path):
    kept = tmp_path / "window.xplane.pb"
    rc, out, err = bench(cell, "--trace", "1", "--keep-trace", str(kept))
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    got = {k: m["value"] for k, m in res["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(got[k] > 0 for k in NEW), got
    gate_parts = sum(got[k] for k in ("reference_s.tune", "fetch_s.tune",
                                      "compare_s.tune"))
    assert gate_parts <= got["gate_s.tune"] * (1 + 1e-9)
    # the program's spans reach the benchmark's own trace
    names = {name for _, _, name in program.host_spans(kept)}
    assert {"space.make", "space.instance", "space.put", "kernel.build",
            "kernel.reference", "kernel.fetch", "kernel.compare",
            "driver.run", "rules.distill"} <= names


@pytest.mark.parametrize("space", ["spmv_mulsum", "flash_attention"])
def test_benchmarks_draw_is_the_spaces_instance(space):
    """The tune cells' check draws each job's instance again with the
    benchmark's own generators: the same inputs give the same kernel
    output, byte for byte."""
    import jax.numpy as jnp

    from reference import attention, band

    import repro.search  # noqa: F401  (before repro.space: import order)
    from repro.space import make_space

    seed = [2**31 + 5, 3]
    if space == "spmv_mulsum":
        from repro.kernels.spmv.ops import ell_matvec

        sp = make_space(space, n=512, k=4, block_values=(128,), seed=seed,
                        interpret=True)
        vals, cols = band.band_matrix(512, 2048, seed)
        want = ell_matvec(jnp.asarray(vals), jnp.asarray(cols),
                          jnp.asarray(band.vector(512, seed)),
                          block_n=128, interpret=True)
        params = {"block_n": 128}
    else:
        from repro.kernels.flash_attention.ops import mha

        sp = make_space(space, batch=1, heads=1, seq=32, head_dim=16,
                        block_values=(16,), seed=seed, interpret=True)
        q, k, v = (jnp.asarray(a) for a in attention.instance(1, 1, 32, 16,
                                                              seed))
        want = mha(q, k, v, causal=True, block_q=16, block_k=16,
                   interpret=True)
        params = {"block_q": 16, "block_k": 16}
    got = sp.runner.build(params)()
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
