"""repro.obs: the telemetry subsystem and its pure-observer contract.

Locks the PR's acceptance criteria:

* ``run_search`` with an exporter-attached registry is byte-identical
  to a telemetry-off run — times, schedules, and cache counters — on
  every analytic backend (sim / vectorized / pool);
* the Perfetto/Chrome trace a run writes is schema-sane: valid JSON,
  monotone ``ts``, every ``"B"`` matched by an ``"E"`` (LIFO per tid);
* a warm store-backed run's telemetry shows **zero** ``engine.measure``
  spans, and ``EvalStore.stats()`` lookup meters agree one-for-one
  with the evaluator's ``store_hits``;
* ``TraceSink`` rounds carry their index, ``key_stream()`` keeps its
  flat back-compat shape, and the ``"telemetry"`` sink is registered;
* an enabled registry's spans are mirrored, in LIFO order, as
  ``jax.profiler`` annotations that land on a real trace's host plane;
  the disabled one mirrors nothing, and ``repro.obs`` imports no JAX;
* ``benchmarks/run.py``'s baseline comparator flags exactly the
  regressed rows.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

import repro.core as C
import repro.engine as E
import repro.search as S
from repro import obs
from repro.driver import SINKS, TelemetrySink, TraceSink, make_sink
from repro.engine.base import EvalBatch
from repro.engine.store import MAGIC, EvalStore


# -- the core -----------------------------------------------------------------

def test_spans_counters_gauges_and_summary():
    tel = obs.Telemetry()
    with obs.use(tel):
        assert obs.enabled()
        with obs.span("outer", layer="driver") as sp:
            sp.set(n=3)
            with obs.span("inner"):
                pass
            with obs.span("inner"):
                pass
        obs.counter("hits").add(2)
        obs.counter("hits").add(3)
        obs.gauge("best").set(1.5)
        obs.event("marker", round=0)
    spans = tel.spans_by_name()
    assert spans["outer"]["count"] == 1
    assert spans["inner"]["count"] == 2
    assert spans["outer"]["total_s"] >= spans["inner"]["total_s"] >= 0
    assert tel.counters() == {"hits": 5.0}
    assert tel.gauges() == {"best": 1.5}
    text = tel.summary()
    for needle in ("outer", "inner", "hits", "best"):
        assert needle in text


def test_span_attrs_land_on_end_event():
    ex = obs.MemoryExporter()
    tel = obs.Telemetry(exporters=[ex])
    with obs.use(tel):
        with obs.span("work", n=4) as sp:
            sp.set(misses=1)             # discovered mid-span
    begin = next(e for e in ex.events if e["ph"] == "B")
    end = next(e for e in ex.events if e["ph"] == "E")
    assert begin["name"] == end["name"] == "work"
    assert end["args"] == {"n": 4, "misses": 1}
    assert end["ts"] >= begin["ts"]


def test_disabled_default_is_noop_singletons():
    assert obs.current() is obs.DISABLED
    assert not obs.enabled()
    sp = obs.span("anything", n=1)
    with sp as inner:
        inner.set(x=2)                   # all no-ops, nothing raised
    assert obs.span("other") is sp       # one shared singleton
    assert obs.counter("c") is obs.counter("d")
    obs.counter("c").add(5)
    obs.gauge("g").set(3.0)
    obs.event("e", k=1)
    assert obs.DISABLED.spans_by_name() == {}
    assert obs.DISABLED.counters() == {}


def test_use_restores_previous_registry():
    tel = obs.Telemetry()
    with obs.use(tel):
        assert obs.current() is tel
        with obs.use(None):              # explicit re-disable nests
            assert obs.current() is obs.DISABLED
        assert obs.current() is tel
    assert obs.current() is obs.DISABLED


def test_exception_inside_span_still_closes_it():
    tel = obs.Telemetry()
    with obs.use(tel):
        with pytest.raises(RuntimeError):
            with obs.span("fails"):
                raise RuntimeError("boom")
    assert tel.spans_by_name()["fails"]["count"] == 1


# -- the profiler's clock -----------------------------------------------------

@pytest.fixture
def annotations(monkeypatch):
    """Swap ``jax.profiler.TraceAnnotation`` for a stand-in; returns the
    log of its enters and exits."""
    import jax

    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return log


def test_enabled_registry_mirrors_nested_spans_lifo(annotations):
    with obs.use(obs.Telemetry()):
        with obs.span("driver.run"):
            with obs.span("kernel.fetch"):
                pass
            with pytest.raises(RuntimeError):
                with obs.span("kernel.compare"):
                    raise RuntimeError("gate failed")
    assert annotations == [
        ("enter", "driver.run"),
        ("enter", "kernel.fetch"), ("exit", "kernel.fetch"),
        ("enter", "kernel.compare"), ("exit", "kernel.compare"),
        ("exit", "driver.run")]


def test_disabled_registry_makes_no_annotations(annotations):
    with obs.span("driver.run"):
        with obs.span("kernel.fetch"):
            pass
    assert annotations == []


def test_import_obs_imports_no_jax():
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    code = ("import sys\n"
            "from repro import obs\n"
            "with obs.use(obs.Telemetry()):\n"
            "    with obs.span('driver.run'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n")
    env = dict(os.environ, PYTHONPATH=str(src))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_profiler_trace_holds_program_spans(tmp_path):
    """A real ``jax.profiler`` trace on the CPU: the span is an event of
    the host plane, as long as the span."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.use(obs.Telemetry()):
            with obs.span("kernel.compare", bytes=8):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    found = [(e.start_ns, e.end_ns) for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "kernel.compare"]
    assert len(found) == 1
    assert found[0][1] - found[0][0] >= 5e6


# -- pure observer: byte-identity with exporters attached ---------------------

@pytest.mark.parametrize("backend,kwargs", [
    ("sim", {}),
    ("vectorized", {}),
    ("pool", {"n_workers": 2, "min_shard": 1}),
])
def test_run_search_byte_identical_with_telemetry(backend, kwargs):
    g = C.spmv_dag()

    def search():
        return S.run_search(g, S.MCTSSearch(g, 2, seed=0), budget=40,
                            batch_size=8, backend=backend,
                            backend_kwargs=kwargs)

    plain = search()
    tel = obs.Telemetry(exporters=[obs.MemoryExporter()])
    with obs.use(tel):
        traced = search()

    assert traced.times == plain.times
    assert [s.items for s in traced.schedules] \
        == [s.items for s in plain.schedules]
    assert traced.n_proposed == plain.n_proposed
    assert traced.cache_hits == plain.cache_hits
    assert traced.cache_misses == plain.cache_misses
    # The registry saw the run; the plain result carries no digest.
    assert plain.telemetry is None
    assert traced.telemetry is not None and len(traced.telemetry) > 0
    spans = tel.spans_by_name()
    assert spans["driver.run"]["count"] == 1
    assert spans["driver.round"]["count"] == len(traced.telemetry)
    assert spans["engine.batch"]["count"] >= 1
    # Round digests account for every proposal and every miss.
    assert sum(r["n"] for r in traced.telemetry) == traced.n_proposed
    assert sum(r["misses"] for r in traced.telemetry) \
        == traced.cache_misses
    assert traced.telemetry[-1]["best"] == traced.best()[1]


# -- Perfetto trace schema ----------------------------------------------------

def test_perfetto_trace_schema(tmp_path):
    path = tmp_path / "trace.json"
    g = C.spmv_dag()
    tel = obs.Telemetry(exporters=[obs.PerfettoExporter(path)])
    with obs.use(tel):
        res = S.run_search(g, S.MCTSSearch(g, 2, seed=0), budget=40,
                           batch_size=8, backend="vectorized")
    tel.close()

    with open(path) as f:                # valid JSON, Chrome shape
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events and obs.load_trace(path) == events

    names = {e["name"] for e in events}
    assert {"driver.run", "driver.round", "driver.evaluate",
            "engine.batch"} <= names

    stacks: dict = {}
    last_ts = -1.0
    for e in events:
        assert {"name", "ph", "ts", "pid"} <= set(e)
        assert e["ts"] >= last_ts        # monotone emission order
        last_ts = e["ts"]
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append(e["name"])
        elif e["ph"] == "E":             # matched LIFO per thread
            assert stacks[e["tid"]].pop() == e["name"]
        else:
            assert e["ph"] in ("C", "i")
    assert all(not st for st in stacks.values())

    # driver.round B events carry their 0-based round index, in order.
    rounds = [e["args"]["round"] for e in events
              if e["name"] == "driver.round" and e["ph"] == "B"]
    assert rounds == list(range(len(res.telemetry)))


# -- warm runs: zero measure spans + store/evaluator meter parity -------------

def test_warm_run_zero_measure_spans_and_store_stats_parity(tmp_path):
    path = str(tmp_path / "eval.store")
    g = C.spmv_dag()

    def search(store):
        return S.run_search(g, S.MCTSSearch(g, 2, seed=0), budget=60,
                            batch_size=8, backend="vectorized",
                            store=store)

    with EvalStore(path) as st:
        cold = search(st)
        cold_stats = st.stats()
    assert cold.cache_misses > 0
    assert cold_stats["records_appended"] == cold.cache_misses
    assert cold_stats["bytes_appended"] > 0
    assert cold_stats["append_seconds"] >= 0.0

    tel = obs.Telemetry()
    with obs.use(tel), EvalStore(path) as st2:   # registry sees the open
        warm = search(st2)
        warm_stats = st2.stats()
    assert warm.times == cold.times
    assert warm.cache_misses == 0 and warm.store_hits > 0
    spans = tel.spans_by_name()
    assert spans.get("engine.measure", {}).get("count", 0) == 0
    assert spans["store.open"]["count"] == 1
    assert "store.append" not in spans           # nothing new to write
    # stats() parity: every store hit the evaluator metered is exactly
    # one successful lookup on the store handle.
    assert warm_stats["lookup_hits"] == warm.store_hits
    assert warm_stats["lookups"] >= warm_stats["lookup_hits"]
    assert warm_stats["records_appended"] == 0
    assert warm_stats["records_loaded"] == cold.cache_misses
    # The warm open reads back exactly what the cold run appended,
    # plus the file-format magic header.
    assert warm_stats["bytes_read"] \
        == cold_stats["bytes_appended"] + len(MAGIC)


def test_store_open_span_reports_truncated_tail(tmp_path):
    path = tmp_path / "eval.store"
    with EvalStore(path) as st:
        st.put_many(b"f" * 16, [(b"k1", 1.0)])
    with open(path, "ab") as f:
        f.write(b"\x01garbage-partial-record")
    ex = obs.MemoryExporter()
    tel = obs.Telemetry(exporters=[ex])
    with obs.use(tel):
        with EvalStore(path) as st2:
            assert len(st2) == 1
            assert st2.stats()["truncated_bytes"] > 0
    assert tel.counters()["store.truncated_tails"] == 1.0
    trunc = [e for e in ex.events
             if e["name"] == "store.truncated_tail" and e["ph"] == "i"]
    assert len(trunc) == 1 and trunc[0]["args"]["bytes"] > 0


# -- sinks --------------------------------------------------------------------

def _fake_batch(keys, times):
    g = C.spmv_dag()
    scheds = [None] * len(keys)          # TraceSink never touches them
    return EvalBatch(schedules=scheds, keys=list(keys),
                     times=np.asarray(times, dtype=np.float64))


def test_trace_sink_round_indices_and_key_stream_shapes():
    sink = TraceSink()
    sink.consume(_fake_batch([b"a", b"b"], [2.0, 1.0]),
                 np.array([True, True]))
    sink.consume(_fake_batch([b"c"], [3.0]), np.array([False]))
    assert [r["round"] for r in sink.rounds] == [0, 1]
    assert sink.rounds[0]["best"] == 1.0
    assert sink.rounds[1]["best"] == 1.0  # running best, not per-round
    # Back-compat: the default stream is still a flat key tuple.
    assert sink.key_stream() == (b"a", b"b", b"c")
    assert sink.key_stream(rounds=True) \
        == ((0, b"a"), (0, b"b"), (1, b"c"))


def test_telemetry_sink_registered_and_emits():
    assert "telemetry" in SINKS
    g = C.spmv_dag()
    sink = make_sink("telemetry", g)
    assert isinstance(sink, TelemetrySink)

    # Disabled registry: a pure no-op that still counts rounds.
    sink.consume(_fake_batch([b"a"], [1.0]), np.array([True]))
    assert sink.n_rounds == 1

    ex = obs.MemoryExporter()
    tel = obs.Telemetry(exporters=[ex])
    with obs.use(tel):
        sink.consume(_fake_batch([b"b", b"c"], [2.0, 0.5]),
                     np.array([True, False]))
    assert sink.n_rounds == 2
    assert tel.counters() == {"sink.consumed": 2.0, "sink.fresh": 1.0}
    assert tel.gauges() == {"sink.best": 0.5}
    marks = [e for e in ex.events if e["name"] == "sink.round"]
    assert len(marks) == 1 and marks[0]["args"]["round"] == 1


def test_driver_run_with_telemetry_sink_matches_plain():
    g = C.spmv_dag()
    from repro.driver import SearchDriver
    plain = SearchDriver(g, S.MCTSSearch(g, 2, seed=0), budget=30,
                         batch_size=6).run()
    tel = obs.Telemetry()
    with obs.use(tel):
        sunk = SearchDriver(g, S.MCTSSearch(g, 2, seed=0), budget=30,
                            batch_size=6, sinks=["telemetry"]).run()
    assert sunk.times == plain.times
    assert tel.counters()["sink.consumed"] == sunk.n_proposed
    assert tel.gauges()["sink.best"] == sunk.best()[1]


# -- the benchmark baseline comparator ----------------------------------------

def test_compare_to_baseline_flags_only_regressions():
    from benchmarks.run import compare_to_baseline
    baseline = [{"name": "a", "us_per_call": 100.0, "derived": ""},
                {"name": "b", "us_per_call": 100.0, "derived": ""},
                {"name": "gone", "us_per_call": 5.0, "derived": ""}]
    records = [{"name": "a", "us_per_call": 200.0, "derived": ""},
               {"name": "b", "us_per_call": 120.0, "derived": ""},
               {"name": "new", "us_per_call": 1.0, "derived": ""}]
    lines, regs = compare_to_baseline(records, baseline, threshold=0.5)
    assert regs == ["a"]                 # +100% > 50%; +20% is ok
    text = "\n".join(lines)
    assert "REGRESSED" in text and "+100.0%" in text
    assert "new" in text and "gone" in text
    # Everything passes under a permissive threshold.
    _, regs_loose = compare_to_baseline(records, baseline, threshold=1.5)
    assert regs_loose == []
