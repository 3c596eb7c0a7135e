"""Autotuning the repo's own Pallas kernels through the DesignSpace
stack: the param-space wallclock backend, its value-correctness gate,
persistent warm starts, and block-size design rules.

Everything runs on CPU (interpret-mode kernels, tiny instances) so the
whole file stays in tier-1 budgets; the same code paths drive a real
TPU sweep by constructing the spaces with bigger shapes and
``interpret=None``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.engine as E
import repro.search as S
from repro import obs
from repro.core import spmv_dag
from repro.engine.params import KernelWallclockEvaluator
from repro.kernels.autotune import (flash_attention_space, pack_space,
                                    spmv_mulsum_space)
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.rules import distill
from repro.rules.labels import Labeling
from repro.space import KernelRunner, ParamSpace


def median_split(times: np.ndarray) -> Labeling:
    """Deterministic 2-class labeler (fast half / slow half).

    Tiny wall-clock corpora (a 9-point block grid) rarely show the
    multi-plateau structure the paper's convolution labeler keys on;
    a median split always yields two classes, so the distilled rules
    exercise the threshold-feature path deterministically.
    """
    order = np.argsort(times, kind="stable")
    s = times[order]
    cut = s.size // 2
    labels = np.empty(s.size, dtype=np.int64)
    labels[order] = (np.arange(s.size) >= cut).astype(np.int64)
    return Labeling(order=order, sorted_times=s,
                    convolution=np.zeros_like(s),
                    boundaries=np.array([cut - 1]),
                    labels=labels, n_classes=2)


def _spmv_grid(block_values=(32, 64)):
    return spmv_mulsum_space(n=128, k=4, block_values=block_values,
                             interpret=True)


# -- evaluator basics ---------------------------------------------------------

def test_kernel_wallclock_dispatch_and_requirements():
    sp = _spmv_grid()
    ev = E.make_evaluator(sp, "wallclock", repeats=1)
    assert isinstance(ev, KernelWallclockEvaluator)
    no_runner = ParamSpace("bare", [("a", (1, 2))])
    with pytest.raises(ValueError, match="KernelRunner"):
        E.make_evaluator(no_runner, "wallclock")
    with pytest.raises(ValueError, match="compile_mode"):
        E.make_evaluator(sp, "wallclock", compile_mode="eager")


@pytest.mark.parametrize("compile_mode", ["batch", "per_candidate"])
def test_kernel_sweep_measures_and_memoizes(compile_mode):
    sp = _spmv_grid()
    ev = E.make_evaluator(sp, "wallclock", repeats=2,
                          compile_mode=compile_mode)
    cands = list(sp.enumerate_candidates())
    times = ev.evaluate(cands)
    assert len(times) == 2 and all(t > 0.0 for t in times)
    assert ev.n_checked == 2                 # every candidate gated
    again = ev.evaluate(cands)
    assert again == times                    # memoized, not re-run
    assert ev.n_checked == 2
    assert ev.stats()["memory_hits"] == 2


def test_wallclock_gate_rejects_wrong_output_candidate():
    """The value-correctness gate: a kernel candidate producing wrong
    output is rejected before (batch mode: any) timing, and the paid
    measurements of earlier good candidates are salvaged."""
    honest = _spmv_grid(block_values=(16, 32, 64))
    bad_block = 64

    def build(params):
        run = honest.runner.build(params)
        if params["block_n"] != bad_block:
            return run
        return lambda: run() + 1.0           # wrong values, right shape
    broken = ParamSpace(honest.name, honest.dims,
                        runner=KernelRunner(
                            build=build,
                            reference=honest.runner.reference),
                        signature=honest.signature + ":broken")

    ev = E.make_evaluator(broken, "wallclock", repeats=1)
    with pytest.raises(AssertionError,
                       match="value-correctness gate"):
        ev.evaluate([(16,), (32,), (bad_block,)])
    # batch compile_mode gates before timing: nothing was banked for
    # the bad candidate, and in batch mode the good ones were not yet
    # timed either — re-evaluating them measures fresh.
    good = ev.evaluate([(16,), (32,)])
    assert all(t > 0.0 for t in good)

    # per_candidate mode interleaves, so the good candidates *before*
    # the bad one were already timed — salvage banks them (metered as
    # misses on next lookup, per the salvage contract): re-evaluating
    # them re-runs nothing, so the gate count stays at the first
    # pass's two successful checks.
    ev2 = E.make_evaluator(broken, "wallclock", repeats=1,
                           compile_mode="per_candidate")
    with pytest.raises(AssertionError,
                       match="value-correctness gate"):
        ev2.evaluate([(16,), (32,), (bad_block,)])
    assert ev2.n_checked == 2
    banked = ev2.evaluate([(16,), (32,)])
    assert ev2.n_checked == 2                # served from salvage
    assert all(t > 0.0 for t in banked)


def test_gate_error_names_the_candidate():
    honest = _spmv_grid(block_values=(32,))
    broken = ParamSpace(honest.name, honest.dims,
                        runner=KernelRunner(
                            build=lambda p: lambda: jnp.zeros(128),
                            reference=honest.runner.reference),
                        signature=honest.signature + ":zeros")
    ev = E.make_evaluator(broken, "wallclock", repeats=1)
    with pytest.raises(AssertionError, match="block_n=32"):
        ev.evaluate([(32,)])


def test_gate_tolerance_is_the_runners_unless_overridden():
    honest = _spmv_grid(block_values=(32,))

    def off_by(tol_atol):
        return ParamSpace(honest.name, honest.dims,
                          runner=KernelRunner(
                              build=lambda p: lambda: honest.runner.build(
                                  p)() + 1e-3,
                              reference=honest.runner.reference,
                              atol=tol_atol),
                          signature=honest.signature + ":off-by-1e-3")

    strict = E.make_evaluator(off_by(1e-6), "wallclock", repeats=1)
    with pytest.raises(AssertionError, match="value-correctness gate"):
        strict.evaluate([(32,)])
    loose = E.make_evaluator(off_by(1e-2), "wallclock", repeats=1)
    assert loose.evaluate([(32,)])[0] > 0.0 and loose.n_checked == 1
    override = E.make_evaluator(off_by(1e-2), "wallclock", repeats=1,
                                atol=1e-6)
    with pytest.raises(AssertionError, match="value-correctness gate"):
        override.evaluate([(32,)])


def _durations(events) -> dict[str, list[float]]:
    """Seconds of each finished span, by name (B/E pairs, LIFO)."""
    stack, out = [], {}
    for e in events:
        if e["ph"] == "B":
            stack.append(e)
        elif e["ph"] == "E":
            out.setdefault(e["name"], []).append(
                (e["ts"] - stack.pop()["ts"]) / 1e6)
    return out


@pytest.mark.parametrize("compile_mode", ["batch", "per_candidate"])
def test_gate_spans_split_the_gate(compile_mode):
    """One ``kernel.reference`` per evaluator; one ``kernel.build``,
    ``kernel.fetch`` and ``kernel.compare`` per candidate; the gate's
    three parts lie inside the ``gate_s`` the phases report."""
    out = jnp.arange(8, dtype=jnp.float32)
    stub = ParamSpace("stub", [("block", (1, 2, 3))],
                      runner=KernelRunner(
                          build=lambda p: lambda: out,
                          reference=lambda: np.arange(8, dtype=np.float32)),
                      signature="stub:arange8")
    ex = obs.MemoryExporter()
    with obs.use(obs.Telemetry(exporters=[ex])):
        ev = E.make_evaluator(stub, "wallclock", repeats=1,
                              compile_mode=compile_mode)
        ev.evaluate([(1,), (2,)])
        ev.evaluate([(3,)])                 # a second miss batch
    ends = [e for e in ex.events if e["ph"] == "E"]
    count = {name: sum(e["name"] == name for e in ends)
             for name in ("kernel.reference", "kernel.build",
                          "kernel.fetch", "kernel.compare")}
    assert count == {"kernel.reference": 1, "kernel.build": 3,
                     "kernel.fetch": 3, "kernel.compare": 3}
    assert {e["args"]["bytes"] for e in ends
            if e["name"] in ("kernel.reference", "kernel.build",
                             "kernel.fetch", "kernel.compare")} == {32}
    gate_s = sum(e["args"]["gate_s"] for e in ends
                 if e["name"] in ("kernel.compile", "kernel.timing"))
    dur = _durations(ex.events)
    parts = sum(sum(dur[n]) for n in ("kernel.reference", "kernel.fetch",
                                      "kernel.compare"))
    assert 0.0 < parts <= gate_s + 1e-6
    assert ev.n_checked == 3


@pytest.mark.parametrize("name,kwargs", [
    ("spmv_mulsum", dict(n=128, k=4, block_values=(32,))),
    ("flash_attention", dict(batch=1, heads=1, seq=32, head_dim=16,
                             block_values=(16,))),
    ("pack", dict(n=256, m=64, block_c_values=(32,),
                  chunk_values=(64,))),
])
def test_make_space_spans_the_draw_and_the_copy(name, kwargs):
    """``space.make`` holds ``space.instance`` then ``space.put``, both
    sized in bytes, and the space is the same byte for byte with the
    telemetry on or off."""
    from repro.space import make_space

    plain = make_space(name, interpret=True, **kwargs)
    ex = obs.MemoryExporter()
    with obs.use(obs.Telemetry(exporters=[ex])):
        traced = make_space(name, interpret=True, **kwargs)
    assert [(e["ph"], e["name"]) for e in ex.events] == [
        ("B", "space.make"), ("B", "space.instance"),
        ("E", "space.instance"), ("B", "space.put"), ("E", "space.put"),
        ("E", "space.make")]
    sizes = {e["name"]: e["args"]["bytes"] for e in ex.events
             if e["ph"] == "E" and e["name"] != "space.make"}
    assert sizes["space.instance"] == sizes["space.put"] > 0
    params = plain.as_dict(next(iter(plain.enumerate_candidates())))
    for a, b in ((plain.runner.reference(), traced.runner.reference()),
                 (plain.runner.build(params)(),
                  traced.runner.build(params)())):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_check_values_off_skips_the_gate():
    honest = _spmv_grid(block_values=(32,))
    broken = ParamSpace(honest.name, honest.dims,
                        runner=KernelRunner(
                            build=lambda p: lambda: jnp.zeros(128),
                            reference=honest.runner.reference),
                        signature=honest.signature + ":unchecked")
    ev = E.make_evaluator(broken, "wallclock", repeats=1,
                          check_values=False)
    assert ev.evaluate([(32,)])[0] > 0.0
    assert ev.n_checked == 0


def test_platform_is_part_of_the_objective_key():
    sp = _spmv_grid()
    ev = E.make_evaluator(sp, "wallclock", repeats=3, warmup=2)
    key = ev._objective_key()
    dev = jax.devices()[0]
    # Device kind and count, not just the backend name: "tpu" alone
    # would let a store filled on one chip generation serve another.
    device = (f"platform={dev.platform}:kind={dev.device_kind}:"
              f"count={jax.device_count()}")
    assert key == f"kernel-wallclock:{device}:repeats=3:warmup=2"
    # The schedule-space wallclock backend keys on the same identity.
    impls, env = E.demo_spmv_impls(spmv_dag())
    sched_ev = E.make_evaluator(spmv_dag(), "wallclock", impls=impls,
                                env=env)
    assert sched_ev._objective_key() == \
        f"wallclock:{device}:repeats=5:warmup=1"
    # compile_mode moves compile cost around but measures the same
    # quantity — deliberately NOT in the key.
    ev2 = E.make_evaluator(sp, "wallclock", repeats=3, warmup=2,
                           compile_mode="per_candidate")
    assert ev2._objective_key() == key
    assert ev2.store_fingerprint == ev.store_fingerprint


# -- warm starts across runs --------------------------------------------------

def test_warm_kernel_search_replays_with_zero_measurements(
        tmp_path, monkeypatch):
    """tests/test_engine_store.py's acceptance lock, for kernel grids:
    the second ``run_search`` against a fresh evaluator performs zero
    measurements — 100% store hits — and replays the cold trajectory
    byte-identically (wallclock times are memoized real measurements,
    so the values match exactly)."""
    path = str(tmp_path / "kernels.store")

    def run():
        sp = _spmv_grid()                      # fresh space each run
        return S.run_search(sp, S.MCTSSearch(sp, seed=2), budget=6,
                            batch_size=2, backend="wallclock",
                            backend_kwargs={"repeats": 1},
                            store_path=path)

    cold = run()
    assert cold.cache_misses == 2 and cold.store_hits == 0
    assert len(cold.schedules) == 2

    def no_measuring(self, candidates, encoded=None):
        raise AssertionError("warm run called _measure_batch")
    monkeypatch.setattr(KernelWallclockEvaluator, "_measure_batch",
                        no_measuring)
    warm = run()
    assert warm.cache_misses == 0
    assert warm.store_hits == cold.cache_misses   # 100% store hits
    assert warm.cache_hits == cold.cache_hits
    assert warm.times == cold.times
    assert warm.schedules == cold.schedules
    fa, la, ta = cold.dataset()
    fb, lb, tb = warm.dataset()
    assert ta.tobytes() == tb.tobytes()
    assert fa.X.tobytes() == fb.X.tobytes()
    assert np.array_equal(la.labels, lb.labels)


def test_different_grids_never_share_store_entries(tmp_path):
    path = str(tmp_path / "kernels.store")
    sp = _spmv_grid()
    with E.make_evaluator(sp, "wallclock", repeats=1,
                          store_path=path) as ev:
        ev.evaluate(list(sp.enumerate_candidates()))
        assert ev.cache_misses == 2
    # Same kernel, different problem instance: different signature,
    # different fingerprint, zero warm hits.
    other = spmv_mulsum_space(n=256, k=4, block_values=(32, 64),
                              interpret=True)
    with E.make_evaluator(other, "wallclock", repeats=1,
                          store_path=path) as ev2:
        ev2.evaluate(list(other.enumerate_candidates()))
        assert (ev2.store_hits, ev2.cache_misses) == (0, 2)


# -- the acceptance criterion: kernel design rules ---------------------------

def test_flash_attention_autotune_distills_block_size_rules(tmp_path):
    """ISSUE acceptance: a flash_attention param-space wallclock search
    on CPU distills to a RuleReport of block-size design rules, and
    the warm re-run reports 100% store hits."""
    path = str(tmp_path / "fa.store")

    def run():
        sp = flash_attention_space(batch=1, heads=1, seq=64,
                                   head_dim=16,
                                   block_values=(16, 32, 64),
                                   interpret=True)
        res = S.run_search(sp, S.ExhaustiveSearch(sp), budget=None,
                           backend="wallclock",
                           backend_kwargs={"repeats": 1},
                           store_path=path)
        return sp, res

    sp, cold = run()
    assert len(cold.schedules) == sp.n_candidates() == 9
    assert cold.cache_misses == 9 and cold.store_hits == 0

    report = distill(cold, labeler=median_split)
    assert report.n_schedules == 9
    assert report.labeling.n_classes == 2
    assert report.rulesets and all(rs.rules for rs in report.rulesets)
    rule_dims = {r.feature.u for rs in report.rulesets
                 for r in rs.rules}
    assert rule_dims <= {"block_q", "block_k"} and rule_dims
    text = report.render()
    assert "block_q" in text or "block_k" in text

    _, warm = run()
    assert (warm.store_hits, warm.cache_misses) == (9, 0)  # 100% warm
    assert warm.times == cold.times


def _mask_dropped(body):
    return lambda *refs, **kw: body(*refs, **{**kw, "causal": False})


def _mask_off_by_one(body):
    return lambda *refs, **kw: body(
        *refs, **{**kw, "q_offset": kw["q_offset"] + 1})


def _state_not_carried(body):
    def faulty(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, **kw):
        m_scr[...] = jnp.full_like(m_scr, fa_kernel.NEG_INF)
        body(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
             m_scr, l_scr, acc_scr, **kw)
    return faulty


@pytest.fixture
def planted_flash_fault(monkeypatch):
    """Swap the flash kernel's body for a faulty one; the jit caches
    are cleared on both sides so no faulty trace outlives the test."""
    def plant(fault):
        monkeypatch.setattr(fa_kernel, "_flash_body",
                            fault(fa_kernel._flash_body))
        jax.clear_caches()
    yield plant
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("fault", [_mask_dropped, _mask_off_by_one,
                                   _state_not_carried])
def test_flash_gate_rejects_planted_faults(planted_flash_fault, fault):
    """The flash space's gate tolerance (atol 2e-2, set for the chip's
    bf16-pass dots) still rejects wrong masking and a lost online-
    softmax state, each far outside it."""
    sp = flash_attention_space(batch=1, heads=2, seq=64, head_dim=16,
                               block_values=(16,), interpret=True)
    ref = np.asarray(sp.runner.reference())
    planted_flash_fault(fault)
    err = np.abs(np.asarray(sp.runner.build({"block_q": 16,
                                             "block_k": 16})()) - ref)
    assert err.max() > 10 * sp.runner.atol
    ev = E.make_evaluator(sp, "wallclock", repeats=1)
    with pytest.raises(AssertionError, match="value-correctness gate"):
        ev.evaluate([(16, 16)])


@pytest.mark.parametrize("causal,steps", [(True, 6), (False, 8)])
def test_flash_space_build_counts_grid_steps(causal, steps):
    """Building a candidate adds its call's grid steps, read from the
    kernel's block table, and the full rectangle's: at 64 positions in
    16 x 32 blocks the causal table keeps 6 of 8 (q block, kv block)
    pairs a head."""
    from repro.kernels.flash_attention.kernel import block_table

    sp = flash_attention_space(batch=1, heads=2, seq=64, head_dim=16,
                               block_values=(16, 32), causal=causal,
                               interpret=True)
    table = block_table(4, 2, block_q=16, block_k=32, q_offset=0,
                        causal=causal)
    assert table.qi.size == steps
    with obs.use(obs.Telemetry()) as tel:
        sp.runner.build({"block_q": 16, "block_k": 32})
    assert tel.counters() == {"flash.grid_steps": 2 * steps,
                              "flash.grid_steps_dense": 2 * 8}


def test_pack_space_smallest_grid_round_trip():
    sp = pack_space(n=256, m=64, block_c_values=(32, 64),
                    chunk_values=(64, 128), interpret=True)
    assert sp.n_candidates() == 4
    res = S.run_search(sp, S.ExhaustiveSearch(sp), budget=None,
                       backend="wallclock",
                       backend_kwargs={"repeats": 1})
    assert len(res.times) == 4 and min(res.times) > 0.0
    best, _ = res.best()
    assert best in set(sp.enumerate_candidates())


def test_flash_attention_space_filters_non_divisor_blocks():
    sp = flash_attention_space(seq=64, block_values=(16, 48, 64),
                               interpret=True)
    assert dict(sp.dims)["block_q"] == (16, 64)
    with pytest.raises(ValueError, match="divides"):
        flash_attention_space(seq=64, block_values=(48,))
