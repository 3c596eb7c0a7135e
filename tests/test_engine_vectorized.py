"""engine.vectorized: bit-identical to core.costmodel.simulate.

The contract is exact float equality (==, not isclose): the batch
simulator must execute the same IEEE adds/maxes per element as the
serial discrete-event loop. Locked three ways — exhaustively on the
paper's coarse SpMV space, and by randomized property tests on the
fine-grained SpMV and halo3d spaces (uniform random canonical
schedules at 2 and 3 streams).
"""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
import repro.engine as E
from repro.core.costmodel import Machine
from repro.core.dag import halo3d_dag, spmv_dag_fine
from repro.search.strategy import random_schedule


@pytest.fixture(scope="module")
def spmv_space():
    g = C.spmv_dag()
    return g, list(C.enumerate_schedules(g, 2))


def test_exhaustive_spmv_bit_identical(spmv_space):
    """The whole 280-schedule paper space, == on floats."""
    g, scheds = spmv_space
    ev = E.make_evaluator(g, "vectorized")
    assert ev.evaluate(scheds) == [C.makespan(g, s) for s in scheds]
    assert ev.cache_misses == len(scheds)


def test_exhaustive_spmv_bit_identical_custom_machine(spmv_space):
    g, scheds = spmv_space
    m = Machine(flops_per_s=100e12, hbm_bytes_per_s=500e9,
                launch_overhead_s=7e-6, sync_op_s=0.9e-6)
    ev = E.make_evaluator(g, "vectorized", machine=m)
    assert ev.evaluate(scheds) == [C.makespan(g, s, m) for s in scheds]


def test_simulate_batch_function_matches_simulate(spmv_space):
    """The raw batch simulator (no evaluator cache in front)."""
    g, scheds = spmv_space
    from repro.engine import GraphTables, simulate_batch
    from repro.core.costmodel import op_durations
    m = Machine()
    tables = GraphTables(g, m, op_durations(g, m))
    out = simulate_batch(tables, scheds)
    assert out.tolist() == [C.makespan(g, s, m) for s in scheds]


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 3))
def test_property_fine_grained_bit_identical(seed, n_streams):
    g = spmv_dag_fine()
    rng = random.Random(seed)
    scheds = [random_schedule(g, n_streams, rng) for _ in range(8)]
    ev = E.make_evaluator(g, "vectorized")
    assert ev.evaluate(scheds) == [C.makespan(g, s) for s in scheds]


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 3))
def test_property_halo3d_bit_identical(seed, n_streams):
    g = halo3d_dag()
    rng = random.Random(seed)
    scheds = [random_schedule(g, n_streams, rng) for _ in range(6)]
    ev = E.make_evaluator(g, "vectorized")
    assert ev.evaluate(scheds) == [C.makespan(g, s) for s in scheds]


def test_non_canonical_input_hits_canonical_twin(spmv_space):
    """Stream-relabeled input must hit the canonical cache entry and
    produce the identical float (the simulator is bijection-invariant)."""
    g, scheds = spmv_space
    two = next(s for s in scheds if len(set(s.streams().values())) == 2)
    relabeled = C.Schedule(tuple(
        C.BoundOp(i.name, 1 - i.stream if i.stream is not None else None)
        for i in two.items))
    ev = E.make_evaluator(g, "vectorized")
    t0, t1 = ev.evaluate([two, relabeled])
    assert t0 == t1 == C.makespan(g, relabeled)
    assert (ev.cache_hits, ev.cache_misses) == (1, 1)


def test_vectorized_agrees_inside_run_search(spmv_space):
    """run_search(backend='vectorized') == run_search(backend='sim'),
    byte for byte, at batch_size > 1."""
    import repro.search as S
    g, _ = spmv_space
    results = {}
    for backend in ("sim", "vectorized"):
        res = S.run_search(g, S.MCTSSearch(g, 2, seed=3), budget=120,
                           batch_size=16, backend=backend)
        results[backend] = res
    a, b = results["sim"], results["vectorized"]
    assert a.times == b.times
    assert [s.key() for s in a.schedules] == [s.key() for s in b.schedules]
    assert (a.cache_hits, a.cache_misses) == (b.cache_hits, b.cache_misses)


def test_stepdag_supported():
    """The train-step DAG (GPU collectives, no CPU comm roles) encodes
    and simulates bit-identically too."""
    from repro.core.stepdag import StepCosts, train_step_dag
    g = train_step_dag(3, StepCosts(fwd_flops=1e12, bwd_flops=2e12,
                                    fwd_bytes=1e9, bwd_bytes=2e9,
                                    grad_bytes=5e8))
    rng = random.Random(0)
    scheds = [random_schedule(g, 2, rng) for _ in range(20)]
    ev = E.make_evaluator(g, "vectorized")
    assert ev.evaluate(scheds) == [C.makespan(g, s) for s in scheds]


def test_unsupported_rendezvous_graph_raises():
    """A WaitRecv whose posts are not DAG ancestors (no rendezvous
    edges) is rejected at construction, not silently mis-simulated."""
    from repro.core.dag import CommRole, Graph, Op, OpKind
    g = Graph()
    g.add_op(Op("PostRecv", OpKind.CPU, comm_bytes=8.0,
                comm_role=CommRole.POST_RECV))
    g.add_op(Op("WaitRecv", OpKind.CPU, comm_role=CommRole.WAIT_RECV))
    # No PostRecv -> WaitRecv edge: the post is not an ancestor.
    g.finalize()
    with pytest.raises(ValueError, match="ancestor"):
        E.make_evaluator(g, "vectorized")
