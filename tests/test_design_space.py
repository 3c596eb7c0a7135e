"""The DesignSpace protocol: registry, schedule-space bit-compat, and
parameter grids.

The schedule-space locks are THE refactor acceptance contract: a
search driven through an explicit :class:`ScheduleSpace` must be
byte-identical — (features, labels, times), cache/store accounting,
store fingerprints — to the historical graph-first calls on every
analytic backend, and the space's RNG consumption must match the
pre-protocol helpers exactly (same seeds -> same trajectories).
"""
import random

import numpy as np
import pytest

import repro.core as C
import repro.engine as E
import repro.search as S
from repro.core.costmodel import Machine, op_durations
from repro.engine.store import store_fingerprint
from repro.rules import distill
from repro.space import (SPACES, DesignSpace, ParamFeature, ParamSpace,
                         ScheduleSpace, as_space, demo_param_space,
                         make_space, random_schedule)


# -- registry / normalization -------------------------------------------------

def test_registry_has_the_shipped_spaces():
    assert {"spmv", "spmv_fine", "halo3d", "flash_attention",
            "spmv_mulsum", "pack", "demo"} <= set(SPACES)
    sp = make_space("spmv", n_streams=3)
    assert isinstance(sp, ScheduleSpace) and sp.n_streams == 3
    assert isinstance(make_space("demo"), ParamSpace)
    with pytest.raises(ValueError, match="unknown design space"):
        make_space("no-such-space")


def test_as_space_normalizes_graphs_and_passes_spaces_through():
    g = C.spmv_dag()
    sp = as_space(g)
    assert isinstance(sp, ScheduleSpace)
    assert sp.graph is g and sp.n_streams == 2     # historical default
    assert as_space(g, 3).n_streams == 3
    demo = demo_param_space()
    assert as_space(demo) is demo
    with pytest.raises(TypeError, match="n_streams"):
        as_space(demo, 2)
    with pytest.raises(TypeError):
        as_space(42)


# -- schedule-space bit-compat ------------------------------------------------

def test_schedule_space_fingerprint_is_the_graph_fingerprint():
    """Old store files must stay warm: the space's fingerprint equals
    the pre-protocol graph fingerprint byte for byte."""
    g = C.spmv_dag()
    m = Machine()
    durs = op_durations(g, m)
    sp = ScheduleSpace(g, 2)
    assert sp.fingerprint(m, durs, "analytic") \
        == store_fingerprint(g, m, durs, "analytic")


def test_schedule_space_rng_matches_historical_helpers():
    """random_candidate consumes the RNG exactly like random_schedule
    (same seed -> same schedule), so seeded searches reproduce."""
    g = C.spmv_dag()
    sp = ScheduleSpace(g, 2)
    for seed in range(5):
        a = sp.random_candidate(random.Random(seed))
        b = random_schedule(g, 2, random.Random(seed))
        assert a.items == b.items


@pytest.mark.parametrize("backend,kwargs", [
    ("sim", {}),
    ("vectorized", {}),
    ("pool", {"n_workers": 2, "min_shard": 1}),
])
def test_space_first_search_is_byte_identical_to_graph_first(
        backend, kwargs):
    """run_search(space, ...) == run_search(graph, ...) on every
    analytic backend: same (features, labels, times), same accounting."""
    g = C.spmv_dag()

    def run(target):
        strat = S.MCTSSearch(target, 2 if target is g else None, seed=4)
        return S.run_search(target, strat, budget=60, batch_size=4,
                            backend=backend,
                            backend_kwargs=dict(kwargs))

    a = run(g)
    b = run(ScheduleSpace(g, 2))
    assert a.times == b.times
    assert [s.items for s in a.schedules] \
        == [s.items for s in b.schedules]
    assert (a.cache_hits, a.cache_misses, a.store_hits) \
        == (b.cache_hits, b.cache_misses, b.store_hits)
    fa, la, ta = a.dataset()
    fb, lb, tb = b.dataset()
    assert ta.tobytes() == tb.tobytes()
    assert fa.X.tobytes() == fb.X.tobytes()
    assert fa.names() == fb.names()
    assert np.array_equal(la.labels, lb.labels)


def test_strategies_accept_spaces_and_graphs_interchangeably():
    g = C.spmv_dag()
    sp = ScheduleSpace(g, 2)
    for mk in (S.RandomSearch, S.ExhaustiveSearch):
        a = mk(g, 2) if mk is S.ExhaustiveSearch else mk(g, 2, seed=1)
        b = mk(sp) if mk is S.ExhaustiveSearch else mk(sp, seed=1)
        pa, pb = a.propose(12), b.propose(12)
        assert [s.items for s in pa] == [s.items for s in pb]


# -- ParamSpace unit behavior -------------------------------------------------

@pytest.fixture()
def grid():
    return demo_param_space()


def test_param_space_candidates_and_encoding(grid):
    cand = grid.candidate(tile=32, unroll=2, prefetch=1)
    assert cand == (32, 2, 1)
    assert grid.as_dict(cand) == {"tile": 32, "unroll": 2,
                                  "prefetch": 1}
    keys, enc = grid.encode_batch([cand, (8, 1, 0)])
    assert enc.dtype == np.int32 and enc.shape == (2, 3)
    assert enc.tolist() == [[2, 1, 1], [0, 0, 0]]
    assert keys[0] == enc[0].tobytes()
    assert len(set(keys)) == 2
    assert grid.tie_key(cand) == (2, 1, 1)
    assert grid.describe(cand) == "tile=32, unroll=2, prefetch=1"
    with pytest.raises(ValueError, match="not a value"):
        grid.encode_batch([(31, 2, 1)])
    with pytest.raises(ValueError, match="dimensions"):
        grid.encode_batch([(32, 2)])
    with pytest.raises(ValueError, match="candidate needs"):
        grid.candidate(tile=32)


def test_param_space_moves_build_candidates_in_dim_order(grid):
    assert grid.moves([]) == [8, 16, 32, 64, 128]
    assert grid.moves([8]) == [1, 2, 4]
    assert grid.moves([8, 1]) == [0, 1]
    assert grid.moves([8, 1, 0]) == []
    assert grid.finalize([8, 1, 0]) == (8, 1, 0)
    with pytest.raises(ValueError, match="incomplete"):
        grid.finalize([8, 1])
    cands = list(grid.enumerate_candidates())
    assert len(cands) == grid.n_candidates() == 5 * 3 * 2
    assert len(set(cands)) == len(cands)
    # random_candidate lands inside the grid; mutate stays inside too.
    rng = random.Random(0)
    c = grid.random_candidate(rng)
    assert c in set(cands)
    assert grid.mutate(c, rng) in set(cands)


def test_param_space_threshold_features(grid):
    feats = grid.all_features()
    by_dim = {}
    for f in feats:
        by_dim.setdefault(f.u, []).append(f)
    # n_values - 1 thresholds per ordered dimension (the smallest value
    # gives a constant column and is never emitted).
    assert [f.v for f in by_dim["tile"]] == [16, 32, 64, 128]
    assert [f.v for f in by_dim["unroll"]] == [2, 4]
    assert [f.v for f in by_dim["prefetch"]] == [1]
    assert all(f.kind == "param_ge" for f in feats)
    X = grid.apply_features([(8, 1, 0), (128, 4, 1)], feats)
    assert X[0].tolist() == [0] * len(feats)
    assert X[1].tolist() == [1] * len(feats)
    assert ParamFeature("param_ge", "tile", 64).describe(1) \
        == "tile >= 64"
    assert ParamFeature("param_ge", "tile", 64).describe(0) \
        == "tile < 64"
    # Features from a foreign basis evaluate to 0, not an error.
    alien = [ParamFeature("param_ge", "warp", 2)]
    assert grid.apply_features([(8, 1, 0)], alien).tolist() == [[0]]


def test_param_space_featurize_prunes_and_guards_degenerate(grid):
    fm = grid.featurize([(8, 1, 0), (8, 1, 1), (8, 2, 0)])
    assert {f.u for f in fm.features} == {"unroll", "prefetch"}
    with pytest.raises(C.DegenerateFeatureSpaceError):
        grid.featurize([(8, 1, 0), (8, 1, 0)])


def test_param_space_validation():
    with pytest.raises(ValueError, match="at least one"):
        ParamSpace("empty", [])
    with pytest.raises(ValueError, match="no values"):
        ParamSpace("p", [("a", ())])
    with pytest.raises(ValueError, match="duplicate values"):
        ParamSpace("p", [("a", (1, 1))])
    with pytest.raises(ValueError, match="duplicate dimension"):
        ParamSpace("p", [("a", (1,)), ("a", (2,))])


def test_param_space_fingerprints_separate_everything(grid):
    m = Machine()
    other_dims = ParamSpace(grid.name,
                            [("tile", (8, 16, 32)), ("unroll", (1, 2))],
                            signature=grid.signature)
    other_sig = demo_param_space()
    other_sig.signature = "different-instance"
    fps = {
        grid.fingerprint(m, {}, "analytic"),
        grid.fingerprint(m, {}, "kernel-wallclock:platform=cpu:kind=cpu:"
                                "count=1:repeats=5:warmup=1"),
        grid.fingerprint(Machine(flops_per_s=1e12), {}, "analytic"),
        other_dims.fingerprint(m, {}, "analytic"),
        other_sig.fingerprint(m, {}, "analytic"),
        demo_param_space("renamed").fingerprint(m, {}, "analytic"),
    }
    assert len(fps) == 6
    # Deterministic across instances.
    assert demo_param_space().fingerprint(m, {}, "analytic") \
        == grid.fingerprint(m, {}, "analytic")


def test_param_space_without_analytic_cost_points_at_wallclock():
    sp = ParamSpace("knobs", [("a", (1, 2))])
    with pytest.raises(NotImplementedError, match="wallclock"):
        E.make_evaluator(sp, "sim").evaluate([(1,)])


def test_analytic_backends_reject_graphless_spaces(grid):
    for backend, kwargs in (("vectorized", {}),
                            ("pool", {"n_workers": 2})):
        with pytest.raises(TypeError, match="no graph"):
            E.make_evaluator(grid, backend, **kwargs)


# -- searching a parameter grid ----------------------------------------------

def test_mcts_exhausts_demo_grid_and_finds_the_optimum(grid):
    strat = S.MCTSSearch(grid, seed=0)
    res = S.run_search(grid, strat, budget=400, batch_size=1)
    assert strat.exhausted()
    assert len(res.schedules) == grid.n_candidates()
    best, best_t = res.best()
    assert best == (32, 2, 1)                  # the bowl's optimum
    assert best_t == min(res.times)
    assert res.graph is None and res.space is grid


def test_exhaustive_and_random_over_param_space(grid):
    res = S.run_search(grid, S.ExhaustiveSearch(grid), budget=None)
    assert len(res.schedules) == grid.n_candidates()
    assert res.best()[0] == (32, 2, 1)
    rnd = S.run_search(grid, S.RandomSearch(grid, seed=2), budget=50)
    assert set(rnd.schedules) <= set(grid.enumerate_candidates())


def test_surrogate_guided_over_param_space(grid):
    strat = S.SurrogateGuided(grid, seed=0)
    res = S.run_search(grid, strat, budget=60, batch_size=4)
    assert res.n_proposed == 60
    assert len(res.schedules) <= grid.n_candidates()


def test_distill_param_space_rules(grid):
    """The rules pipeline speaks threshold features: an exhaustive
    demo-grid sweep distills to block-size-style interval rules."""
    res = S.run_search(grid, S.ExhaustiveSearch(grid), budget=None)
    report = distill(res)
    assert report.graph is None
    assert report.n_schedules == grid.n_candidates()
    assert report.rulesets
    text = report.render()
    assert "tile >= " in text or "tile < " in text
    assert report.training_error <= 0.25


def test_param_space_store_warm_start(tmp_path, grid, monkeypatch):
    """demo-grid searches warm-start across evaluators through the
    param-space fingerprint (same contract as schedule spaces)."""
    path = str(tmp_path / "eval.store")

    def run():
        return S.run_search(grid, S.MCTSSearch(grid, seed=1),
                            budget=80, batch_size=4, backend="sim",
                            store_path=path)

    cold = run()
    assert cold.cache_misses > 0 and cold.store_hits == 0

    def no_measuring(self, schedules, encoded=None):
        raise AssertionError("warm run measured — store missed")
    monkeypatch.setattr(E.BatchEvaluator, "_measure_batch",
                        no_measuring)
    warm = run()
    assert warm.cache_misses == 0
    assert warm.store_hits == cold.cache_misses
    assert warm.times == cold.times
    assert warm.schedules == cold.schedules
