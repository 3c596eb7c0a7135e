"""The acquisition-aware search driver (repro.driver).

Locks the three contracts the driver refactor introduced:

* ``run_search`` is a *bit-compatible* wrapper over
  :class:`~repro.driver.SearchDriver`: byte-identical
  (features, labels, times) and identical budget/cache accounting vs
  an embedded copy of the pre-refactor loop, for every analytic
  backend (and structurally for wallclock);
* acquisition screening is deterministic: same seed + same corpus
  choose the same batch on every analytic backend (the driver-round
  extension of the evaluator noise-permutation test);
* sinks stream the same dataset the batch pipeline materializes.
"""
import random

import numpy as np
import pytest

import repro.core as C
import repro.engine as E
import repro.search as S
from repro.core.dag import halo3d_dag
from repro.driver import (DatasetSink, SearchDriver, StreamingHistogram,
                          TraceSink, make_acquisition, make_sink,
                          predict_with_std)
from repro.rules.trees import forest_leaf_values
from repro.search.pipeline import SearchResult
from repro.search.strategy import PoolSearchStrategy


def _reference_run_search(graph, strategy, budget=2000, batch_size=1,
                          evaluator=None, backend=None,
                          sim_budget=None, stall_limit=1000):
    """Verbatim copy of the pre-driver ``run_search`` loop (PR 2-4).

    The oracle the thin wrapper is locked against: any divergence in
    proposal sequence, evaluator traffic, dedup, or accounting between
    this and ``S.run_search`` is a regression.
    """
    owns = evaluator is None
    ev = evaluator if evaluator is not None else \
        E.make_evaluator(graph, backend or "sim")
    hits0, misses0 = ev.cache_hits, ev.cache_misses
    schedules, times = [], []
    seen = set()
    n_proposed = 0
    stalled = 0
    try:
        while ((budget is None or n_proposed < budget) and
               (sim_budget is None
                or ev.cache_misses - misses0 < sim_budget)):
            ask = batch_size if budget is None else \
                min(batch_size, budget - n_proposed)
            batch = strategy.propose(ask)[:ask]
            if not batch:
                break
            n_proposed += len(batch)
            batch_misses0 = ev.cache_misses
            for schedule, (key, t) in zip(batch,
                                          ev.evaluate_keyed(batch)):
                strategy.observe(schedule, t)
                if key not in seen:
                    seen.add(key)
                    schedules.append(schedule)
                    times.append(t)
            if sim_budget is not None or budget is None:
                if ev.cache_misses == batch_misses0:
                    stalled += len(batch)
                    if stalled >= stall_limit:
                        break
                else:
                    stalled = 0
    finally:
        if owns:
            ev.close()
    return SearchResult(graph=graph, schedules=schedules, times=times,
                        n_proposed=n_proposed,
                        cache_hits=ev.cache_hits - hits0,
                        cache_misses=ev.cache_misses - misses0)


def _assert_results_identical(a, b):
    assert a.n_proposed == b.n_proposed
    assert a.cache_hits == b.cache_hits
    assert a.cache_misses == b.cache_misses
    assert a.times == b.times                     # exact float equality
    assert [s.items for s in a.schedules] == [s.items for s in b.schedules]
    fa, la, ta = a.dataset()
    fb, lb, tb = b.dataset()
    assert fa.features == fb.features
    assert fa.X.tobytes() == fb.X.tobytes()       # byte-identical
    np.testing.assert_array_equal(la.labels, lb.labels)
    assert ta.tobytes() == tb.tobytes()


# -- the thin wrapper is bit-compatible with the pre-refactor loop ----------

@pytest.mark.parametrize("backend", ["sim", "vectorized", "pool"])
def test_run_search_byte_identical_to_reference_loop(backend):
    g = C.spmv_dag()
    kwargs = {"n_workers": 2} if backend == "pool" else {}
    for make_strategy, run_kw in [
        (lambda: S.MCTSSearch(g, 2, seed=3),
         dict(budget=90, batch_size=4)),
        (lambda: S.RandomSearch(g, 2, seed=1),
         dict(budget=None, sim_budget=25, batch_size=1)),
        (lambda: S.SurrogateGuided(g, 2, seed=0, warmup=16),
         dict(budget=96, batch_size=8)),
    ]:
        ref = _reference_run_search(
            g, make_strategy(),
            evaluator=E.make_evaluator(g, backend, **kwargs), **run_kw)
        new = S.run_search(g, make_strategy(), backend=backend,
                           backend_kwargs=kwargs or None, **run_kw)
        _assert_results_identical(ref, new)


def test_run_search_wallclock_structurally_identical():
    """Wallclock measurements are not replayable across evaluators, so
    the lock is structural: against a *shared* (pre-warmed) evaluator
    the wrapper must propose the identical schedule sequence and read
    back the identical memoized times as the reference loop."""
    g = C.spmv_dag(rows_per_rank=32, nnz_per_rank=128)
    impls, env = E.demo_spmv_impls(g, n=8)
    ev = E.make_evaluator(g, "wallclock", impls=impls, env=env,
                          repeats=1)
    try:
        ref = _reference_run_search(g, S.MCTSSearch(g, 2, seed=5),
                                    budget=10, batch_size=2,
                                    evaluator=ev)
        assert ref.cache_misses > 0
        new = S.run_search(g, S.MCTSSearch(g, 2, seed=5), budget=10,
                           batch_size=2, evaluator=ev)
        assert new.times == ref.times             # pure memo replay
        assert [s.items for s in new.schedules] == \
            [s.items for s in ref.schedules]
        assert new.n_proposed == ref.n_proposed
        assert new.cache_misses == 0              # nothing re-measured
        assert new.cache_hits == ref.cache_hits + ref.cache_misses
    finally:
        ev.close()


def test_run_search_argument_validation_preserved():
    g = C.spmv_dag()
    ev = S.BatchEvaluator(g)
    with pytest.raises(ValueError, match="machine="):
        S.run_search(g, S.RandomSearch(g, 2), evaluator=ev,
                     machine=C.Machine())
    with pytest.raises(ValueError, match="backend"):
        S.run_search(g, S.RandomSearch(g, 2), evaluator=ev,
                     backend="sim")
    with pytest.raises(ValueError, match="acquisition"):
        SearchDriver(g, S.RandomSearch(g, 2),
                     acquisition_kwargs={"beta": 1.0})


def test_driver_is_single_use():
    g = C.spmv_dag()
    drv = SearchDriver(g, S.RandomSearch(g, 2, seed=0), budget=10)
    drv.run()
    with pytest.raises(RuntimeError, match="single-use"):
        drv.run()


def test_driver_acquisition_reaches_portfolio_exploitation_phase():
    """PortfolioSearch delegates the pool protocol to its surrogate
    phase: with argmin_topk the driver-screened run is identical to
    the plain one, and an uncertainty acquisition actually screens."""
    def make_port():
        return S.PortfolioSearch(C.spmv_dag(), 2, seed=0,
                                 seed_proposals=0, mcts_proposals=8,
                                 warmup=12)

    g = C.spmv_dag()
    a, b = make_port(), make_port()
    assert isinstance(a, PoolSearchStrategy)
    res_a = S.run_search(g, a, budget=60, batch_size=4)
    res_b = SearchDriver(g, b, budget=60, batch_size=4,
                         acquisition="argmin_topk").run()
    _assert_results_identical(res_a, res_b)
    assert b.surrogate.n_screened == a.surrogate.n_screened > 0

    c = make_port()
    SearchDriver(g, c, budget=60, batch_size=4, acquisition="ucb",
                 acquisition_kwargs={"beta": 1.0}).run()
    assert c.surrogate.n_screened > 0      # override reached the phase


def test_driver_clamps_over_returning_screen():
    """A screen() that ignores its budget must not overshoot — the
    pool path applies the same clamp as the propose() path."""
    g = C.spmv_dag()

    class Greedy10x(S.SurrogateGuided):
        def screen(self, pool, budget, acquisition):
            return list(pool)              # returns the WHOLE pool

    strat = Greedy10x(g, 2, seed=0, warmup=8)
    res = SearchDriver(g, strat, budget=40, batch_size=4,
                       acquisition="argmin_topk").run()
    assert res.n_proposed == 40
    assert res.cache_hits + res.cache_misses == 40


def test_dataset_sink_dedups_across_driver_runs():
    """One sink fed by two runs over a shared memoized evaluator holds
    each canonical implementation exactly once (the per-run fresh mask
    alone would re-fold run 1's schedules in run 2)."""
    g = C.spmv_dag()
    sink = DatasetSink(g)
    with E.make_evaluator(g, "sim") as ev:
        r1 = SearchDriver(g, S.RandomSearch(g, 2, seed=0), budget=30,
                          evaluator=ev, sinks=[sink]).run()
        SearchDriver(g, S.RandomSearch(g, 2, seed=0), budget=30,
                     evaluator=ev, sinks=[sink]).run()
        r3 = SearchDriver(g, S.RandomSearch(g, 2, seed=1), budget=30,
                          evaluator=ev, sinks=[sink]).run()
    keys = [E.canonical_key(s) for s in sink.schedules]
    assert len(keys) == len(set(keys))     # no duplicate rows
    assert len(sink.schedules) == len(sink.times) == sink.histogram.n
    # run 1's corpus is a prefix; run 3 only appended novel schedules
    assert sink.schedules[:len(r1.schedules)] == r1.schedules
    assert len(sink.schedules) <= len(r1.schedules) + len(r3.schedules)


def test_driver_argmin_topk_reproduces_strategy_screening():
    """The driver's external argmin_topk screening IS the strategy's
    built-in screening: identical results, RNG state, and logs."""
    g = C.spmv_dag()
    a = S.SurrogateGuided(g, 2, seed=0, warmup=16)
    b = S.SurrogateGuided(g, 2, seed=0, warmup=16)
    assert isinstance(a, PoolSearchStrategy)
    res_a = S.run_search(g, a, budget=80, batch_size=4)
    res_b = SearchDriver(g, b, budget=80, batch_size=4,
                         acquisition="argmin_topk").run()
    _assert_results_identical(res_a, res_b)
    assert a.screen_log == b.screen_log
    assert a.n_screened == b.n_screened


# -- acquisition determinism across backends (satellite) --------------------

@pytest.mark.parametrize("acq,kw,noise", [
    ("ucb", {"beta": 1.0}, 0.0),
    ("expected_improvement", {}, 0.0),
    # the noise-permutation guarantee extended to driver rounds: noise
    # is seeded per (canonical key, draw index), so even a *noisy*
    # objective trains byte-identical surrogates on every backend
    ("ucb", {"beta": 1.0}, 0.05),
])
def test_acquisition_chooses_identical_batches_across_backends(
        acq, kw, noise):
    """Same seed + same corpus => identical chosen batch, every round,
    on every analytic backend (extends the evaluator noise-permutation
    guarantee to the full driver round loop: observed times are
    byte-identical across backends, so surrogate fits, acquisition
    scores, and the stable top-k must be too)."""
    streams = {}
    for backend in ("sim", "vectorized", "pool"):
        g = C.spmv_dag()
        strat = S.SurrogateGuided(g, 2, seed=0, warmup=16,
                                  surrogate="boost",
                                  surrogate_kwargs={"n_estimators": 20})
        trace = TraceSink()
        kwargs = {"n_workers": 2} if backend == "pool" else {}
        if noise:
            kwargs.update(noise_sigma=noise, noise_seed=7)
        res = SearchDriver(g, strat, budget=72, batch_size=4,
                           backend=backend,
                           backend_kwargs=kwargs or None,
                           acquisition=acq, acquisition_kwargs=kw,
                           sinks=[trace]).run()
        streams[backend] = (trace.key_stream(), tuple(res.times))
    assert streams["sim"] == streams["vectorized"] == streams["pool"]


# -- acquisition functions ---------------------------------------------------

@pytest.fixture(scope="module")
def boosted_corpus():
    g = halo3d_dag()
    rng = random.Random(0)
    train = [S.random_schedule(g, 2, rng) for _ in range(150)]
    with E.make_evaluator(g, "vectorized") as ev:
        times = ev.evaluate(train)
    sur = S.GradientBoostedSurrogate(g, n_estimators=40)
    for s, t in zip(train, times):
        sur.observe(s, t)
    pool = [S.random_schedule(g, 2, rng) for _ in range(80)]
    return g, sur, pool


def test_predict_with_std_mean_matches_predict(boosted_corpus):
    _, sur, pool = boosted_corpus
    mu, sd = sur.predict_with_std(pool)
    np.testing.assert_array_equal(mu, sur.predict(pool))
    assert sd.shape == mu.shape
    assert np.all(sd >= 0.0)
    assert np.any(sd > 0.0)          # a real ensemble disagrees somewhere
    assert sur.n_trees >= 2


def test_predict_with_std_degenerate_is_zero():
    g = C.spmv_dag()
    sur = S.GradientBoostedSurrogate(g, refit_every=1)
    s = S.random_schedule(g, 2, random.Random(0))
    mu, sd = sur.predict_with_std([s])
    assert mu.tolist() == [0.0] and sd.tolist() == [0.0]
    # generic helper: surrogates without predict_with_std get sd = 0
    ridge = S.RidgeSurrogate(g)
    mu2, sd2 = predict_with_std(ridge, [s])
    assert sd2.tolist() == [0.0]


def test_forest_leaf_values_matches_per_tree_predict(boosted_corpus):
    g, sur, pool = boosted_corpus
    from repro.core.features import apply_features
    X = apply_features(g, pool, sur._features).astype(np.float64)
    H = forest_leaf_values(sur._trees, X)
    assert H.shape == (sur.n_trees, len(pool))
    for t, tree in enumerate(sur._trees):
        np.testing.assert_array_equal(H[t], tree.predict(X))
    with pytest.raises(ValueError, match="at least one tree"):
        forest_leaf_values([], X)


def test_ucb_beta_zero_is_argmin_ordering(boosted_corpus):
    _, sur, pool = boosted_corpus
    s_ucb, mu_ucb = make_acquisition("ucb", beta=0.0)(sur, pool)
    s_arg, mu_arg = make_acquisition("argmin_topk")(sur, pool)
    np.testing.assert_array_equal(s_ucb, s_arg)
    np.testing.assert_array_equal(mu_ucb, mu_arg)
    # positive beta rewards uncertainty: scores can only drop
    s_b, _ = make_acquisition("ucb", beta=2.0)(sur, pool)
    assert np.all(s_b <= s_arg + 1e-15)


def test_expected_improvement_prefers_low_mean_and_uncertainty():
    class Stub:
        def __init__(self, mu, sd):
            self._mu = np.asarray(mu, float)
            self._sd = np.asarray(sd, float)

        def predict(self, pool):
            return self._mu

        def predict_with_std(self, pool):
            return self._mu, self._sd

    ei = make_acquisition("expected_improvement")
    pool = [None] * 3
    # equal sd: lower mean wins (scores are lower-is-better)
    s, mu = ei(Stub([1.0, 2.0, 3.0], [0.5, 0.5, 0.5]), pool, best=2.5)
    assert s[0] < s[1] < s[2]
    np.testing.assert_array_equal(mu, [1.0, 2.0, 3.0])
    # equal mean: higher sd wins
    s, _ = ei(Stub([2.0, 2.0, 2.0], [0.1, 0.5, 1.0]), pool, best=2.0)
    assert s[2] < s[1] < s[0]
    # no incumbent / no uncertainty: falls back to mean ordering
    s, _ = ei(Stub([3.0, 1.0, 2.0], [1.0, 1.0, 1.0]), pool, best=None)
    np.testing.assert_array_equal(s, [3.0, 1.0, 2.0])
    s, _ = ei(Stub([3.0, 1.0, 2.0], [0.0, 0.0, 0.0]), pool, best=2.0)
    np.testing.assert_array_equal(s, [3.0, 1.0, 2.0])
    with pytest.raises(ValueError, match="unknown acquisition"):
        make_acquisition("nope")


def test_expected_improvement_zero_ei_tail_ranks_by_mean():
    """Mixed pool: candidates whose EI is exactly zero (deterministic,
    past the incumbent) must rank by predicted time behind every
    positive-EI candidate — not by accidental pool order."""
    class Stub:
        def predict_with_std(self, pool):
            #           EI > 0     ── zero-EI tail (sd=0, mu>=best) ──
            return (np.array([2.0, 5.0, 3.0, 4.0]),
                    np.array([0.5, 0.0, 0.0, 0.0]))

        def predict(self, pool):
            return self.predict_with_std(pool)[0]

    ei = make_acquisition("expected_improvement")
    s, mu = ei(Stub(), [None] * 4, best=2.5)
    order = np.argsort(s, kind="stable").tolist()
    assert order == [0, 2, 3, 1]          # EI winner, then by mu
    np.testing.assert_array_equal(mu, [2.0, 5.0, 3.0, 4.0])


# -- sinks -------------------------------------------------------------------

def test_dataset_sink_streams_byte_identical_dataset():
    g = C.spmv_dag()
    sink = make_sink("dataset", g)
    res = SearchDriver(g, S.MCTSSearch(g, 2, seed=0), budget=120,
                       batch_size=8, sinks=[sink]).run()
    assert sink.n_consumed == res.n_proposed
    assert [s.items for s in sink.schedules] == \
        [s.items for s in res.schedules]
    fm_s, lab_s, t_s = sink.dataset()
    fm_r, lab_r, t_r = res.dataset()
    assert fm_s.features == fm_r.features
    assert fm_s.X.tobytes() == fm_r.X.tobytes()
    np.testing.assert_array_equal(lab_s.labels, lab_r.labels)
    assert t_s.tobytes() == t_r.tobytes()
    # histogram folded every fresh observation
    assert sink.histogram.n == len(res.schedules)


def test_dataset_sink_matrix_cache_invalidated_by_consume():
    """matrix() prunes once per corpus length: repeated calls return
    the same object, a consume that adds rows drops the cache, and the
    cached matrix stays byte-identical to a fresh pruning pass."""
    g = C.spmv_dag()
    sink = DatasetSink(g)
    SearchDriver(g, S.RandomSearch(g, 2, seed=0), budget=40,
                 batch_size=8, sinks=[sink]).run()
    fm = sink.matrix()
    assert sink.matrix() is fm                 # cached, not re-pruned
    assert sink.dataset()[0] is fm
    SearchDriver(g, S.RandomSearch(g, 2, seed=1), budget=40,
                 batch_size=8, sinks=[sink]).run()
    fm2 = sink.matrix()
    assert fm2 is not fm                       # new rows invalidated it
    assert fm2.X.shape[0] == len(sink.schedules)
    fresh = sink.basis.matrix()
    assert fm2.features == fresh.features
    assert fm2.X.tobytes() == fresh.X.tobytes()


def test_dataset_sink_distill_skips_featurize():
    import repro.rules as R
    g = C.spmv_dag()
    sink = DatasetSink(g)
    res = SearchDriver(g, S.MCTSSearch(g, 2, seed=0), budget=100,
                       sinks=[sink]).run()
    rep_stream = sink.distill()
    rep_batch = R.distill(res)
    assert "featurize" not in rep_stream.stage_seconds
    assert "featurize" in rep_batch.stage_seconds
    assert rep_stream.training_error == rep_batch.training_error
    assert rep_stream.labeling.n_classes == rep_batch.labeling.n_classes
    assert len(rep_stream.rulesets) == len(rep_batch.rulesets)
    # row-count mismatch is rejected, not silently mis-distilled
    with pytest.raises(ValueError, match="rows"):
        R.distill(res, features=C.featurize(g, res.schedules[:-1]))


def test_streaming_histogram_matches_numpy():
    rng = np.random.default_rng(0)
    h = StreamingHistogram(half_bins=32)
    vals = []
    for scale in (1.0, 5.0, 40.0):      # forces two range doublings
        batch = rng.uniform(0.0, scale, 100)
        h.add(batch)
        vals.extend(batch.tolist())
    want, _ = np.histogram(vals, bins=h.edges())
    np.testing.assert_array_equal(h.counts, want)
    assert h.n == len(vals)


from hypothesis import given, settings, strategies as st


@settings(max_examples=40, deadline=None)
@given(st.lists(st.lists(st.floats(min_value=0.0, max_value=1e6),
                         min_size=0, max_size=40),
                min_size=1, max_size=8),
       st.integers(min_value=1, max_value=64))
def test_streaming_histogram_property(batches, half_bins):
    """Property lock for the doubling fold: (1) every range doubling
    preserves total counts exactly — observations are merged, never
    dropped; (2) after any batch sequence, counts equal np.histogram
    of the folded data on the histogram's own edges()."""
    h = StreamingHistogram(half_bins=half_bins)
    seen = []
    for batch in batches:
        n_before = h.n
        h.add(np.asarray(batch, dtype=np.float64))
        seen.extend(batch)
        assert h.n == n_before + len(batch)     # doubling loses nothing
    if not seen:
        return
    assert h.counts.size == 2 * half_bins       # footprint is constant
    edges = h.edges()
    assert edges[0] == 0.0 and edges[-1] == h.hi
    assert max(seen) < h.hi or max(seen) == 0.0
    want, _ = np.histogram(seen, bins=edges)
    np.testing.assert_array_equal(h.counts, want)


# -- SearchResult.best() tie handling (satellite) ----------------------------

def test_best_breaks_ties_by_canonical_encoding():
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))[:6]
    t = [2.0, 1.0, 1.0, 3.0, 1.0, 4.0]
    tied = [scheds[i] for i in (1, 2, 4)]
    want = min(tied, key=lambda s: tuple(
        (n, -1 if st is None else st) for n, st in E.canonical_key(s)))
    for order in ([0, 1, 2, 3, 4, 5], [5, 4, 3, 2, 1, 0],
                  [2, 4, 1, 0, 3, 5]):
        res = SearchResult(graph=g, schedules=[scheds[i] for i in order],
                           times=[t[i] for i in order], n_proposed=6,
                           cache_hits=0, cache_misses=6)
        best_s, best_t = res.best()
        assert best_t == 1.0
        assert best_s.items == want.items, order
    with pytest.raises(ValueError, match="empty"):
        SearchResult(graph=g, schedules=[], times=[], n_proposed=0,
                     cache_hits=0, cache_misses=0).best()
