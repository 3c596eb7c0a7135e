"""The rules-subsystem tree stack: vectorized-vs-loop split identity,
sklearn cross-checks, batch prediction, warm starts, regression trees,
and the gradient-boosted surrogate."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as C
import repro.rules as R


def tree_signature(tree):
    """(feature, threshold) preorder + leaf stats — full structure."""
    out = []

    def walk(nd):
        if nd.is_leaf:
            out.append(("leaf", nd.n_samples, nd.majority_class()))
            return
        out.append((nd.feature, nd.threshold))
        walk(nd.left)
        walk(nd.right)

    walk(tree.root)
    return out


def random_dataset(rng, kind):
    n = int(rng.integers(8, 120))
    d = int(rng.integers(1, 10))
    if kind == 0:                       # the paper's 0/1 features
        X = rng.integers(0, 2, size=(n, d)).astype(float)
    elif kind == 1:                     # small-cardinality ordinals
        X = rng.integers(0, 4, size=(n, d)).astype(float)
    elif kind == 2:                     # continuous
        X = rng.random((n, d))
    else:                               # mixed + constant columns
        X = np.concatenate(
            [rng.integers(0, 2, size=(n, d)).astype(float),
             rng.random((n, 2)), np.ones((n, 1))], axis=1)
    y = rng.integers(0, int(rng.integers(2, 5)), size=n)
    return X, y


# -- vectorized splitter == loop reference ------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_vectorized_splitter_identical_to_loop(seed):
    """The property pin: on random (X, y) of every feature flavor the
    vectorized and loop splitters grow bit-identical trees."""
    rng = np.random.default_rng(seed)
    X, y = random_dataset(rng, seed % 4)
    if len(np.unique(y)) < 2:
        y[0] = y[0] + 1
    mln = int(rng.integers(2, 14))
    tv = R.DecisionTree(mln, splitter="vectorized").fit(X, y)
    tl = R.DecisionTree(mln, splitter="loop").fit(X, y)
    assert tree_signature(tv) == tree_signature(tl)
    np.testing.assert_array_equal(tv.predict(X), tl.predict(X))


def test_vectorized_identical_across_feature_chunks(monkeypatch):
    """The sorted-path feature chunking must not change results: with a
    tiny _FEATURE_BLOCK every multi-valued dataset spans many chunks,
    and the chunk-local -> global feature mapping is exercised."""
    from repro.rules import trees as T

    monkeypatch.setattr(T, "_FEATURE_BLOCK", 8)
    rng = np.random.default_rng(13)
    for kind in (1, 2, 3):
        X, y = random_dataset(rng, kind)
        if len(np.unique(y)) < 2:
            y[0] = y[0] + 1
        tv = R.DecisionTree(8, splitter="vectorized").fit(X, y)
        tl = R.DecisionTree(8, splitter="loop").fit(X, y)
        assert tree_signature(tv) == tree_signature(tl), kind
        # regression trees share the chunked kernel
        yr = rng.standard_normal(len(y))
        rt = R.RegressionTree(max_leaf_nodes=6).fit(X, yr)
        assert rt.n_leaves() >= 1


def test_vectorized_identical_on_exhaustive_spmv():
    """Acceptance pin: prediction-identical trees on the exhaustive
    280-schedule SpMV dataset, through the full Algorithm-1 sweep."""
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))
    times = np.array([C.makespan(g, s) for s in scheds])
    lab = C.label_times(times)
    fm = C.featurize(g, scheds)
    tv = R.algorithm1(fm.X, lab.labels)
    tl = R.algorithm1(fm.X, lab.labels, splitter="loop")
    assert tree_signature(tv) == tree_signature(tl)
    np.testing.assert_array_equal(tv.predict(fm.X), tl.predict(fm.X))
    assert tv.training_error(fm.X, lab.labels) == 0.0


def test_algorithm1_warm_start_matches_cold_fits():
    """The shared Presort + split cache must not change the sweep's
    outcome: every trial equals a from-scratch fit."""
    rng = np.random.default_rng(3)
    X = rng.integers(0, 2, size=(150, 12)).astype(float)
    y = (X[:, 0] + 2 * X[:, 1] * X[:, 2] + X[:, 3]).astype(int)
    warm = R.algorithm1(X, y)
    k = warm.max_leaf_nodes
    cold = R.DecisionTree(max_leaf_nodes=k, max_depth=k - 1).fit(X, y)
    assert tree_signature(warm) == tree_signature(cold)


def test_split_cache_rejects_nothing_but_matches():
    """Explicit split_cache sharing across equal-data fits is exact."""
    rng = np.random.default_rng(4)
    X = rng.random((80, 6))
    y = rng.integers(0, 3, size=80)
    ps = R.Presort(X)
    cache: dict = {}
    a = R.DecisionTree(6).fit(X, y, presort=ps, split_cache=cache)
    assert cache  # populated
    b = R.DecisionTree(6).fit(X, y, presort=ps, split_cache=cache)
    assert tree_signature(a) == tree_signature(b)


def test_presort_shape_mismatch_raises():
    ps = R.Presort(np.zeros((10, 3)))
    with pytest.raises(ValueError, match="presort"):
        R.DecisionTree(2).fit(np.zeros((11, 3)), np.zeros(11),
                              presort=ps)


def test_batch_predict_equals_single_descent():
    rng = np.random.default_rng(5)
    X = rng.random((120, 7))
    y = rng.integers(0, 4, size=120)
    t = R.DecisionTree(10).fit(X, y)
    Xq = rng.random((50, 7))
    batch = t.predict(Xq)
    single = np.array([t.classes_[t._leaf(x).majority_class()]
                       for x in Xq])
    np.testing.assert_array_equal(batch, single)


# -- sklearn cross-check ------------------------------------------------------

def _sklearn_tree(k, seed=0):
    sktree = pytest.importorskip("sklearn.tree")
    return sktree.DecisionTreeClassifier(
        criterion="gini", class_weight="balanced", max_leaf_nodes=k,
        max_depth=k - 1, random_state=seed)


@pytest.mark.parametrize("seed,kind", [(0, 0), (1, 1), (2, 2), (3, 3)])
def test_dtree_matches_sklearn_training_error(seed, kind):
    """Same CART recipe (gini, balanced weights, best-first growth
    under max_leaf_nodes) -> same training error as sklearn."""
    rng = np.random.default_rng(seed)
    X, y = random_dataset(rng, kind)
    if len(np.unique(y)) < 2:
        y[0] = y[0] + 1
    for k in (2, 4, 8):
        ours = R.DecisionTree(k, max_depth=k - 1).fit(X, y)
        sk = _sklearn_tree(k).fit(X, y)
        ours_err = ours.training_error(X, y)
        sk_err = float(np.mean(sk.predict(X) != y))
        assert ours_err == pytest.approx(sk_err, abs=1e-12), k
        assert ours.n_leaves() == sk.get_n_leaves(), k


def test_algorithm1_matches_sklearn_on_spmv():
    """The paper pipeline's tree agrees with sklearn at the chosen
    hyperparameters on the exhaustive SpMV dataset."""
    pytest.importorskip("sklearn")
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))
    times = np.array([C.makespan(g, s) for s in scheds])
    lab = C.label_times(times)
    fm = C.featurize(g, scheds)
    ours = R.algorithm1(fm.X, lab.labels)
    k = ours.max_leaf_nodes
    sk = _sklearn_tree(k).fit(fm.X, lab.labels)
    assert ours.training_error(fm.X, lab.labels) == \
        pytest.approx(float(np.mean(sk.predict(fm.X) != lab.labels)),
                      abs=1e-12)


def test_regression_tree_matches_sklearn():
    sktree = pytest.importorskip("sklearn.tree")
    rng = np.random.default_rng(7)
    X = rng.random((200, 6))
    y = 2.0 * X[:, 0] + (X[:, 1] > 0.5) - X[:, 2] ** 2 \
        + 0.01 * rng.standard_normal(200)
    for k in (4, 8, 16):
        ours = R.RegressionTree(max_leaf_nodes=k).fit(X, y)
        sk = sktree.DecisionTreeRegressor(max_leaf_nodes=k,
                                          random_state=0).fit(X, y)
        ours_mse = float(np.mean((ours.predict(X) - y) ** 2))
        sk_mse = float(np.mean((sk.predict(X) - y) ** 2))
        assert ours_mse == pytest.approx(sk_mse, rel=1e-9), k


# -- regression tree ----------------------------------------------------------

def test_regression_tree_brute_force_first_split():
    """First split must maximize SSE reduction over every candidate."""
    rng = np.random.default_rng(11)
    X = rng.random((40, 4))
    y = rng.standard_normal(40)
    t = R.RegressionTree(max_leaf_nodes=2).fit(X, y)
    assert not t.root.is_leaf

    def sse(v):
        return float(((v - v.mean()) ** 2).sum()) if v.size else 0.0

    best = None
    for f in range(X.shape[1]):
        vals = np.unique(X[:, f])
        for j in range(len(vals) - 1):
            thr = (vals[j] + vals[j + 1]) / 2.0
            mask = X[:, f] <= thr
            gain = sse(y) - sse(y[mask]) - sse(y[~mask])
            if best is None or gain > best + 1e-12:
                best = gain
    got_mask = X[:, t.root.feature] <= t.root.threshold
    got_gain = sse(y) - sse(y[got_mask]) - sse(y[~got_mask])
    assert got_gain == pytest.approx(best, rel=1e-9)


def test_regression_tree_constant_target_is_leaf():
    X = np.random.default_rng(0).random((30, 3))
    t = R.RegressionTree(max_leaf_nodes=8).fit(X, np.ones(30))
    assert t.n_leaves() == 1
    np.testing.assert_allclose(t.predict(X), 1.0)


def test_regression_tree_respects_limits():
    rng = np.random.default_rng(2)
    X = rng.random((300, 5))
    y = rng.standard_normal(300)
    for k in (2, 5, 9):
        t = R.RegressionTree(max_leaf_nodes=k).fit(X, y)
        assert 1 <= t.n_leaves() <= k
    t = R.RegressionTree(max_leaf_nodes=64, max_depth=3).fit(X, y)
    assert t.depth() <= 3


# -- gradient-boosted surrogate ----------------------------------------------

def test_boosted_surrogate_fits_nonlinear_target():
    """Boosting must capture a feature interaction the linear ridge
    cannot (XOR-shaped makespan)."""
    import random as pyrandom

    import repro.search as S

    g = C.spmv_dag()
    rng = pyrandom.Random(0)
    train = [S.random_schedule(g, 2, rng) for _ in range(200)]
    held = [S.random_schedule(g, 2, rng) for _ in range(100)]
    fm = C.featurize(g, train + held)
    # synthetic nonlinear target over the real feature space
    t_all = (fm.X[:, 0] ^ fm.X[:, 1]).astype(float) \
        + 0.1 * fm.X[:, 2]

    boost = R.GradientBoostedSurrogate(g, n_estimators=100,
                                       refit_every=1)
    ridge = S.RidgeSurrogate(g, refit_every=1)
    for s, t in zip(train, t_all[:200]):
        boost.observe(s, float(t))
        ridge.observe(s, float(t))
    err_b = float(np.mean((boost.predict(held) - t_all[200:]) ** 2))
    err_r = float(np.mean((ridge.predict(held) - t_all[200:]) ** 2))
    assert err_b < err_r
    assert boost.n_trees > 0


def test_boosted_surrogate_degenerate_predicts_mean():
    import random as pyrandom

    import repro.search as S

    g = C.spmv_dag()
    sur = R.GradientBoostedSurrogate(g, refit_every=1)
    s = S.random_schedule(g, 2, pyrandom.Random(0))
    assert sur.predict([s]) == pytest.approx([0.0])  # no data: mean 0
    sur.observe(s, 3.0)
    sur.observe(s, 5.0)  # identical schedules: no features survive
    np.testing.assert_allclose(sur.predict([s]), [4.0])


def test_surrogate_registry_and_seam():
    import repro.search as S

    g = C.spmv_dag()
    assert set(S.SURROGATES) >= {"ridge", "boost"}
    guided = S.SurrogateGuided(g, 2, surrogate="boost",
                               surrogate_kwargs={"n_estimators": 10})
    assert isinstance(guided.surrogate, R.GradientBoostedSurrogate)
    assert guided.surrogate.n_estimators == 10
    # pre-built objects pass through
    pre = S.RidgeSurrogate(g)
    assert S.SurrogateGuided(g, 2, surrogate=pre).surrogate is pre
    with pytest.raises(ValueError, match="unknown surrogate"):
        S.make_surrogate(g, "nope")
    with pytest.raises(ValueError, match="surrogate_kwargs"):
        S.SurrogateGuided(g, 2, surrogate=pre,
                          surrogate_kwargs={"x": 1})
    # refit_every forwards to any named surrogate; l2 is ridge-only
    gb = S.SurrogateGuided(g, 2, surrogate="boost", refit_every=3)
    assert gb.surrogate.refit_every == 3
    gr = S.SurrogateGuided(g, 2, l2=0.5, refit_every=3)
    assert gr.surrogate.l2 == 0.5 and gr.surrogate.refit_every == 3
    with pytest.raises(ValueError, match="ridge"):
        S.SurrogateGuided(g, 2, surrogate="boost", l2=0.5)


def test_boost_guided_search_runs_end_to_end():
    import repro.search as S

    g = C.spmv_dag()
    strat = S.SurrogateGuided(g, 2, seed=0, warmup=16,
                              surrogate="boost",
                              surrogate_kwargs={"n_estimators": 20})
    res = S.run_search(g, strat, budget=60, batch_size=4)
    assert res.n_proposed == 60
    q = strat.screening_quality()
    assert q["n_screened"] > 0 and q["n_compared"] > 0


# -- re-exports ---------------------------------------------------------------

def test_core_reexports_the_rules_subsystem():
    """repro.core's one-stop names must be the rules-subsystem objects."""
    assert C.DecisionTree is R.DecisionTree
    assert C.algorithm1 is R.algorithm1
    assert C.label_times is R.label_times
    assert C.extract_rulesets is R.extract_rulesets
    assert C.class_range_accuracy is R.class_range_accuracy
