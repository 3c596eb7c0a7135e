"""Labels, features, decision tree, and rules — unit + property tests."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.rules.labels import (find_peaks, label_times, peak_prominences,
                                step_convolve)


# -- labels -------------------------------------------------------------------

def test_label_synthetic_steps():
    """Three well-separated performance plateaus -> three classes."""
    rng = np.random.default_rng(0)
    times = np.concatenate([
        1.00 + 0.01 * rng.random(400),
        1.50 + 0.01 * rng.random(300),
        2.00 + 0.01 * rng.random(300),
    ])
    rng.shuffle(times)
    lab = label_times(times)
    # The two 0.5-wide plateau jumps must be detected (the 98th-pct
    # prominence filter may keep an occasional extra small peak, which
    # the paper tolerates too — class count is not known a priori).
    assert 3 <= lab.n_classes <= 5
    assert any(abs(b - 399) <= 10 for b in lab.boundaries)
    assert any(abs(b - 699) <= 10 for b in lab.boundaries)
    # class ids nondecreasing along the sorted order
    pred = lab.labels[np.argsort(times, kind="stable")]
    assert (np.diff(pred) >= 0).all()


def test_label_single_class_flat_data():
    times = np.linspace(1.0, 1.001, 300)  # no structure
    lab = label_times(times)
    assert lab.n_classes <= 2  # nothing prominent to split on


def test_step_convolve_peak_at_jump():
    a = np.array([1.0] * 50 + [2.0] * 50)
    c = step_convolve(a, 5)
    assert np.argmax(c) in (49, 50)


def test_step_convolve_matches_explicit_kernel():
    """Lock the §IV-A kernel: -1 on [-r, 0] (r+1 taps), +1 on [1, r]
    (r taps) — cross-checked against an explicit correlation and a
    brute-force double sum (and scipy, when importable)."""
    rng = np.random.default_rng(5)
    a = np.sort(rng.random(100))
    for r in (1, 3, 7):
        ours = step_convolve(a, r)
        kernel = np.array([-1.0] * (r + 1) + [1.0] * r)
        ref = np.zeros_like(a)
        ref[r:a.size - r] = np.correlate(a, kernel, mode="valid")
        np.testing.assert_allclose(ours, ref, atol=1e-12)
        for i in range(r, a.size - r):
            want = a[i + 1:i + r + 1].sum() - a[i - r:i + 1].sum()
            assert abs(ours[i] - want) < 1e-12
        try:
            import scipy.signal as sps
        except ImportError:
            continue
        sref = np.zeros_like(a)
        sref[r:a.size - r] = sps.correlate(a, kernel, mode="valid")
        np.testing.assert_allclose(ours, sref, atol=1e-12)


def test_step_convolve_too_short_is_zero():
    # The kernel spans 2r+1 taps; anything shorter has no valid window.
    assert (step_convolve(np.arange(6, dtype=float), 3) == 0).all()


def test_find_peaks_matches_scipy():
    scipy_signal = pytest.importorskip("scipy.signal")
    rng = np.random.default_rng(3)
    x = rng.random(500)
    ours = find_peaks(x)
    ref, _ = scipy_signal.find_peaks(x)
    np.testing.assert_array_equal(ours, ref)
    ours_p = peak_prominences(x, ours)
    ref_p = scipy_signal.peak_prominences(x, ref)[0]
    np.testing.assert_allclose(ours_p, ref_p)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(0.1, 10.0), min_size=10, max_size=300))
def test_label_properties(times):
    lab = label_times(np.array(times))
    assert lab.labels.shape == (len(times),)
    assert lab.n_classes >= 1
    assert lab.labels.max() == lab.n_classes - 1
    # class ranges must tile the sorted data in order
    ranges = lab.class_ranges()
    for (lo1, hi1), (lo2, _hi2) in zip(ranges, ranges[1:]):
        assert lo1 <= hi1 <= lo2


# -- features -----------------------------------------------------------------

@pytest.fixture(scope="module")
def spmv_space():
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))
    return g, scheds


def test_feature_values_match_sequences(spmv_space):
    g, scheds = spmv_space
    fm = C.featurize(g, scheds)
    for i, s in enumerate(list(scheds)[:20]):
        names = C.expanded_names(g, s)
        pos = {n: j for j, n in enumerate(names)}
        streams = s.streams()
        for j, f in enumerate(fm.features):
            if f.kind == "order":
                if f.u in pos and f.v in pos:
                    assert fm.X[i, j] == (pos[f.u] < pos[f.v])
                else:
                    assert fm.X[i, j] == 0
            else:
                assert fm.X[i, j] == (streams.get(f.u) == streams.get(f.v))


def test_constant_features_dropped(spmv_space):
    g, scheds = spmv_space
    fm = C.featurize(g, scheds)
    for j in range(fm.X.shape[1]):
        assert fm.X[:, j].min() != fm.X[:, j].max()
    # DAG-implied orderings must be gone: Pack always before PostSend
    assert not any(f.kind == "order" and {f.u, f.v} == {"Pack", "PostSend"}
                   for f in fm.features)


def test_featurize_like_consistent_basis(spmv_space):
    g, scheds = spmv_space
    fm = C.featurize(g, scheds)
    X2 = C.featurize_like(g, scheds, fm)
    np.testing.assert_array_equal(fm.X, X2)


def test_featurize_degenerate_corpus_raises(spmv_space):
    """A corpus with <= 1 distinct schedule prunes every column; that
    must be a nameable error, not a 0-feature matrix handed to the
    tree fit."""
    g, scheds = spmv_space
    s = scheds[0]
    for corpus in ([], [s], [s, s, s]):
        with pytest.raises(C.DegenerateFeatureSpaceError,
                           match="distinct"):
            C.featurize(g, corpus)
    # the guard is a ValueError subclass, so legacy handlers still work
    assert issubclass(C.DegenerateFeatureSpaceError, ValueError)
    # two distinct schedules are the minimum viable corpus
    assert C.featurize(g, scheds[:2]).features


def test_feature_basis_incremental_equals_batch(spmv_space):
    """Absorbing the corpus in chunks must give the same basis/matrix
    as one featurize call over everything."""
    g, scheds = spmv_space
    basis = C.FeatureBasis(g)
    basis.add(scheds[:10]).add(scheds[10:75]).add(scheds[75:])
    inc = basis.matrix()
    ref = C.featurize(g, list(scheds))
    assert inc.features == ref.features
    np.testing.assert_array_equal(inc.X, ref.X)


# -- decision tree --------------------------------------------------------------

def test_dtree_fits_xor():
    X = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], dtype=float)
    y = np.array([0, 1, 1, 0])
    t = C.DecisionTree(max_leaf_nodes=4).fit(X, y)
    assert t.training_error(X, y) == 0.0
    np.testing.assert_array_equal(t.predict(X), y)


def test_dtree_max_leaves_respected():
    rng = np.random.default_rng(0)
    X = rng.integers(0, 2, size=(200, 8)).astype(float)
    y = rng.integers(0, 3, size=200)
    for k in (2, 3, 5, 8):
        t = C.DecisionTree(max_leaf_nodes=k).fit(X, y)
        assert t.n_leaves() <= k


def test_dtree_balanced_weights_protect_minority():
    # 95/5 imbalance, single separating feature: balanced weights must
    # split rather than predict the majority everywhere.
    X = np.array([[0.0]] * 95 + [[1.0]] * 5)
    y = np.array([0] * 95 + [1] * 5)
    t = C.DecisionTree(max_leaf_nodes=2).fit(X, y)
    assert t.predict(np.array([[1.0]]))[0] == 1


def test_algorithm1_reaches_zero_error():
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))
    times = np.array([C.makespan(g, s) for s in scheds])
    lab = C.label_times(times)
    fm = C.featurize(g, scheds)
    trace = C.TreeSearchTrace([], [], [])
    tree = C.algorithm1(fm.X, lab.labels, trace=trace)
    assert tree.training_error(fm.X, lab.labels) == 0.0
    # Alg. 1 invariant: max_depth == max_leaf_nodes - 1 each trial
    assert all(d <= m - 1 for m, d in
               zip(trace.max_leaf_nodes, trace.depths))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_dtree_separable_property(seed):
    """On data where the label is a function of the features, enough
    leaves always reach zero training error."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 2, size=(60, 5)).astype(float)
    y = (X[:, 0] + 2 * X[:, 1] * X[:, 2]).astype(int)
    t = C.DecisionTree(max_leaf_nodes=64).fit(X, y)
    assert t.training_error(X, y) == 0.0


# -- rules ---------------------------------------------------------------------

def test_rule_text_matches_paper_style(spmv_space):
    g, scheds = spmv_space
    times = np.array([C.makespan(g, s) for s in scheds])
    lab = C.label_times(times)
    fm = C.featurize(g, scheds)
    tree = C.algorithm1(fm.X, lab.labels)
    rulesets = C.extract_rulesets(tree, fm.features)
    texts = [r.text() for rs in rulesets for r in rs.rules]
    assert any("before" in t for t in texts)


def test_annotate_over_and_under_constrained():
    f1 = C.Feature("order", "a", "b")
    f2 = C.Feature("order", "b", "c")
    canon = [C.RuleSet([C.Rule(f1, 1)], class_label=0, n_samples=10,
                       pure=True)]
    over = C.RuleSet([C.Rule(f1, 1), C.Rule(f2, 0)], class_label=0,
                     n_samples=5, pure=True)
    under = C.RuleSet([C.Rule(f2, 0)], class_label=0, n_samples=5,
                      pure=True)
    C.annotate_vs_canonical([over, under], canon)
    assert not over.insufficient and len(over.extraneous) == 1
    assert under.insufficient
