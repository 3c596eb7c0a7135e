"""Out-of-core histogram-folded CART training (the PR-9 tentpole).

Locks the exactness contract end to end: a tree trained from folded
per-feature x per-class count histograms — one blockwise pass per tree
level, never a materialized (rows x features) matrix — must be
**bit-identical** (splits, thresholds, tie-breaks, ``predict``) to the
in-memory vectorized splitter, on the exhaustive 280-schedule SpMV
space, on 2000-schedule halo3d corpora, and through the full
Algorithm-1 sweep; plus the mergeability laws (associative/commutative
histogram ``merge`` == single-stream ``add``), the subtraction trick
(``right = parent - left`` equals a fresh scan), block-size invariance,
and the :class:`~repro.driver.HistogramSink` ``distill`` path against
:class:`~repro.driver.DatasetSink`.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as C
import repro.rules as R
import repro.search as S
from repro.core.dag import halo3d_dag
from repro.driver import (DatasetSink, HistogramSink, SearchDriver,
                          StreamingHistogram)
from repro.rules.trees import (ClassCountHistogram, HistogramGrower,
                               algorithm1_from_histograms,
                               fit_from_histograms)
from repro.space.params import demo_param_space


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


def _blocks(X, block):
    """Re-callable block stream over a materialized matrix."""
    return lambda: (X[i:i + block] for i in range(0, len(X), block))


def random_dataset(rng, kind):
    n = int(rng.integers(8, 120))
    d = int(rng.integers(1, 10))
    if kind == 0:                       # the paper's 0/1 features
        X = rng.integers(0, 2, size=(n, d)).astype(float)
    elif kind == 1:                     # small-cardinality ordinals
        X = rng.integers(0, 4, size=(n, d)).astype(float)
    elif kind == 2:                     # continuous
        X = np.round(rng.random((n, d)), 3)
    else:                               # mixed + constant columns
        X = np.concatenate(
            [rng.integers(0, 2, size=(n, d)).astype(float),
             np.round(rng.random((n, 2)), 3), np.ones((n, 1))], axis=1)
    y = rng.integers(0, int(rng.integers(2, 5)), size=n)
    return X, y


# -- acceptance pins: bit-identity on the paper's corpora ---------------------

def test_histogram_tree_identical_on_exhaustive_spmv():
    """Acceptance pin: the histogram path reproduces the in-memory
    Algorithm-1 sweep bit for bit on the exhaustive 280-schedule SpMV
    space — identical trial schedule, leaf counts, tree structure,
    predictions, and training error."""
    g = C.spmv_dag()
    scheds = list(C.enumerate_schedules(g, 2))
    assert len(scheds) == 280
    times = np.array([C.makespan(g, s) for s in scheds])
    lab = C.label_times(times)
    fm = C.featurize(g, scheds)
    ref_trace = R.TreeSearchTrace([], [], [])
    ref = R.algorithm1(fm.X, lab.labels, trace=ref_trace)
    ooc_trace = R.TreeSearchTrace([], [], [])
    ooc = algorithm1_from_histograms(_blocks(fm.X, 64), lab.labels,
                                     trace=ooc_trace)
    assert tree_signature(ref) == tree_signature(ooc)
    np.testing.assert_array_equal(ref.predict(fm.X), ooc.predict(fm.X))
    # the sweep itself is identical: same trials, same leaf counts
    assert ref_trace.max_leaf_nodes == ooc_trace.max_leaf_nodes
    assert ref_trace.errors == ooc_trace.errors
    assert ref.n_leaves() == ooc.n_leaves()


def test_histogram_tree_identical_on_halo3d_2000():
    """Acceptance pin: bit-identity on a 2000-schedule halo3d corpus
    (the bench-scale dataset), including max_depth-capped fits."""
    g = halo3d_dag()
    res = S.run_search(g, S.RandomSearch(g, seed=0), budget=2000,
                       batch_size=64, backend="vectorized")
    fm, lab, _ = res.dataset()
    ref = R.DecisionTree(max_leaf_nodes=12, max_depth=6).fit(
        np.asarray(fm.X, dtype=np.float64), lab.labels)
    ooc = fit_from_histograms(_blocks(fm.X, 257), lab.labels,
                              max_leaf_nodes=12, max_depth=6)
    assert tree_signature(ref) == tree_signature(ooc)
    Xf = np.asarray(fm.X, dtype=np.float64)
    np.testing.assert_array_equal(ref.predict(Xf), ooc.predict(Xf))
    # a grower is reusable across the whole Algorithm-1 sweep
    ref_trace = R.TreeSearchTrace([], [], [])
    full_ref = R.algorithm1(Xf, lab.labels, trace=ref_trace)
    ooc_trace = R.TreeSearchTrace([], [], [])
    full_ooc = algorithm1_from_histograms(_blocks(fm.X, 257),
                                          lab.labels, trace=ooc_trace)
    assert tree_signature(full_ref) == tree_signature(full_ooc)
    assert ref_trace.max_leaf_nodes == ooc_trace.max_leaf_nodes
    assert full_ref.n_leaves() == full_ooc.n_leaves()


# -- the sink: streamed corpus == in-memory corpus ----------------------------

def test_histogram_sink_distill_matches_dataset_sink():
    """One driver run feeding both sinks: the out-of-core ``distill``
    must reproduce the dense report — same pruned feature list, same
    tree, same rulesets, same training error — without ever holding
    the feature matrix."""
    g = halo3d_dag()
    ds, hs = DatasetSink(g), HistogramSink(g, block_rows=97)
    SearchDriver(g, S.RandomSearch(g, seed=0), budget=600,
                 batch_size=64, backend="vectorized",
                 sinks=[ds, hs]).run()
    assert hs.n_rows == len(ds.schedules)
    assert hs.times == ds.times
    assert hs.feature_list() == ds.matrix().features
    rd, rh = ds.distill(), hs.distill()
    assert tree_signature(rd.tree) == tree_signature(rh.tree)
    assert rd.training_error == rh.training_error
    assert rd.n_schedules == rh.n_schedules
    assert [(r.class_label, r.rules, r.n_samples, r.pure)
            for r in rd.rulesets] \
        == [(r.class_label, r.rules, r.n_samples, r.pure)
            for r in rh.rulesets]
    assert rd.trace.max_leaf_nodes == rh.trace.max_leaf_nodes
    assert rd.trace.errors == rh.trace.errors
    # the report renders identically (feature names line up too)
    assert rd.render() == rh.render()
    # and the out-of-core report never materialized a row
    assert rh.feature_matrix.X.shape == (0, len(rh.feature_matrix.features))


def test_histogram_sink_merge_equals_sequential_consume():
    """Sharded hosts: merging two sinks equals one sink that consumed
    both runs in sequence — rows, times, doubling histogram, and the
    distilled report all agree."""
    g = halo3d_dag()
    h1, h2 = HistogramSink(g), HistogramSink(g)
    SearchDriver(g, S.RandomSearch(g, seed=1), budget=300,
                 batch_size=64, backend="vectorized", sinks=[h1]).run()
    SearchDriver(g, S.RandomSearch(g, seed=2), budget=300,
                 batch_size=64, backend="vectorized", sinks=[h2]).run()
    both = HistogramSink(g)
    SearchDriver(g, S.RandomSearch(g, seed=1), budget=300,
                 batch_size=64, backend="vectorized", sinks=[both]).run()
    SearchDriver(g, S.RandomSearch(g, seed=2), budget=300,
                 batch_size=64, backend="vectorized", sinks=[both]).run()
    h1.merge(h2)
    assert h1.n_rows == both.n_rows
    assert h1.times == both.times
    assert h1.histogram.hi == both.histogram.hi
    np.testing.assert_array_equal(h1.histogram.counts,
                                  both.histogram.counts)
    ra, rb = h1.distill(), both.distill()
    assert tree_signature(ra.tree) == tree_signature(rb.tree)
    assert ra.training_error == rb.training_error
    with pytest.raises(TypeError):
        h1.merge(object())


def test_histogram_sink_on_param_space():
    """The out-of-core path is space-generic: a kernel parameter grid
    (threshold features, value-index encodings) distills identically
    through the histogram sink."""
    sp = demo_param_space()
    ds, hs = DatasetSink(sp), HistogramSink(sp, block_rows=7)
    SearchDriver(sp, S.ExhaustiveSearch(sp), budget=None,
                 batch_size=8, sinks=[ds, hs]).run()
    assert hs.n_rows == len(ds.schedules)
    rd, rh = ds.distill(), hs.distill()
    assert tree_signature(rd.tree) == tree_signature(rh.tree)
    assert rd.training_error == rh.training_error
    assert rd.render() == rh.render()


def test_decode_batch_roundtrips_canonical_encodings():
    """decode_batch(encode_batch(c)) returns the canonical candidate:
    identical cache key, for schedules and parameter grids, from both
    the (B, 2, N) form and the flattened key bytes."""
    from repro.space.base import as_space
    g = C.spmv_dag()
    sp = as_space(g)
    scheds = list(C.enumerate_schedules(g, 2))[:40]
    keys, enc = sp.encode_batch(scheds)
    back = sp.decode_batch(enc)
    keys2, _ = sp.encode_batch(back)
    assert keys == keys2
    flat = np.stack([np.frombuffer(k, dtype=np.int32) for k in keys])
    keys3, _ = sp.encode_batch(sp.decode_batch(flat))
    assert keys == keys3

    demo = demo_param_space()
    cands = list(demo.enumerate_candidates())
    dkeys, denc = demo.encode_batch(cands)
    assert demo.decode_batch(denc) == cands
    with pytest.raises(ValueError, match="out of range"):
        demo.decode_batch(np.full((1, len(demo.dims)), 99,
                                  dtype=np.int32))


def test_distill_histograms_validates_row_count():
    g = halo3d_dag()
    hs = HistogramSink(g)
    SearchDriver(g, S.RandomSearch(g, seed=0), budget=64,
                 batch_size=16, backend="vectorized",
                 sinks=[hs]).run()
    hs.times = hs.times[:-1]            # corrupt the corpus
    with pytest.raises(ValueError, match="rows"):
        R.distill(hs, histograms=hs)


# -- satellite (3a): merge is associative/commutative == single stream --------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.lists(st.floats(min_value=0.0, max_value=1e6),
                         min_size=0, max_size=30),
                min_size=2, max_size=6),
       st.integers(min_value=1, max_value=32))
def test_streaming_histogram_merge_property(batches, half_bins):
    """merge() == single-stream add, associative and commutative, even
    when the shards' ranges differ by several doublings."""
    single = StreamingHistogram(half_bins=half_bins)
    shards = []
    for batch in batches:
        v = np.asarray(batch, dtype=np.float64)
        single.add(v)
        h = StreamingHistogram(half_bins=half_bins)
        h.add(v)
        shards.append(h)

    def fold(hs):
        acc = StreamingHistogram(half_bins=half_bins)
        for h in hs:
            acc.merge(h)
        return acc

    left = fold(shards)
    right = fold(list(reversed(shards)))           # commutativity
    # associativity: merge a pre-merged pair into the rest
    pair = fold(shards[:2])
    nested = fold([pair] + shards[2:])
    for h in (left, right, nested):
        assert h.hi == single.hi
        np.testing.assert_array_equal(h.counts, single.counts)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_class_count_histogram_merge_property(seed):
    """ClassCountHistogram.merge == single-stream add, in any order,
    including shards whose value grids differ."""
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 6))
    K = int(rng.integers(2, 5))
    grids = [np.unique(np.round(rng.random(int(rng.integers(1, 5))), 2))
             for _ in range(d)]
    n_shards = int(rng.integers(2, 5))
    shards, single = [], ClassCountHistogram(grids, K)
    for _ in range(n_shards):
        m = int(rng.integers(0, 40))
        X = np.stack([g[rng.integers(0, g.size, m)] for g in grids],
                     axis=1) if m else np.zeros((0, d))
        y = rng.integers(0, K, m).astype(np.int32)
        single.add(X, y)
        # each shard only declares the values it actually saw (plus one
        # guaranteed bin), so shard grids genuinely differ
        sh_grids = [np.unique(X[:, j]) if m else grids[j][:1]
                    for j in range(d)]
        sh = ClassCountHistogram(sh_grids, K)
        sh.add(X, y)
        shards.append(sh)
    acc = shards[0]
    for sh in shards[1:]:
        acc = acc.merge(sh)
    rev = shards[-1]
    for sh in reversed(shards[:-1]):
        rev = rev.merge(sh)
    for merged in (acc, rev):
        # project the merged counts onto the full grids for comparison
        onto = ClassCountHistogram(grids, K).merge(merged)
        np.testing.assert_array_equal(onto.counts, single.counts)
    assert single.n == sum(sh.n for sh in shards)


# -- satellite (3b): subtraction == fresh scan --------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([0, 1, 2, 3]))
def test_histogram_subtraction_equals_fresh_scan(seed, kind):
    """Every frontier histogram the grower holds — half of which were
    produced purely by ``parent - left`` subtraction — equals a fresh
    scan over the rows that actually reach that node."""
    rng = np.random.default_rng(seed)
    X, y = random_dataset(rng, kind)
    grower = HistogramGrower(_blocks(X, 13), y)
    tree = grower.fit(max_leaf_nodes=8)     # expands several levels

    def path_mask(nd, target, mask):
        if nd is target:
            return mask
        if nd.left is None:
            return None
        col = X[:, nd.feature] <= nd.threshold
        got = path_mask(nd.left, target, mask & col)
        if got is None:
            got = path_mask(nd.right, target, mask & ~col)
        return got

    for nd in grower._frontier:
        if nd.hist is None:
            continue
        mask = path_mask(grower.root, nd,
                         np.ones(len(X), dtype=bool))
        fresh = ClassCountHistogram(grower.values, grower.n_classes)
        fresh.add(X[mask], grower.y_enc[mask])
        np.testing.assert_array_equal(nd.hist.counts, fresh.counts)
        np.testing.assert_array_equal(nd.counts, fresh.class_counts())
    # the structure itself must match the in-memory reference
    ref = R.DecisionTree(max_leaf_nodes=8).fit(
        np.asarray(X, dtype=np.float64), y)
    assert tree_signature(ref) == tree_signature(tree)
    # subtract() refuses non-sub-histograms
    empty = ClassCountHistogram(grower.values, grower.n_classes)
    one = ClassCountHistogram(grower.values, grower.n_classes)
    one.add(X[:1], grower.y_enc[:1])
    with pytest.raises(ValueError, match="sub-histogram"):
        empty.subtract(one)


# -- satellite (3c): fit is invariant to block size ---------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([0, 1, 2, 3]),
       st.integers(min_value=1, max_value=200))
def test_histogram_fit_invariant_to_block_size(seed, kind, block):
    """1 row per block, the whole corpus in one block, or anything in
    between: identical trees, all equal to the in-memory splitter."""
    rng = np.random.default_rng(seed)
    X, y = random_dataset(rng, kind)
    mln = int(rng.integers(2, 10))
    ref = R.DecisionTree(max_leaf_nodes=mln).fit(
        np.asarray(X, dtype=np.float64), y)
    want = tree_signature(ref)
    for b in {1, block, len(X)}:
        ooc = fit_from_histograms(_blocks(X, b), y, max_leaf_nodes=mln)
        assert tree_signature(ooc) == want, b
        assert ooc.training_error(np.asarray(X, dtype=np.float64), y) \
            == ref.training_error(np.asarray(X, dtype=np.float64), y)
