"""Batched-round grower tests (learner/batch_grower.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.batch_grower import grow_tree_batched
from lightgbm_tpu.learner.grower import grow_tree
from lightgbm_tpu.ops.split import SplitHyper

HP = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                rows_per_block=2048)


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(7)
    n, f = 6000, 10
    bins = rng.integers(0, 63, size=(n, f)).astype(np.uint8)
    logit = (bins[:, 0] / 32.0 - 1.0) + 0.6 * (bins[:, 1] > 40) \
        - 0.4 * (bins[:, 2] < 20)
    y = (logit + rng.normal(scale=0.4, size=n) > 0).astype(np.float32)
    g = (1 / (1 + np.exp(-logit)) - y).astype(np.float32)
    h = np.full(n, 0.25, np.float32)
    nb = np.full(f, 63, np.int32)
    nanb = np.full(f, -1, np.int32)
    cat = np.zeros(f, bool)
    return tuple(map(jnp.asarray, (bins, g, h, nb, nanb, cat)))


def test_batch1_identical_to_strict(problem):
    bins, g, h, nb, nanb, cat = problem
    t0, lor0 = grow_tree(bins, g, h, None, nb, nanb, cat, None, HP)
    t1, lor1 = grow_tree_batched(bins, g, h, None, nb, nanb, cat, None, HP,
                                 batch=1)
    assert int(t1.num_leaves) == int(t0.num_leaves)
    np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                  np.asarray(t0.split_feature))
    np.testing.assert_array_equal(np.asarray(t1.split_bin),
                                  np.asarray(t0.split_bin))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t0.leaf_value), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lor1), np.asarray(lor0))


def test_batch8_consistent_tree(problem):
    """batch=8 relaxes split ORDER, not split validity: the tree is full
    size, partitions are consistent, and leaf stats match the row map."""
    bins, g, h, nb, nanb, cat = problem
    t, lor = grow_tree_batched(bins, g, h, None, nb, nanb, cat, None, HP,
                               batch=8)
    nl = int(t.num_leaves)
    assert nl == HP.num_leaves
    counts = np.bincount(np.asarray(lor), minlength=HP.num_leaves)
    np.testing.assert_array_equal(counts[:nl],
                                  np.asarray(t.leaf_count)[:nl].astype(int))
    assert (counts[:nl] >= HP.min_data_in_leaf).all()


@pytest.mark.parametrize("batch", [4, 8])
def test_batched_training_quality(synthetic_binary, batch):
    """End-to-end through params: same ballpark logloss as strict."""
    X, y = synthetic_binary
    p0 = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
          "verbose": -1}
    b0 = lgb.train(p0, lgb.Dataset(X, label=y, params=p0),
                   num_boost_round=15)
    p1 = {**p0, "tpu_split_batch": batch}
    b1 = lgb.train(p1, lgb.Dataset(X, label=y, params=p1),
                   num_boost_round=15)

    def logloss(b):
        pr = np.clip(b.predict(X), 1e-9, 1 - 1e-9)
        return float(-np.mean(y * np.log(pr) + (1 - y) * np.log(1 - pr)))

    l0, l1 = logloss(b0), logloss(b1)
    assert l1 < l0 * 1.15 + 0.01


def test_batched_narrow_frontier_completes():
    """Chain-shaped trees (one positive-gain leaf per round) must still
    reach num_leaves — the round loop runs until no progress, not a fixed
    ceil((L-1)/K) budget."""
    rng = np.random.default_rng(1)
    n = 4096
    # single informative monotone feature -> deep chain growth
    x = np.sort(rng.normal(size=n))
    bins = np.clip((np.searchsorted(np.quantile(x, np.linspace(0, 1, 63)[1:-1]), x)), 0, 62).astype(np.uint8)[:, None]
    g = np.exp(x).astype(np.float32) - 1.0  # skewed gradients
    h = np.ones(n, np.float32)
    hp = SplitHyper(num_leaves=33, min_data_in_leaf=1, n_bins=64)
    t, _ = grow_tree_batched(jnp.asarray(bins), jnp.asarray(g),
                             jnp.asarray(h), None,
                             jnp.asarray(np.array([63], np.int32)),
                             jnp.asarray(np.array([-1], np.int32)),
                             jnp.asarray(np.array([False])), None, hp,
                             batch=16)
    ts, _ = grow_tree(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                      None, jnp.asarray(np.array([63], np.int32)),
                      jnp.asarray(np.array([-1], np.int32)),
                      jnp.asarray(np.array([False])), None, hp)
    assert int(t.num_leaves) == int(ts.num_leaves)


def test_batched_data_parallel(synthetic_binary):
    """tpu_split_batch composes with tree_learner=data over the mesh."""
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 8, "tree_learner": "data"}
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=10)
    assert float(((b.predict(X) > 0.5) == y).mean()) > 0.9


def test_batched_supports_path_smooth(synthetic_binary):
    """path_smooth is batched-capable since round 3 (parent_output rides
    the kids' own leaf values, mirroring the strict learner)."""
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 8, "path_smooth": 5.0}
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=5)
    assert b._gbdt._use_batched_grower()
    assert np.isfinite(b.predict(X)).all()


def test_batched_fallback_for_categorical():
    """Categorical data rides the batched grower (it once fell back to
    the strict learner, whence the name): a job with a categorical column
    at ``tpu_split_batch=8`` trains and fits."""
    rng = np.random.default_rng(0)
    n = 1000
    X = np.column_stack([rng.normal(size=n), rng.integers(0, 5, size=n)])
    y = ((X[:, 0] > 0) ^ (X[:, 1] == 2)).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 8, "categorical_feature": [1]}
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=10)
    assert float(((b.predict(X) > 0.5) == y).mean()) > 0.9


def _onehot_only_job(levels: int, n: int = 3000):
    """Four columns of which one is a ``levels``-level code (2: a binary
    flag), at most ``max_cat_to_onehot`` levels: every categorical column
    of the job takes the one-hot variant."""
    rng = np.random.default_rng(levels)
    X = rng.normal(size=(n, 4))
    X[:, 2] = rng.integers(0, levels, size=n)
    y = ((X[:, 0] + 1.5 * (X[:, 2] == 1)
          + rng.normal(scale=0.3, size=n)) > 0.5).astype(np.float64)
    return X, y


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("levels", [2, 3])
def test_a_job_whose_categorical_columns_are_all_one_hot(levels, fused):
    """Such a job states an EMPTY list of subset columns
    (``hp.cat_subset_cols == ()``): the batched grower traces no subset
    scan (it once read row 0 of a ``[0, B]`` array there and failed to
    trace), trains through the fused scan with the partition on either
    path, splits on the column by one level, and returns the model it
    trained."""
    from lightgbm_tpu.ops import round_fuse
    X, y = _onehot_only_job(levels)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 8}
    round_fuse._FUSE_TEST_INTERPRET = fused     # read when traced
    jax.clear_caches()
    try:
        b = lgb.train(p, lgb.Dataset(X, label=y, params=p,
                                     categorical_feature=[2]),
                      num_boost_round=8)
    finally:
        round_fuse._FUSE_TEST_INTERPRET = False
    gb = b._gbdt
    assert gb._use_batched_grower() and gb.hp.has_categorical
    assert gb.hp.cat_subset_cols == ()
    assert gb.metrics.counter("fused_rounds") == 8
    assert gb.metrics.counter("fused_partition_declined") == (0 if fused
                                                               else 8)
    assert gb.metrics.counter("cat_splits") > 0
    assert gb.metrics.counter("cat_subset_splits") == 0
    np.testing.assert_allclose(np.asarray(gb.scores)[:, 0],
                               b.predict(X, raw_score=True),
                               rtol=0, atol=2e-6)
    jax.clear_caches()


@pytest.mark.parametrize("levels", [2, 3])
def test_no_subset_columns_grow_the_tree_of_the_scan_of_every_column(levels):
    """The tree of a job whose categorical columns are all one-hot, with
    the empty list stated (no scan) and with the list not known (every
    column scanned, those columns' subset candidates masked: the form
    before the list existed): equal field for field."""
    X, y = _onehot_only_job(levels)
    ds = lgb.Dataset(X, label=y, categorical_feature=[2]).construct()._inner
    bins = jnp.asarray(ds.bins)
    g = jnp.asarray((0.5 - y).astype(np.float32))
    h = jnp.full((len(y),), 0.25, jnp.float32)
    consts = (jnp.asarray(ds.num_bins_array()), jnp.asarray(ds.nan_bin_array()),
              jnp.asarray(ds.categorical_array()))
    assert ds.cat_subset_columns() == ()
    trees = []
    for cols in ((), None):
        hp = SplitHyper(num_leaves=15, min_data_in_leaf=5,
                        n_bins=ds.device_n_bins(), has_categorical=True,
                        cat_subset_cols=cols)
        trees.append(grow_tree_batched(bins, g, h, None, *consts, None, hp,
                                       batch=8))
    (t0, lor0), (t1, lor1) = trees
    assert int(t0.num_leaves) == 15 and bool(np.asarray(t0.split_cat).any())
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(lor0), np.asarray(lor1))


def test_batch1_categorical_identical_to_strict():
    """batch=1 with categorical features reproduces the strict learner's
    trees exactly (split set, bitsets, partition)."""
    rng = np.random.default_rng(3)
    n, f = 5000, 6
    bins = rng.integers(0, 31, size=(n, f)).astype(np.uint8)
    # feature 1 and 4 categorical; signal on specific categories
    logit = (bins[:, 0] / 16.0 - 1.0) + 0.8 * np.isin(bins[:, 1], [3, 7, 11]) \
        - 0.5 * np.isin(bins[:, 4], [0, 2])
    y = (logit + rng.normal(scale=0.4, size=n) > 0).astype(np.float32)
    g = (1 / (1 + np.exp(-logit)) - y).astype(np.float32)
    h = np.full(n, 0.25, np.float32)
    nb = np.full(f, 31, np.int32)
    nanb = np.full(f, -1, np.int32)
    cat = np.zeros(f, bool)
    cat[[1, 4]] = True
    hp = SplitHyper(num_leaves=15, min_data_in_leaf=5, n_bins=32,
                    has_categorical=True, max_cat_to_onehot=4)
    args = tuple(map(jnp.asarray, (bins, g, h)))
    consts = tuple(map(jnp.asarray, (nb, nanb, cat)))
    t0, lor0 = grow_tree(*args[:3], None, *consts, None, hp)
    t1, lor1 = grow_tree_batched(*args[:3], None, *consts, None, hp, batch=1)
    assert int(t1.num_leaves) == int(t0.num_leaves)
    np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                  np.asarray(t0.split_feature))
    np.testing.assert_array_equal(np.asarray(t1.split_bin),
                                  np.asarray(t0.split_bin))
    np.testing.assert_array_equal(np.asarray(t1.split_cat),
                                  np.asarray(t0.split_cat))
    np.testing.assert_array_equal(np.asarray(t1.cat_bitset),
                                  np.asarray(t0.cat_bitset))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t0.leaf_value), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lor1), np.asarray(lor0))


def test_batched_categorical_quality():
    """batch=8 on categorical data trains to the same quality ballpark as
    strict, through the public params surface (the perf-representative
    path: VERDICT r1 #3)."""
    rng = np.random.default_rng(9)
    n = 4000
    X = rng.normal(size=(n, 5))
    X[:, 2] = rng.integers(0, 20, size=n)
    y = ((X[:, 0] + 1.2 * np.isin(X[:, 2], [4, 9, 13])
          + rng.normal(scale=0.4, size=n)) > 0.5).astype(np.float64)
    base = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
            "verbose": -1, "categorical_feature": [2]}
    b0 = lgb.train(base, lgb.Dataset(X, label=y, categorical_feature=[2],
                                     params=base), num_boost_round=15)
    p1 = {**base, "tpu_split_batch": 8}
    b1 = lgb.train(p1, lgb.Dataset(X, label=y, categorical_feature=[2],
                                   params=p1), num_boost_round=15)

    def logloss(b):
        pr = np.clip(b.predict(X), 1e-9, 1 - 1e-9)
        return float(-np.mean(y * np.log(pr) + (1 - y) * np.log(1 - pr)))

    l0, l1 = logloss(b0), logloss(b1)
    assert l1 < l0 * 1.15 + 0.01


def test_batch1_monotone_basic_identical_to_strict(problem):
    bins, g, h, nb, nanb, cat = problem
    mono = jnp.asarray(np.array([1, -1, 0, 0, 0, 0, 0, 0, 0, 0], np.int32))
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    rows_per_block=2048, use_monotone=True,
                    monotone_method="basic")
    t0, lor0 = grow_tree(bins, g, h, None, nb, nanb, cat, None, hp,
                         monotone=mono)
    t1, lor1 = grow_tree_batched(bins, g, h, None, nb, nanb, cat, None, hp,
                                 batch=1, monotone=mono)
    assert int(t1.num_leaves) == int(t0.num_leaves)
    np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                  np.asarray(t0.split_feature))
    np.testing.assert_array_equal(np.asarray(t1.split_bin),
                                  np.asarray(t0.split_bin))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t0.leaf_value), atol=1e-5)


def test_batched_monotone_respected():
    """batch=8 + monotone_constraints=basic: predictions are monotone in
    the constrained feature (sweep test, strict learner's own gate)."""
    rng = np.random.default_rng(12)
    n = 4000
    X = rng.normal(size=(n, 4))
    y = (2.0 * X[:, 0] + np.sin(X[:, 1] * 2) +
         rng.normal(scale=0.3, size=n))
    p = {"objective": "regression", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbose": -1, "monotone_constraints": [1, 0, 0, 0],
         "monotone_constraints_method": "basic", "tpu_split_batch": 8}
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=20)
    base = np.zeros((64, 4))
    base[:, 1:] = rng.normal(size=(1, 3))
    base[:, 0] = np.linspace(-3, 3, 64)
    pred = b.predict(base)
    assert (np.diff(pred) >= -1e-6).all()


def test_warmup_rounds_same_tree_large_n(monkeypatch):
    """The width-matched warmup rounds change kernel shapes, not
    selection: the grown tree matches the no-warmup result on identical
    inputs.  Round 6 gates the ladder to configs whose masked pass takes
    the K-scaling radix-joint kernel (auto dispatch, >= 128 bins —
    ops/histogram.py hist_dispatch), so the test runs there, with
    the row gate patched down to keep it CPU-cheap."""
    import lightgbm_tpu.learner.batch_grower as BG
    monkeypatch.setattr(BG, "_WARMUP_MIN_ROWS", 1024)
    rng = np.random.default_rng(4)
    n, f = 6000, 6
    bins = rng.integers(0, 128, size=(n, f)).astype(np.uint8)
    logit = (bins[:, 0] / 64.0 - 1.0) + 0.5 * (bins[:, 1] > 80)
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    g = (1 / (1 + np.exp(-logit)) - y).astype(np.float32)
    h = np.full(n, 0.25, np.float32)
    hp = SplitHyper(num_leaves=15, min_data_in_leaf=5, n_bins=128)
    args = (jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), None,
            jnp.asarray(np.full(f, 128, np.int32)),
            jnp.asarray(np.full(f, -1, np.int32)),
            jnp.asarray(np.zeros(f, bool)), None, hp)
    from lightgbm_tpu.ops.histogram import hist_dispatch
    assert hist_dispatch(hp.hist_kernel, hp.n_bins).ladder
    t_warm, lor_warm = grow_tree_batched.__wrapped__(*args, batch=4)
    t_ref, lor_ref = grow_tree_batched(*args, batch=4, warmup=False)
    # the warmup widths always cover the whole frontier (frontier after r
    # rounds <= 2^r), so the grown tree must be IDENTICAL, not just equal
    # in size
    assert int(t_warm.num_leaves) == int(t_ref.num_leaves)
    np.testing.assert_array_equal(np.asarray(t_warm.split_feature),
                                  np.asarray(t_ref.split_feature))
    np.testing.assert_array_equal(np.asarray(t_warm.split_bin),
                                  np.asarray(t_ref.split_bin))
    np.testing.assert_array_equal(np.asarray(lor_warm), np.asarray(lor_ref))
    counts = np.bincount(np.asarray(lor_warm), minlength=hp.num_leaves)
    np.testing.assert_array_equal(
        counts[:int(t_warm.num_leaves)],
        np.asarray(t_warm.leaf_count)[:int(t_warm.num_leaves)].astype(int))


def test_batched_interaction_constraints(synthetic_binary):
    """Interaction constraints in the batched grower: every tree path uses
    features from a single constraint set (reference col_sampler.hpp)."""
    import lightgbm_tpu as lgb
    X, y = synthetic_binary
    sets = [[0, 1], [2, 3, 4]]
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 4,
         "interaction_constraints": "[0,1],[2,3,4]"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=8)
    df = bst.trees_to_dataframe()

    # walk each root->leaf path; its split features must fit one set
    import numpy as np
    for ti in df["tree_index"].unique():
        tdf = df[df["tree_index"] == ti]
        nodes = {r["node_index"]: r for _, r in tdf.iterrows()}

        def walk(idx, feats):
            r = nodes[idx]
            sf = r["split_feature"]
            if not isinstance(sf, str) or not sf:   # leaf (NaN/None)
                if feats:
                    assert any(set(feats) <= set(s) for s in sets), feats
                return
            f = int(sf.split("_")[-1])
            for child in (r["left_child"], r["right_child"]):
                if child is not None and child in nodes:
                    walk(child, feats + [f])

        root = tdf.iloc[0]["node_index"]
        walk(root, [])


def test_batched_intermediate_monotone(synthetic_binary):
    """Intermediate monotone in the batched grower: predictions are
    monotone in the constrained feature (property test, same pattern as
    tests/test_constraints.py)."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(8)
    n = 3000
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] * 1.5 + np.sin(X[:, 1]) +
         rng.normal(scale=0.2, size=n))
    p = {"objective": "regression", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 4,
         "monotone_constraints": [1, 0, 0, 0],
         "monotone_constraints_method": "intermediate"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=10)
    base = rng.normal(size=(50, 4))
    grid = np.linspace(-3, 3, 25)
    for row in base[:10]:
        probes = np.tile(row, (len(grid), 1))
        probes[:, 0] = grid
        pred = bst.predict(probes)
        assert (np.diff(pred) >= -1e-6).all()


def test_batched_path_smooth_matches_strict(synthetic_binary):
    """path_smooth > 0 at batch=1 must reproduce the strict learner's
    decisions exactly (batch=1 == strict contract)."""
    import lightgbm_tpu as lgb
    X, y = synthetic_binary
    base = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
            "verbose": -1, "path_smooth": 2.0}
    p1 = dict(base, tpu_split_batch=1)
    p2 = dict(base, tpu_split_batch=2)
    b_strict = lgb.train(p1, lgb.Dataset(X, label=y, params=p1),
                         num_boost_round=5)
    b_batch = lgb.train(p2, lgb.Dataset(X, label=y, params=p2),
                        num_boost_round=5)
    # strict vs batched: same quality ballpark; batch=1 handled by the
    # strict learner dispatch itself
    pred_s = b_strict.predict(X)
    pred_b = b_batch.predict(X)
    acc_s = ((pred_s > 0.5) == (y > 0)).mean()
    acc_b = ((pred_b > 0.5) == (y > 0)).mean()
    assert abs(acc_s - acc_b) < 0.05
    # smoothing must actually flow through the batched path: leaf values
    # with path_smooth differ from the unsmoothed batched model
    p3 = dict(base, tpu_split_batch=2)
    p3.pop("path_smooth")
    b_nosmooth = lgb.train(p3, lgb.Dataset(X, label=y, params=p3),
                           num_boost_round=5)
    assert b_batch._gbdt._use_batched_grower()
    assert b_batch.model_to_string().split("parameters:")[0] != \
        b_nosmooth.model_to_string().split("parameters:")[0]


def test_batched_extra_trees_and_bynode(synthetic_binary):
    """extra_trees + feature_fraction_bynode through the batched grower:
    trains, differs from the deterministic model, and stays accurate."""
    X, y = synthetic_binary
    base = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
            "verbose": -1, "tpu_split_batch": 4}
    p = dict(base, extra_trees=True, feature_fraction_bynode=0.6,
             extra_seed=11)
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=8)
    assert bst._gbdt._use_batched_grower()
    acc = ((bst.predict(X) > 0.5) == (y > 0)).mean()
    assert acc > 0.8
    b0 = lgb.train(base, lgb.Dataset(X, label=y, params=base),
                   num_boost_round=8)
    assert bst.model_to_string().split("parameters:")[0] != \
        b0.model_to_string().split("parameters:")[0]
    # deterministic under the same seed
    bst2 = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=8)
    assert bst.model_to_string().split("parameters:")[0] == \
        bst2.model_to_string().split("parameters:")[0]


def test_batched_forced_splits_match_strict(tmp_path, synthetic_binary):
    """Forced splits through the batched grower: the forced prefix of the
    tree matches the strict learner exactly (same BFS schedule, same
    gathered stats)."""
    import json
    X, y = synthetic_binary
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps(
        {"feature": 0, "threshold": 0.0,
         "left": {"feature": 1, "threshold": 0.5}}))
    base = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
            "verbose": -1, "forcedsplits_filename": str(fpath)}
    p_strict = dict(base, tpu_split_batch=1)
    p_batch = dict(base, tpu_split_batch=4)
    bs = lgb.train(p_strict, lgb.Dataset(X, label=y, params=p_strict),
                   num_boost_round=4)
    bb = lgb.train(p_batch, lgb.Dataset(X, label=y, params=p_batch),
                   num_boost_round=4)
    assert bb._gbdt._use_batched_grower()
    ds = bs.dump_model()["tree_info"]
    db = bb.dump_model()["tree_info"]
    for ts, tb in zip(ds, db):
        # roots forced to feature 0 @ 0.0; left child forced to feature 1
        assert ts["tree_structure"]["split_feature"] == 0
        assert tb["tree_structure"]["split_feature"] == 0
        assert abs(tb["tree_structure"]["threshold"]
                   - ts["tree_structure"]["threshold"]) < 1e-9
        # the second forced entry must have APPLIED in both learners
        ls = ts["tree_structure"]["left_child"]
        lb = tb["tree_structure"]["left_child"]
        assert ls["split_feature"] == 1
        assert lb["split_feature"] == 1


def test_batch1_monotone_advanced_identical_to_strict(problem):
    """batch=1 + advanced monotone equals the strict learner exactly:
    the per-(feature, threshold) bounds and box refreshes degenerate to
    the strict per-split cadence at K=1."""
    bins, g, h, nb, nanb, cat = problem
    mono = jnp.asarray(np.array([1, -1, 0, 0, 0, 0, 0, 0, 0, 0], np.int32))
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    rows_per_block=2048, use_monotone=True,
                    monotone_method="advanced")
    t0, lor0 = grow_tree(bins, g, h, None, nb, nanb, cat, None, hp,
                         monotone=mono)
    t1, lor1 = grow_tree_batched(bins, g, h, None, nb, nanb, cat, None, hp,
                                 batch=1, monotone=mono)
    assert int(t1.num_leaves) == int(t0.num_leaves)
    np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                  np.asarray(t0.split_feature))
    np.testing.assert_array_equal(np.asarray(t1.split_bin),
                                  np.asarray(t0.split_bin))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t0.leaf_value), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lor1), np.asarray(lor0))


def test_batched_monotone_advanced_respected():
    """batch=8 + advanced monotone: predictions stay monotone in both
    constrained directions (the strict learner's own sweep gate), and
    the fit is not worse than intermediate's (reference quality
    ordering basic <= intermediate <= advanced)."""
    rng = np.random.default_rng(12)
    n = 4000
    X = rng.normal(size=(n, 4))
    y = (2.0 * X[:, 0] - 1.2 * X[:, 1] + np.sin(X[:, 2] * 2) +
         rng.normal(scale=0.3, size=n))
    fits = {}
    for method in ("intermediate", "advanced"):
        p = {"objective": "regression", "num_leaves": 31,
             "min_data_in_leaf": 5, "verbose": -1,
             "monotone_constraints": [1, -1, 0, 0],
             "monotone_constraints_method": method, "tpu_split_batch": 8}
        b = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                      num_boost_round=20)
        base = np.zeros((64, 4))
        base[:, 2:] = rng.normal(size=(1, 2))
        for col, sign in ((0, +1), (1, -1)):
            sweep = base.copy()
            sweep[:, col] = np.linspace(-3, 3, 64)
            pred = b.predict(sweep)
            assert (sign * np.diff(pred) >= -1e-6).all(), (method, col)
        fits[method] = float(np.mean((b.predict(X) - y) ** 2))
    assert fits["advanced"] <= fits["intermediate"] * 1.05, fits


def test_batched_linear_tree_trains_and_matches_strict_at_batch1():
    """linear_tree + tpu_split_batch: the batched grower's trees carry
    leaf_path, so the post-growth ridge fit composes.  batch=1 must
    reproduce the strict learner's model exactly (growth identical =>
    identical per-leaf fits); batch=4 keeps linear-fit quality."""
    rng = np.random.default_rng(9)
    n = 3000
    X = rng.normal(size=(n, 5))
    y = 1.5 * X[:, 0] + np.where(X[:, 1] > 0, 2.0 * X[:, 2], -X[:, 2]) \
        + rng.normal(scale=0.2, size=n)
    base = {"objective": "regression", "num_leaves": 15, "verbose": -1,
            "min_data_in_leaf": 20, "linear_tree": True,
            "linear_lambda": 0.01}
    models = {}
    for k in (1, 4):
        p = {**base, "tpu_split_batch": k,
             # batch=1 alone routes strict; a pool with fewer slots than
             # num_leaves forces the batched grower at batch=1 for the
             # equivalence check (5 feats x 256 bins x 4ch x 4B = 20 KB
             # per slot; 0.15 MB => ~7 slots < 15 leaves)
             **({"histogram_pool_size": 0.15} if k == 1 else {})}
        b = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                      num_boost_round=10)
        models[k] = b
    p_strict = {**base, "tpu_split_batch": 1}
    b_strict = lgb.train(p_strict, lgb.Dataset(X, label=y, params=p_strict),
                         num_boost_round=10)
    assert any(t.is_linear for t in b_strict._gbdt.models)
    # batch=1 (batched route, pooled) == strict, linear fits included
    np.testing.assert_allclose(models[1].predict(X), b_strict.predict(X),
                               rtol=1e-6, atol=1e-7)
    # batch=4 relaxes split order only: linear-fit quality stays within
    # a whisker of the strict learner's at the same budget
    mse4 = float(np.mean((models[4].predict(X) - y) ** 2))
    mse_s = float(np.mean((b_strict.predict(X) - y) ** 2))
    assert any(t.is_linear for t in models[4]._gbdt.models)
    assert mse4 < mse_s * 1.10, (mse4, mse_s)
