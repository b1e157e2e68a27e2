"""Data ingestion parity tests (reference basic.py pandas/Arrow/CSR and
Sequence streaming paths; test strategy: reference test_basic.py /
test_arrow.py)."""

import numpy as np
import pandas as pd
import pytest

import lightgbm_tpu as lgb

FAST = {"num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1}


@pytest.fixture(scope="module")
def frame():
    rng = np.random.default_rng(0)
    n = 1500
    df = pd.DataFrame({
        "num1": rng.normal(size=n),
        "num2": rng.normal(size=n),
        "color": pd.Categorical(rng.choice(["red", "green", "blue"], size=n)),
        "size": pd.Categorical(rng.choice(["s", "m", "l", "xl"], size=n)),
    })
    y = ((df["num1"] > 0) ^ (df["color"] == "red")).astype(float)
    return df, y.to_numpy()


def test_pandas_categorical_auto(frame):
    """categorical dtype columns are used as categorical splits under
    categorical_feature='auto' (reference _data_from_pandas)."""
    df, y = frame
    ds = lgb.Dataset(df, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=15)
    acc = float(((bst.predict(df) > 0.5) == y).mean())
    assert acc > 0.95  # needs the categorical split on 'color' to get here
    assert ds._inner.categorical_array().any()
    assert bst.feature_name() == ["num1", "num2", "color", "size"]
    # category order permuted at predict time must NOT change predictions
    df2 = df.copy()
    df2["color"] = df2["color"].cat.reorder_categories(
        ["blue", "red", "green"])
    np.testing.assert_allclose(bst.predict(df2), bst.predict(df), atol=1e-12)


def test_pandas_valid_set_aligns_categories(frame):
    df, y = frame
    ds = lgb.Dataset(df, label=y, params=FAST)
    # valid frame with categories in different declaration order
    dfv = df.iloc[:400].copy()
    dfv["color"] = pd.Categorical(dfv["color"].astype(str),
                                  categories=["green", "blue", "red"])
    dv = ds.create_valid(dfv, label=y[:400])
    res = {}
    lgb.train({**FAST, "objective": "binary", "metric": ["binary_error"]},
              ds, num_boost_round=10, valid_sets=[dv], valid_names=["v"],
              callbacks=[lgb.record_evaluation(res)])
    assert res["v"]["binary_error"][-1] < 0.1


def test_pandas_categorical_model_roundtrip(frame, tmp_path):
    """pandas category lists persist in the model file, so a RELOADED
    booster converts string-categorical frames identically (reference
    pandas_categorical trailer)."""
    df, y = frame
    ds = lgb.Dataset(df, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=10)
    f = tmp_path / "m.txt"
    bst.save_model(str(f))
    assert "pandas_categorical:[[" in f.read_text()
    bst2 = lgb.Booster(model_file=str(f))
    # trained booster predicts through f32 device scores; the reloaded one
    # sums f64 host-side -> ~1e-7 relative drift is expected, not a bug
    np.testing.assert_allclose(bst2.predict(df), bst.predict(df), rtol=1e-5)


def test_arrow_table(frame):
    import pyarrow as pa
    df, y = frame
    table = pa.Table.from_pandas(df[["num1", "num2"]])
    ds = lgb.Dataset(table, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=5)
    assert np.isfinite(bst.predict(table)).all()


def test_scipy_csr(synthetic_binary):
    from scipy import sparse
    X, y = synthetic_binary
    Xs = sparse.csr_matrix(np.where(np.abs(X) < 1.0, 0.0, X))
    ds = lgb.Dataset(Xs, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=5)
    p1 = bst.predict(Xs)
    p2 = bst.predict(np.asarray(Xs.todense()))
    np.testing.assert_allclose(p1, p2, atol=1e-12)


def test_trees_to_dataframe(synthetic_binary):
    """reference Booster.trees_to_dataframe: one row per node, parent/child
    links consistent, leaf counts match training data."""
    X, y = synthetic_binary
    bst = lgb.train({**FAST, "objective": "binary"},
                    lgb.Dataset(X, label=y, params=FAST), num_boost_round=3)
    df = bst.trees_to_dataframe()
    assert set(df.tree_index.unique()) == {0, 1, 2}
    t0 = df[df.tree_index == 0]
    splits = t0[t0.split_feature.notna()]
    leaves = t0[t0.split_feature.isna()]
    assert len(leaves) == len(splits) + 1          # binary tree invariant
    assert leaves["count"].sum() == len(X)
    # every child pointer resolves to a node with the right parent
    for _, r in splits.iterrows():
        for child in (r.left_child, r.right_child):
            assert (t0[t0.node_index == child].parent_index
                    == r.node_index).all()


def test_sequence_streaming(synthetic_binary):
    """lgb.Sequence subclass feeds batched rows (reference basic.py:915)."""
    X, y = synthetic_binary

    class NpSeq(lgb.Sequence):
        batch_size = 256

        def __init__(self, arr):
            self.arr = arr

        def __getitem__(self, idx):
            return self.arr[idx]

        def __len__(self):
            return len(self.arr)

    ds_seq = lgb.Dataset(NpSeq(X), label=y, params=FAST)
    ds_np = lgb.Dataset(X, label=y, params=FAST)
    b1 = lgb.train({**FAST, "objective": "binary"}, ds_seq, num_boost_round=5)
    b2 = lgb.train({**FAST, "objective": "binary"}, ds_np, num_boost_round=5)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), atol=1e-12)
    # list of sequences concatenates (multi-file streaming)
    half = len(X) // 2
    ds_two = lgb.Dataset([NpSeq(X[:half]), NpSeq(X[half:])], label=y,
                         params=FAST)
    b3 = lgb.train({**FAST, "objective": "binary"}, ds_two, num_boost_round=5)
    np.testing.assert_allclose(b3.predict(X), b2.predict(X), atol=1e-12)


def test_sparse_ingestion_matches_dense():
    """scipy CSR input produces the SAME binned dataset + model as the
    dense equivalent (sparse path never densifies: io/dataset.py
    _from_sparse; reference sparse_bin.hpp semantics)."""
    from scipy import sparse
    rng = np.random.default_rng(5)
    n, f = 3000, 30
    dense = rng.normal(size=(n, f))
    dense[rng.random((n, f)) < 0.85] = 0.0          # 85% zeros
    Xs = sparse.csr_matrix(dense)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 5}
    y = ((dense[:, 0] + dense[:, 3] - dense[:, 7]) > 0).astype(np.float64)

    ds_dense = lgb.Dataset(dense, label=y, params=p)
    ds_dense.construct()
    ds_sparse = lgb.Dataset(Xs, label=y, params=p)
    ds_sparse.construct()
    di, si = ds_dense._inner, ds_sparse._inner
    # same bin boundaries per feature
    for md, ms in zip(di.mappers, si.mappers):
        np.testing.assert_allclose(md.bin_upper_bound, ms.bin_upper_bound)
    # identical virtual bin assignment: compare via training equivalence
    bd = lgb.train(p, ds_dense, num_boost_round=8)
    bs = lgb.train(p, lgb.Dataset(Xs, label=y, params=p), num_boost_round=8)
    np.testing.assert_allclose(bd.predict(dense[:200]),
                               bs.predict(dense[:200]), atol=1e-6)


def test_sparse_wide_trains_without_densifying():
    """1M-scale wide sparse check, shrunk for CI: 12k x 2048 at 94%
    sparsity trains with EFB compressing the columns and sane accuracy
    (VERDICT r1 #8 — the [L, F, B, C] histogram state would not fit at
    full width).  The width is what is checked; the rows are what the
    strict grower's one-hot histograms cost on the CPU (30 s a round at
    60,000 rows), and 12,000 rows learn the two variables as well."""
    from scipy import sparse
    rng = np.random.default_rng(0)
    # one-hot-expanded categorical variables — the Allstate-class shape:
    # 128 variables x 16 categories = 2048 columns, columns within a
    # variable mutually exclusive, so zero-conflict EFB can merge each
    # variable's columns back into ~one bundle
    n, n_vars, card = 12_000, 128, 16
    f = n_vars * card
    cats = rng.integers(0, card, size=(n, n_vars))
    rows = np.repeat(np.arange(n), n_vars)
    cols = (np.arange(n_vars)[None, :] * card + cats).ravel()
    vals = rng.integers(1, 8, size=n * n_vars).astype(np.float64)
    X = sparse.csr_matrix((vals, (rows, cols)), shape=(n, f))
    w = rng.normal(size=card)
    y = (w[cats[:, 0]] + 0.5 * w[cats[:, 1]]
         + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "metric": "auc", "min_data_in_leaf": 20}
    ds = lgb.Dataset(X, label=y, params=p)
    ds.construct()
    inner = ds._inner
    # EFB must compress 98%-sparse columns substantially
    assert inner.bins.shape[1] < f // 3, inner.bins.shape
    bst = lgb.train(p, ds, num_boost_round=5, valid_sets=[ds])
    (_, _, auc, _), = bst.eval_train()
    assert auc > 0.75, auc


def test_sparse_valid_set_alignment():
    """create_valid with sparse data reuses the training mappers + bundle
    plan (reference CreateValid alignment)."""
    from scipy import sparse
    rng = np.random.default_rng(9)
    n, f = 2000, 50
    dense = rng.normal(size=(n, f))
    dense[rng.random((n, f)) < 0.9] = 0.0
    y = ((dense[:, 0] - dense[:, 5]) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "metric": "auc", "min_data_in_leaf": 5}
    dtr = lgb.Dataset(sparse.csr_matrix(dense[:1500]), label=y[:1500],
                      params=p)
    dva = dtr.create_valid(sparse.csr_matrix(dense[1500:]), label=y[1500:])
    bst = lgb.train(p, dtr, num_boost_round=8, valid_sets=[dva])
    (_, _, auc, _), = bst.eval_valid()
    assert auc > 0.7, auc


def test_sparse_valid_against_dense_reference_no_densify():
    """Sparse valid data against a DENSE-trained reference whose bundle
    defaults are not zero bins binds WITHOUT densification (the r3
    fallback is gone): implicit zeros decode through values_to_bins(0.0)
    and first-writer bundle order, bit-equal to the dense-built valid."""
    from scipy import sparse
    rng = np.random.default_rng(2)
    n, f = 3000, 20
    dense = rng.normal(size=(n, f))
    # mostly-5.0 bundleable-ish columns: most-frequent bin != zero bin
    dense[:, 5:15][rng.random((n, 10)) < 0.6] = 5.0
    dense[:, 5:15][rng.random((n, 10)) < 0.3] = 0.0
    y = ((dense[:, 0] + (dense[:, 5] == 5.0)) > 0.5).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "metric": "binary_logloss", "min_data_in_leaf": 5}
    dtr = lgb.Dataset(dense[:2000], label=y[:2000], params=p)
    dva_sparse = dtr.create_valid(sparse.csr_matrix(dense[2000:]),
                                  label=y[2000:])
    dva_dense = dtr.create_valid(dense[2000:], label=y[2000:])
    dva_sparse.construct()
    dva_dense.construct()
    np.testing.assert_array_equal(dva_sparse._inner.bins,
                                  dva_dense._inner.bins)
    bst = lgb.train(p, dtr, num_boost_round=6,
                    valid_sets=[dva_sparse, dva_dense],
                    valid_names=["sp", "dn"])
    vals = {name: v for name, _, v, _ in bst.eval_valid()}
    assert abs(vals["sp"] - vals["dn"]) < 1e-9, vals


def test_sparse_valid_against_categorical_reference_no_densify():
    """Categorical mappers map implicit zeros to the bin of CATEGORY 0
    (not bin 0); the sparse valid bins must equal the dense-built ones."""
    from scipy import sparse
    rng = np.random.default_rng(7)
    n, f = 2500, 8
    dense = rng.normal(size=(n, f))
    # integer category column where category 0 is NOT the most frequent
    cats = rng.choice([0, 1, 2, 3, 4], size=n, p=[0.1, 0.4, 0.3, 0.1, 0.1])
    dense[:, 3] = cats
    dense[rng.random((n, f)) < 0.5] = 0.0
    dense[:, 3] = cats  # keep the categorical column intact
    y = ((dense[:, 0] + (cats == 1)) > 0.5).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 5}
    dtr = lgb.Dataset(dense[:2000], label=y[:2000], params=p,
                      categorical_feature=[3])
    dva_sparse = dtr.create_valid(sparse.csr_matrix(dense[2000:]),
                                  label=y[2000:])
    dva_dense = dtr.create_valid(dense[2000:], label=y[2000:])
    dva_sparse.construct()
    dva_dense.construct()
    np.testing.assert_array_equal(dva_sparse._inner.bins,
                                  dva_dense._inner.bins)


def test_arrow_direct_column_path():
    """Numeric arrow Tables convert straight from the arrow buffers (no
    pandas intermediate), with nulls as NaN and chunked columns handled."""
    import pyarrow as pa
    rng = np.random.default_rng(3)
    n = 1200
    c0 = rng.normal(size=n)
    c1 = rng.integers(0, 100, size=n).astype(np.int64)
    t1 = pa.table({"a": c0[:600], "b": c1[:600]})
    t2 = pa.table({"a": c0[600:], "b": c1[600:]})
    table = pa.concat_tables([t1, t2])          # chunked columns
    # inject a null
    col_with_null = pa.chunked_array([pa.array([1.0, None] +
                                               list(c0[2:600])),
                                      pa.array(c0[600:])])
    table = table.set_column(0, "a", col_with_null)
    y = (c1 > 50).astype(np.float64)
    ds = lgb.Dataset(table, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=5)
    pred = bst.predict(table)
    assert np.isfinite(pred).all()
    # identical to the dense numpy equivalent
    dense = np.column_stack([c0, c1.astype(np.float64)])
    dense[1, 0] = np.nan
    b2 = lgb.train({**FAST, "objective": "binary"},
                   lgb.Dataset(dense, label=y, params=FAST),
                   num_boost_round=5)
    # predictions come back float32; identical trees within f32 epsilon
    np.testing.assert_allclose(pred, b2.predict(dense), atol=1e-6)


@pytest.mark.slow
def test_allstate_shaped_wide_sparse_end_to_end():
    """Allstate-class scale (BASELINE.md: 13.2M x 4228 one-hot sparse):
    1M x 4000 mutually-exclusive sparse features must construct (EFB on),
    train and predict WITHOUT ever materializing the dense [n, 4000]
    matrix (32 GB f64 — the test could not finish if any path densified).
    The bundled bin matrix must stay at a few uint8 columns.

    Gate calibration note: splits are found per ORIGINAL feature (the
    reference's EFB semantics too — bundles are storage, not features),
    so on one-hot-expanded data every split isolates exactly ONE 2-bin
    indicator; 2 rounds x 15 leaves = 28 splits can order at most ~28 of
    the 500 signal categories, which puts the ACHIEVABLE AUC near 0.56
    (measured; stock LightGBM is bounded the same way — fast learning on
    such data is what the categorical treatment is for).  The strong
    correctness gate here is exact trainer-score vs sparse-predict
    parity: it fails if ANY sparse->EFB->bin->predict step misaligns
    bundle offsets, independent of learnability."""
    from scipy import sparse
    rng = np.random.default_rng(11)
    n, B, M = 1_000_000, 8, 500          # 8 bundles x 500 members = 4000
    f = B * M
    rows_idx = []
    cols_idx = []
    vals = []
    member = rng.integers(0, M, size=(n, B))
    for b in range(B):
        rows_idx.append(np.arange(n))
        cols_idx.append(b * M + member[:, b])
        # one-hot indicators (2 bins/feature) — the real Allstate columns
        # are one-hot-expanded categoricals, BASELINE.md
        vals.append(np.ones(n))
    rows_idx = np.concatenate(rows_idx)
    cols_idx = np.concatenate(cols_idx)
    vals = np.concatenate(vals)
    X = sparse.csr_matrix((vals, (rows_idx, cols_idx)), shape=(n, f))
    y = ((member[:, 0] % 7 < 3).astype(np.float64)
         + 0.3 * rng.normal(size=n) > 0.5).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "max_bin": 63, "min_data_in_leaf": 20, "tpu_split_batch": 4,
         "tpu_hist_dtype": "float32", "metric": "auc"}
    ds = lgb.Dataset(X, label=y, params=p)
    ds.construct()
    inner = ds._inner
    # EFB collapsed the 4000 exclusive features into a handful of bundled
    # uint8 columns: this IS the memory budget (1 MB per column at 1M rows)
    assert inner.bins.shape[0] == n
    assert inner.bins.shape[1] <= 8 * B, inner.bins.shape
    assert inner.bins.dtype == np.uint8
    bst = lgb.train(p, ds, num_boost_round=2)
    pred = bst.predict(X[:50_000])
    assert np.isfinite(pred).all()
    # alignment: prediction through the sparse path reproduces the
    # trainer's own device-side scores (sigmoid of margins) for all rows
    # EXCEPT sampled-conflict collisions — EFB merges cross-group
    # features whose co-occurrence the sampled masks missed (~4 rows/1M
    # per pair; the reference's FastFeatureBundling samples the same
    # way), and a collided row can store only one of its two offsets, so
    # training and raw-value prediction legitimately diverge there.
    # Measured: 9 / 50_000 rows (0.018%).  A bundle-offset misalignment
    # BUG would break parity for whole categories (hundreds of rows per
    # 50k), caught by the 0.1% ceiling.
    sc = np.asarray(bst._gbdt.scores[:50_000, 0], np.float64)
    train_p = 1.0 / (1.0 + np.exp(-sc))
    mismatch = np.abs(train_p - pred) > 1e-4
    assert mismatch.mean() < 1e-3, int(mismatch.sum())
    np.testing.assert_allclose(train_p[~mismatch], pred[~mismatch],
                               rtol=1e-5, atol=1e-6)
    order = np.argsort(pred)
    ranks = np.empty(len(order))
    ranks[order] = np.arange(1, len(order) + 1)
    yb = y[:50_000]
    npos = yb.sum()
    auc = (ranks[yb > 0].sum() - npos * (npos + 1) / 2) / \
        (npos * (len(yb) - npos))
    # ~28 isolated categories of 500: small but real lift over chance
    assert auc > 0.54, auc


def test_datatable_frame_ingestion():
    """datatable Frame input (reference basic.py _data_from_datatable):
    the image ships no datatable, so a duck-typed stand-in exercises the
    module-name-gated path — names carry over, NaN survives, training
    matches the ndarray route."""
    import sys
    import types
    import numpy.testing as npt

    rng = np.random.default_rng(4)
    X = rng.normal(size=(600, 4))
    X[::17, 2] = np.nan
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float64)

    dt_mod = types.ModuleType("datatable")

    class Frame:
        def __init__(self, arr, names):
            self._arr = arr
            self.names = tuple(names)

        def to_numpy(self):
            return self._arr

    Frame.__module__ = "datatable"
    dt_mod.Frame = Frame
    sys.modules.setdefault("datatable", dt_mod)
    try:
        frame = Frame(X, ["a", "b", "c", "d"])
        p = {"objective": "binary", "verbose": -1, "num_leaves": 7,
             "min_data_in_leaf": 5}
        ds = lgb.Dataset(frame, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=5)
        ds2 = lgb.Dataset(X, label=y, params=p,
                          feature_name=["a", "b", "c", "d"])
        bst2 = lgb.train(p, ds2, num_boost_round=5)
        npt.assert_array_equal(bst.predict(X[:100]), bst2.predict(X[:100]))
        assert bst.feature_name() == ["a", "b", "c", "d"]
    finally:
        sys.modules.pop("datatable", None)
