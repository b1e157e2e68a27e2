"""Binning unit tests (reference analogue: test_basic.py bin-boundary
checks, SURVEY.md §4)."""

import numpy as np
import pytest

from lightgbm_tpu.io.binning import (BIN_CATEGORICAL, MISSING_NAN,
                                     MISSING_NONE, MISSING_ZERO, BinMapper)
from lightgbm_tpu.io.dataset import Dataset


def test_simple_numeric_bins():
    vals = np.arange(100, dtype=float)
    m = BinMapper.find_bin(vals, 100, max_bin=255, min_data_in_bin=1,
                           use_missing=True, zero_as_missing=False)
    assert m.num_bin <= 255
    b = m.values_to_bins(vals)
    # monotone: higher values get >= bins
    assert (np.diff(b) >= 0).all()
    # mapping respects boundaries: value <= ub[t] iff bin <= t
    for t in range(m.num_bin - 1):
        ub = m.bin_upper_bound[t]
        assert ((vals <= ub) == (b <= t)).all()


def test_equal_count_binning():
    rng = np.random.default_rng(0)
    vals = rng.normal(size=100_000)
    m = BinMapper.find_bin(vals, len(vals), max_bin=64, min_data_in_bin=3,
                           use_missing=True, zero_as_missing=False)
    b = m.values_to_bins(vals)
    counts = np.bincount(b, minlength=m.num_bin)
    # roughly equal-count: no bin more than 5x the mean (heavy hitters aside)
    assert counts.max() < 5 * counts.mean()
    assert m.num_bin <= 64


def test_zero_bin_isolated():
    vals = np.concatenate([np.zeros(500), np.linspace(-3, 3, 500)])
    m = BinMapper.find_bin(vals, len(vals), max_bin=32, min_data_in_bin=1,
                           use_missing=True, zero_as_missing=False)
    zb = m.values_to_bins(np.array([0.0]))[0]
    # zero bin contains only (near-)zero
    near = m.values_to_bins(np.array([1e-40, -1e-40]))
    assert (near == zb).all()
    far = m.values_to_bins(np.array([0.5, -0.5]))
    assert (far != zb).all()


def test_nan_gets_last_bin():
    vals = np.array([1.0, 2.0, 3.0, np.nan, np.nan, 4.0] * 10)
    m = BinMapper.find_bin(vals, len(vals), max_bin=16, min_data_in_bin=1,
                           use_missing=True, zero_as_missing=False)
    assert m.missing_type == MISSING_NAN
    assert m.nan_bin == m.num_bin - 1
    b = m.values_to_bins(np.array([np.nan]))
    assert b[0] == m.nan_bin


def test_zero_as_missing():
    vals = np.array([0.0, 1.0, 2.0, 3.0] * 10)
    m = BinMapper.find_bin(vals, len(vals), max_bin=16, min_data_in_bin=1,
                           use_missing=True, zero_as_missing=True)
    assert m.missing_type == MISSING_ZERO
    assert m.nan_bin == m.values_to_bins(np.array([0.0]))[0]
    # NaN folds into the zero bin
    assert m.values_to_bins(np.array([np.nan]))[0] == m.nan_bin


@pytest.mark.parametrize("with_nan", [False, True],
                         ids=["every_level_binned", "nan_among_the_rows"])
def test_categorical_by_frequency(with_nan):
    """A column whose levels all get a bin keeps the layout it had: an
    unseen category and NaN fold into bin 0.  One that met NaN keeps an
    other bin for them, its last, which is no level's."""
    vals = np.array([5] * 50 + [2] * 30 + [9] * 20 + [7] * 5, float)
    if with_nan:
        vals = np.concatenate([vals, [np.nan] * 3])
    m = BinMapper.find_bin(vals, len(vals), max_bin=32,
                           min_data_in_bin=1, use_missing=True,
                           zero_as_missing=False, is_categorical=True)
    assert m.bin_type == BIN_CATEGORICAL
    assert m.bin_2_categorical == [5, 2, 9, 7]  # most frequent first
    assert m.values_to_bins(np.array([5.0]))[0] == 0
    assert m.values_to_bins(np.array([2.0]))[0] == 1
    if with_nan:
        # unseen category -> the other bin; NaN -> the other bin
        assert (m.num_bin, m.other_bin, m.nan_bin) == (5, 4, 4)
        assert m.values_to_bins(np.array([123.0]))[0] == 4
        assert m.values_to_bins(np.array([np.nan]))[0] == 4
    else:
        # unseen category -> bin 0; NaN -> bin 0; nan_bin disabled
        assert (m.num_bin, m.other_bin, m.nan_bin) == (4, -1, -1)
        assert m.values_to_bins(np.array([123.0]))[0] == 0
        assert m.values_to_bins(np.array([np.nan]))[0] == 0


@pytest.mark.parametrize("levels", [5, 254, 255, 1000])
def test_categorical_levels_and_the_other_bin(levels):
    """``max_bin - 1`` = 254 levels get a bin each, the most frequent
    first; a column with more keeps ONE more bin, the last, for every row
    that has none (a rarer level, an unseen code, a negative code, NaN),
    and ``bin_2_categorical`` round-trips the kept levels."""
    rng = np.random.default_rng(levels)
    codes = rng.permutation(levels) * 3 + 1         # not 0..levels-1
    p = 1.0 / np.arange(1, levels + 1) ** 1.1
    vals = np.concatenate([codes, codes[rng.choice(levels, size=60000,
                                                   p=p / p.sum())]])
    m = BinMapper.find_bin(vals.astype(float), len(vals), max_bin=255,
                           min_data_in_bin=1, use_missing=True,
                           zero_as_missing=False, is_categorical=True)
    kept, other = min(levels, 254), levels > 254
    assert len(m.bin_2_categorical) == kept
    assert (m.num_bin, m.other_bin, m.nan_bin) == \
        (kept + other, kept if other else -1, kept if other else -1)
    count = np.bincount(vals)
    by_count = sorted(np.flatnonzero(count), key=lambda c: (-count[c], c))
    assert m.bin_2_categorical == by_count[:kept]
    binned = m.values_to_bins(np.asarray(m.bin_2_categorical, float))
    np.testing.assert_array_equal(binned, np.arange(kept))
    assert [m.bin_to_value(b) for b in range(kept)] == m.bin_2_categorical
    fill = kept if other else 0
    probe = np.array([float(c) for c in by_count[kept:kept + 3]]
                     + [10.0 ** 6, -4.0, np.nan])
    np.testing.assert_array_equal(m.values_to_bins(probe), fill)
    back = BinMapper.from_dict(m.to_dict())
    assert (back.num_bin, back.other_bin) == (m.num_bin, m.other_bin)
    np.testing.assert_array_equal(back.values_to_bins(probe), fill)
    np.testing.assert_array_equal(back.values_to_bins(vals.astype(float)),
                                  m.values_to_bins(vals.astype(float)))


def test_trivial_feature_dropped():
    X = np.stack([np.ones(100), np.arange(100, dtype=float)], axis=1)
    ds = Dataset.from_data(X, label=np.zeros(100), config={"min_data_in_bin": 1})
    assert ds.num_total_features == 2
    assert ds.num_features == 1
    assert ds.used_feature_idx == [1]


def test_valid_set_uses_train_mappers():
    rng = np.random.default_rng(1)
    Xtr = rng.normal(size=(500, 3))
    Xva = rng.normal(size=(100, 3)) * 2  # different distribution
    dtr = Dataset.from_data(Xtr, label=np.zeros(500), config={})
    dva = dtr.create_valid(Xva, label=np.zeros(100))
    assert dva.mappers is dtr.mappers
    # same value bins identically in both
    v = Xtr[0, 1]
    btr = dtr.mappers[1].values_to_bins(np.array([v]))[0]
    assert dva.mappers[1].values_to_bins(np.array([v]))[0] == btr


def test_serialization_roundtrip():
    vals = np.array([1.0, 2.0, np.nan, 3.0] * 25)
    m = BinMapper.find_bin(vals, len(vals), max_bin=8, min_data_in_bin=1,
                           use_missing=True, zero_as_missing=False)
    m2 = BinMapper.from_dict(m.to_dict())
    test = np.array([0.5, 1.5, 2.5, np.nan, -1.0])
    assert (m.values_to_bins(test) == m2.values_to_bins(test)).all()


def test_forcedbins_filename(tmp_path):
    """forcedbins_filename JSON forces bin upper bounds (reference
    dataset_loader.cpp forced-bins load; examples/regression/
    forced_bins.json format)."""
    import json
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    n = 2000
    X = rng.uniform(0, 1, size=(n, 2))
    y = (X[:, 0] > 0.3).astype(np.float64)
    fb = tmp_path / "forced.json"
    fb.write_text(json.dumps([
        {"feature": 0, "bin_upper_bound": [0.3, 0.35, 0.4]},
        {"feature": 99, "bin_upper_bound": [1.0]},   # out of range: warn
    ]))
    p = {"objective": "binary", "verbose": -1, "max_bin": 16,
         "forcedbins_filename": str(fb)}
    ds = lgb.Dataset(X, label=y, params=p)
    ds.construct()
    ub = ds._inner.mappers[0].bin_upper_bound
    for forced in (0.3, 0.35, 0.4):
        assert np.any(np.isclose(ub, forced)), (forced, ub)
    # unforced feature keeps data-driven bounds
    assert not np.any(np.isclose(ds._inner.mappers[1].bin_upper_bound, 0.35))
    # trains fine
    bst = lgb.train(p, ds, num_boost_round=3)
    assert np.isfinite(bst.predict(X[:10])).all()


# ------------------------------------------------ the wide construct (PR 45)
def _greedy_walk(distinct_values, counts, max_bin, total_cnt):
    """The reference's walk over the distinct values ONE BY ONE
    (``GreedyFindBin``'s large-set branch as the program had it before it
    jumped from cut to cut): the oracle of the jumps."""
    num_distinct = len(distinct_values)
    mean_bin_size = total_cnt / max_bin
    is_big = counts >= mean_bin_size
    rest_cnt = total_cnt - int(counts[is_big].sum())
    rest_bins = max_bin - int(is_big.sum())
    if rest_bins > 0:
        mean_bin_size = rest_cnt / rest_bins
    upper, lower, cur_cnt, bin_cnt = [], [], 0, 0
    cur_lower = float(distinct_values[0])
    for i in range(num_distinct):
        if not is_big[i]:
            rest_cnt -= int(counts[i])
        cur_cnt += int(counts[i])
        if (is_big[i] or cur_cnt >= mean_bin_size
                or (i + 1 < num_distinct and is_big[i + 1]
                    and cur_cnt >= max(1.0, mean_bin_size * 0.5))):
            upper.append(float(distinct_values[i]))
            lower.append(cur_lower)
            bin_cnt += 1
            if i + 1 < num_distinct:
                cur_lower = float(distinct_values[i + 1])
            cur_cnt = 0
            if not is_big[i] and rest_bins > bin_cnt:
                mean_bin_size = rest_cnt / (rest_bins - bin_cnt)
            if bin_cnt >= max_bin - 1:
                break
    return [(upper[i] + lower[i + 1]) / 2.0
            for i in range(len(upper) - 1)] + [np.inf]


def _counts_of(kind, nd, rng):
    if kind == "ones":
        return np.ones(nd, np.int64)
    if kind == "small":
        return rng.integers(1, 5, nd)
    if kind == "heavy_hitters":
        c = rng.integers(1, 5, nd)
        c[rng.integers(0, nd, 12)] = rng.integers(50, 5000, 12)
        return c
    if kind == "heavy_neighbours":
        c = rng.integers(1, 3, nd)
        j = int(rng.integers(0, nd - 6))
        c[j:j + 5] = rng.integers(500, 50000)
        return c
    if kind == "heavy_ends":
        c = np.ones(nd, np.int64)
        c[0] = c[-1] = 10_000
        return c
    if kind == "pareto":
        return (rng.pareto(1.0, nd) * 3 + 1).astype(np.int64)
    return rng.integers(1, 1000, nd)         # "wide"


@pytest.mark.parametrize("kind", ["ones", "small", "heavy_hitters",
                                  "heavy_neighbours", "heavy_ends", "pareto",
                                  "wide"])
def test_greedy_jumps_cut_where_the_per_value_walk_cuts(kind):
    """``_greedy_find_bin`` jumps from cut to cut over the cumulative
    counts; the bounds are the per-value walk's to the last bit, with heavy
    values, heavy neighbours, a total over the counts' sum (elided zeros)
    and every budget a column can have."""
    from lightgbm_tpu.io.binning import _greedy_find_bin
    rng = np.random.default_rng(45)
    for trial in range(60):
        dv = np.unique(rng.standard_normal(int(rng.integers(300, 3000))))
        counts = _counts_of(kind, len(dv), rng).astype(np.int64)
        max_bin = int(rng.choice([2, 3, 15, 16, 63, 254, 255]))
        total = int(counts.sum()) + int(rng.choice([0, 0, 7, 1000]))
        got = _greedy_find_bin(dv, counts, max_bin, total, 3)
        assert got == _greedy_walk(dv, counts, max_bin, total), (kind, trial)


@pytest.mark.parametrize("shape", [(1, 1), (1023, 3), (1024, 64), (1025, 7),
                                   (5000, 64)])
def test_columns_first_is_the_transpose(shape):
    from lightgbm_tpu.io.binning import columns_first as _columns_first
    a = np.random.default_rng(1).standard_normal(shape)
    got = _columns_first(a)
    assert got.flags.c_contiguous and np.array_equal(got, a.T)
    wide = np.zeros((shape[0], shape[1] + 5))
    _columns_first(got, into=wide[:, 2:2 + shape[1]])
    assert np.array_equal(wide[:, 2:2 + shape[1]], a)
    assert not wide[:, :2].any() and not wide[:, 2 + shape[1]:].any()


@pytest.mark.parametrize("order", ["C", "F"])
def test_wide_construct_bins_by_blocks_as_column_by_column(order):
    """At the rows where binning goes native a run of numeric columns is
    binned side by side (``native.apply_bins_block``); the bins are the
    per-column ``values_to_bins``' (also where the matrix came column-major,
    as the benchmark hands it, and each column is binned by itself and a
    block of them written at a time) with NaN, a zero bin, trivial columns
    dropped between the runs and a categorical column among them, for the
    training set and for a valid set on its mappers."""
    rng = np.random.default_rng(3)
    n, f = 70_000, 150
    X = rng.standard_normal((n, f))
    X[:, 3] = 0.0
    X[:, 10] = rng.integers(0, 40, n)
    X[:, 20] = np.where(rng.random(n) < 0.7, 0.0, X[:, 20])
    X[rng.integers(0, n, 500), 30] = np.nan
    X[:, 40] = np.round(X[:, 40], 1)
    X[:, 77] = 1.0
    X = np.asarray(X, order=order)
    cfg = {"max_bin": 255, "verbose": -1, "enable_bundle": False}
    ds = Dataset.from_data(X, label=np.zeros(n), config=cfg,
                           categorical_feature=[10])
    assert ds.bins.shape == (n, f - 2)
    valid = Dataset.from_data(X[:66_000] + 0.01, label=np.zeros(66_000),
                              config=cfg, reference=ds)
    assert ds.bins.flags.c_contiguous and valid.bins.flags.c_contiguous
    for bins, raw in ((ds.bins, X), (valid.bins, X[:66_000] + 0.01)):
        for col, j in enumerate(ds.used_feature_idx):
            want = ds.mappers[j].values_to_bins(raw[:, j]).astype(np.uint8)
            assert np.array_equal(bins[:, col], want), j
