"""The sorted-subset split search of ``ops/split.py`` by itself: the
carried sort (one variadic stable ``lax.sort`` a direction, with the
sums and the bin index as operands) against the ``argsort`` +
``take_along_axis`` form it replaced, on random histograms with ties;
the static list of subset columns against the scan of every column; the
winner's left bins read off the scan's order against
``categorical_left_bitset``'s second sort; and a column's other bin,
which no left set may hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import split as S
from lightgbm_tpu.ops.split import (SplitHyper, cat_levels,
                                    categorical_left_bitset, find_best_split)

F, B = 9, 64
IS_CAT = np.array([0, 1, 1, 0, 1, 1, 1, 0, 1], bool)
NUM_BINS = np.array([64, 40, 3, 30, 64, 4, 9, 64, 23], np.int32)
SUBSET = (1, 4, 6, 8)            # categorical with more than 4 levels


def _hist(seed: int, ties: bool):
    """A leaf's histogram [F, B, 3] of integer sums: with ``ties`` few
    distinct values, so that many bins share a score."""
    rng = np.random.default_rng(seed)
    top = 4 if ties else 400
    n = rng.integers(0, 60, size=(F, B)).astype(np.float32)
    n = np.where(np.arange(B)[None, :] < NUM_BINS[:, None], n, 0)
    g = (rng.integers(-top, top + 1, size=(F, B)) * (n > 0)).astype(np.float32)
    h = (rng.integers(1, top + 1, size=(F, B)) * (n > 0)).astype(np.float32)
    # every feature's bins add up to the leaf's totals
    g[:, 0] += g[0].sum() - g.sum(1)
    h[:, 0] += h[0].sum() - h.sum(1) + 1
    n[:, 0] += n[0].sum() - n.sum(1)
    hist = np.stack([g, h, n], -1)
    return jnp.asarray(hist), float(g[0].sum()), float(hist[0, :, 1].sum()), \
        float(hist[0, :, 2].sum())


def _hp(**over):
    """``cat_subset_cols`` as a job states it, ``None`` (not known: every
    column scanned) unless given."""
    return SplitHyper(num_leaves=31, min_data_in_leaf=1,
                      min_sum_hessian_in_leaf=1e-3, has_categorical=True,
                      n_bins=B, min_data_per_group=20, cat_smooth=5.0,
                      max_cat_threshold=8, **over)


def _argsort_subset_best(hist, sum_g, sum_h, count, nan_bin, hp):
    """The form the carried sort replaced: per direction ``argsort`` of
    the keys and three ``take_along_axis``; the best subset candidate as
    ``(gain, feature, threshold, direction, left bins)``."""
    g, h, n = hist[..., 0], hist[..., 1], hist[..., 2]
    bin_idx = jnp.arange(B)[None, :]
    levels = cat_levels(jnp.asarray(NUM_BINS), nan_bin, jnp.asarray(IS_CAT))
    ok_feat = jnp.asarray(IS_CAT) & (levels > hp.max_cat_to_onehot)
    cand = (bin_idx < levels[:, None]) & ok_feat[:, None] & (n >= hp.cat_smooth)
    used = cand.sum(1)
    k_limit = jnp.minimum(used, jnp.minimum(hp.max_cat_threshold,
                                            (used + 1) // 2))[:, None]
    score = g / (h + hp.cat_smooth)
    best = None
    for d, descending in enumerate((False, True)):
        key = jnp.where(cand, -score if descending else score, 1e30)
        order = jnp.argsort(key, axis=1)
        gs, hs, ns = (jnp.take_along_axis(a * cand, order, axis=1)
                      for a in (g, h, n))
        gl, hl, nl = (S._cumsum_bins(a, True) for a in (gs, hs, ns))
        ok = bin_idx < k_limit
        mdpg = jnp.float32(hp.min_data_per_group)
        ok &= (jnp.floor(nl / mdpg) > jnp.floor((nl - ns) / mdpg)) \
            & ((count - nl) >= mdpg)
        gain = jnp.where(ok, S.children_gain(
            gl, hl, nl, sum_g, sum_h, count, hp.lambda_l2 + hp.cat_l2, 0.0,
            hp), S.NEG_INF)
        f, t = np.unravel_index(int(jnp.argmax(gain)), gain.shape)
        if best is None or float(gain[f, t]) > best[0]:
            left = np.zeros(B, bool)
            left[np.asarray(order[f, :t + 1])] = True
            best = (float(gain[f, t]), int(f), int(t), d, left)
    return best


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("seed", range(4))
def test_the_carried_sort_finds_the_argsort_form_s_split(seed, ties):
    hist, sg, sh, cnt = _hist(seed, ties)
    hp = _hp(cat_subset_cols=SUBSET)
    nan_bin = jnp.full((F,), -1, jnp.int32)
    # numeric and one-hot candidates out of the way: subset columns only
    mask = jnp.zeros((F,), bool).at[jnp.asarray(SUBSET)].set(True)
    left = []
    res = find_best_split(hist, sg, sh, cnt, jnp.asarray(NUM_BINS), nan_bin,
                          jnp.asarray(IS_CAT), mask, hp, left_bins_out=left)
    gain, f, t, d, want = _argsort_subset_best(hist, sg, sh, cnt, nan_bin, hp)
    shift = float(S.parent_gain_shift(sg, sh, 0.0, hp))
    assert int(res.feature) == f and int(res.threshold) == t
    assert int(res.variant) == (S.VAR_CAT_FWD, S.VAR_CAT_BWD)[d]
    assert float(res.gain) == np.float32(gain) - np.float32(shift)
    np.testing.assert_array_equal(np.asarray(left[0]), want)
    # ... and categorical_left_bitset, which sorts again, agrees
    again = categorical_left_bitset(hist[f], NUM_BINS[f], res.variant,
                                    res.threshold, hp)
    np.testing.assert_array_equal(np.asarray(again), want)


@pytest.mark.parametrize("seed", range(4))
def test_the_static_columns_give_the_scan_of_every_column(seed):
    """Every field of the result and the left bins, whether the scan is
    handed the static list of subset columns or scans all nine; numeric,
    one-hot and subset winners all occur over the seeds."""
    hist, sg, sh, cnt = _hist(10 + seed, seed % 2 == 1)
    nan_bin = jnp.full((F,), -1, jnp.int32)
    mask = None if seed < 2 else jnp.asarray(IS_CAT)
    out = []
    for cols in (None, SUBSET):
        left = []
        res = find_best_split(hist, sg, sh, cnt, jnp.asarray(NUM_BINS), nan_bin,
                              jnp.asarray(IS_CAT), mask,
                              _hp(cat_subset_cols=cols), left_bins_out=left)
        out.append((res, left[0]))
    for a, b in zip(out[0][0], out[1][0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(out[0][1]), np.asarray(out[1][1]))
    numeric = not bool(out[0][0].is_categorical)
    assert bool(np.asarray(out[0][1]).any()) != numeric


def test_no_left_set_holds_the_other_bin():
    """A column whose last bin is its other bin (``nan_bin`` says which):
    though that bin holds the rows with the lowest score by far, neither
    the subset scan nor the one-hot variant puts it left, and the small
    column still takes the one-hot variant (its levels count, not its
    bins)."""
    hp = _hp()
    for col, nb in ((1, 40), (5, 4)):
        hist, sg, sh, cnt = _hist(3, False)
        h = np.array(hist)
        h[col, nb - 1] = (-500.0, 30.0, 40.0)      # the best bin to send left
        h[:, 0, 0] += h[0, :, 0].sum() - h[:, :, 0].sum(1)
        h[:, 0, 1] += h[0, :, 1].sum() - h[:, :, 1].sum(1)
        h[:, 0, 2] += h[0, :, 2].sum() - h[:, :, 2].sum(1)
        hist = jnp.asarray(h)
        sg, sh, cnt = (float(h[0, :, c].sum()) for c in range(3))
        mask = jnp.zeros((F,), bool).at[col].set(True)
        for other in (False, True):
            nan_bin = jnp.full((F,), -1, jnp.int32)
            if other:
                nan_bin = nan_bin.at[col].set(nb - 1)
            left = []
            res = find_best_split(hist, sg, sh, cnt, jnp.asarray(NUM_BINS),
                                  nan_bin, jnp.asarray(IS_CAT), mask, hp,
                                  left_bins_out=left)
            assert bool(res.is_categorical)
            assert bool(left[0][nb - 1]) != other
            # 4 bins, one of them the other bin: 3 levels, one-hot
            assert (int(res.variant) == S.VAR_CAT_ONEHOT) == (col == 5)


@pytest.mark.parametrize("seed", range(3))
def test_an_empty_list_of_subset_columns_skips_the_scan(seed):
    """A job whose categorical columns are all one-hot (a binary flag, a
    3-level code) states ``cat_subset_cols == ()``: no scan is traced (no
    row of a ``[0, B]`` array is read), and every field of the result and
    the left bins are those of the scan of every column, where those
    columns' subset candidates are masked."""
    hist, sg, sh, cnt = _hist(20 + seed, seed == 1)
    is_cat = jnp.asarray(IS_CAT & (NUM_BINS <= 4))      # columns 2 and 5
    nan_bin = jnp.full((F,), -1, jnp.int32)
    mask = None if seed < 2 else is_cat
    out = []
    for cols in (None, ()):
        left = []
        res = jax.jit(lambda h, cols=cols, left=left: find_best_split(
            h, sg, sh, cnt, jnp.asarray(NUM_BINS), nan_bin, is_cat, mask,
            _hp(cat_subset_cols=cols), left_bins_out=left))(hist)
        out.append(res)
    for a, b in zip(*out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out[1].variant) <= S.VAR_CAT_ONEHOT
    if seed == 2:
        assert int(out[1].variant) == S.VAR_CAT_ONEHOT


def test_a_list_that_names_no_row_is_refused():
    """A caller that hands over a selection of the features with the
    job's list still on ``hp`` is told so, not served another row."""
    hist, sg, sh, cnt = _hist(0, False)
    nan_bin = jnp.full((4,), -1, jnp.int32)
    with pytest.raises(ValueError, match="cat_subset_cols"):
        find_best_split(hist[:4], sg, sh, cnt, jnp.asarray(NUM_BINS[:4]),
                        nan_bin, jnp.asarray(IS_CAT[:4]), None,
                        _hp(cat_subset_cols=SUBSET))
