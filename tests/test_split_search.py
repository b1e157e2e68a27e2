"""The split search's reduction of a leaf's candidates to ONE winner
(``ops/split.py`` ``find_best_split``: the live variants reduced
elementwise, then the bins of a feature, then the features; no array holds
the variants side by side) against the formulation it replaced, kept here
as the plain reference: the five variants stacked on a last axis, one
flat argmax.  Every field of the result, the per-feature gains of the
voting hook and the winner's left bins, bit for bit, for one leaf and
under ``jax.vmap`` over eight."""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from lightgbm_tpu.ops.split import (NEG_INF, NUM_VARIANTS, VAR_CAT_BWD,
                                    VAR_CAT_FWD, VAR_CAT_ONEHOT,
                                    VAR_NUM_LEFT, VAR_NUM_RIGHT, SplitHyper,
                                    SplitResult, _cumsum_bins, _row_lookup,
                                    _spread_rows, _take_rows, cat_levels,
                                    children_gain, find_best_split,
                                    gain_given_output, leaf_gain,
                                    parent_gain_shift, smoothed_output,
                                    sort_by_score)


def find_best_split_stacked(hist: jax.Array, sum_g: jax.Array, sum_h: jax.Array,
                    count: jax.Array, num_bins: jax.Array, nan_bin: jax.Array,
                    is_cat: jax.Array, feature_mask: Optional[jax.Array],
                    hp: SplitHyper,
                    monotone: Optional[jax.Array] = None,
                    parent_output=0.0,
                    leaf_min=None, leaf_max=None,
                    depth=None,
                    rng_key: Optional[jax.Array] = None,
                    per_feature_out: Optional[list] = None,
                    gain_penalty: Optional[jax.Array] = None,
                    adv_bounds=None,
                    left_bins_out: Optional[list] = None) -> SplitResult:
    """The parent's ``find_best_split`` (PR 46) word for word: the five
    variants' gains stacked on a last axis ``[F, B, 5]``, those a job
    cannot have filled with ``NEG_INF``, the masks and penalties applied to
    the stack, ONE flat argmax.  The plain reference of this file."""
    num_f, n_b = hist.shape[0], hist.shape[1]
    g, h, n = hist[..., 0], hist[..., 1], hist[..., 2]
    bin_idx = lax.iota(jnp.int32, n_b)[None, :]                  # [1, B]
    valid_bin = bin_idx < num_bins[:, None]                      # [F, B]
    is_nan = bin_idx == nan_bin[:, None]                         # [F, B]

    # base cumulatives exclude the missing bin; its stats ride the variant axis
    gz = jnp.where(is_nan, 0.0, g)
    hz = jnp.where(is_nan, 0.0, h)
    nz = jnp.where(is_nan, 0.0, n)
    exact_scan = hp.hist_dtype == "float32"
    gl = _cumsum_bins(gz, exact_scan)
    hl = _cumsum_bins(hz, exact_scan)
    nl = _cumsum_bins(nz, exact_scan)
    gm = jnp.sum(jnp.where(is_nan, g, 0.0), axis=1, keepdims=True)  # [F, 1]
    hm = jnp.sum(jnp.where(is_nan, h, 0.0), axis=1, keepdims=True)
    nm = jnp.sum(jnp.where(is_nan, n, 0.0), axis=1, keepdims=True)
    has_missing = nan_bin[:, None] >= 0

    l1, l2 = hp.lambda_l1, hp.lambda_l2
    output_path = (hp.use_monotone or hp.path_smooth > 0.0
                   or hp.max_delta_step > 0.0)
    min_shift = parent_gain_shift(sum_g, sum_h, parent_output, hp)

    def variant_gain(gl_v, hl_v, nl_v, l2_v, bnds=None, mono=None):
        if not hp.use_monotone:
            return children_gain(gl_v, hl_v, nl_v, sum_g, sum_h, count,
                                 l2_v, parent_output, hp)
        gr = sum_g - gl_v
        hr = sum_h - hl_v
        nr = count - nl_v
        if not output_path:
            gain = leaf_gain(gl_v, hl_v, l1, l2_v) + leaf_gain(gr, hr, l1, l2_v)
        else:
            lo = smoothed_output(gl_v, hl_v, nl_v, parent_output, l1, l2_v, hp)
            ro = smoothed_output(gr, hr, nr, parent_output, l1, l2_v, hp)
            if hp.use_monotone and bnds is not None:
                # advanced method (monotone_constraints.hpp:858): the
                # per-(feature, threshold) bounds REPLACE the whole-leaf
                # bounds — a neighbor that does not overlap a child's
                # subrange imposes nothing on that child, which is exactly
                # the refinement (intersecting with leaf_min/leaf_max would
                # cancel it: the leaf bound is the min over the superset)
                bmin_l, bmax_l, bmin_r, bmax_r = bnds
                lo = jnp.clip(lo, bmin_l, bmax_l)
                ro = jnp.clip(ro, bmin_r, bmax_r)
            elif hp.use_monotone:
                lo = jnp.clip(lo, leaf_min, leaf_max)
                ro = jnp.clip(ro, leaf_min, leaf_max)
            gain = (gain_given_output(gl_v, hl_v, lo, l1, l2_v)
                    + gain_given_output(gr, hr, ro, l1, l2_v))
            if hp.use_monotone:
                # monotone direction violated → split forbidden
                # (feature_histogram.hpp:788-791 returns 0 = below gain_shift)
                mono = monotone if mono is None else mono
                mono = mono[:, None] if gl_v.ndim == 2 else mono
                bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
                gain = jnp.where(bad, NEG_INF, gain)
        ok = ((nl_v >= hp.min_data_in_leaf) & (nr >= hp.min_data_in_leaf)
              & (hl_v >= hp.min_sum_hessian_in_leaf)
              & (hr >= hp.min_sum_hessian_in_leaf))
        return jnp.where(ok, gain, NEG_INF)

    # numerical thresholds: t splits {bin <= t} | {bin > t}; t == last real bin
    # only splits off the missing bin, t at the nan bin itself is invalid
    thr_ok = valid_bin & (bin_idx < num_bins[:, None] - 1) & ~is_nan
    thr_ok = thr_ok & ~is_cat[:, None]
    gain_right = jnp.where(thr_ok, variant_gain(gl, hl, nl, l2,
                                                bnds=adv_bounds), NEG_INF)
    gain_left = jnp.where(thr_ok & has_missing,
                          variant_gain(gl + gm, hl + hm, nl + nm, l2,
                                       bnds=adv_bounds), NEG_INF)

    # the subset scan's own rows of the histogram: the job's static list
    # of subset columns, every row where it is not known (None), and no
    # scan at all where the job's categorical columns are all one-hot
    sub = hp.cat_subset_cols if hp.has_categorical else ()
    if sub and max(sub) >= num_f:
        raise ValueError(f"cat_subset_cols {sub} name no row of a "
                         f"histogram of {num_f} features")
    order_f = order_b = None
    if hp.has_categorical:
        # a categorical column's other bin (io/binning.py) stands where a
        # numeric column's NaN bin does: no left set holds it, and it is
        # not one of the column's levels
        levels = cat_levels(num_bins, nan_bin, is_cat)
        # one-hot categorical: {bin == t} goes left, gated to low-cardinality
        # features (reference feature_histogram.cpp:179 ``use_onehot =
        # num_bin <= max_cat_to_onehot``; plain lambda_l2 in this branch)
        onehot_ok = is_cat[:, None] & (levels[:, None]
                                       <= hp.max_cat_to_onehot)
        gain_cat = jnp.where(valid_bin & ~is_nan & onehot_ok,
                             variant_gain(g, h, n, l2), NEG_INF)

    if sub != ():
        # sorted-subset categorical (reference feature_histogram.cpp:241-340):
        # candidate bins with count >= cat_smooth, sorted by
        # g/(h+cat_smooth); prefixes of the ascending and descending orders
        # are the left sets, capped at max_cat_threshold, evaluated with
        # l2 + cat_l2 and gated by min_data_per_group.  Vectorized:
        # ``sort_by_score`` per direction, then cumulative sums; the
        # reference's sequential ``cnt_cur_group`` reset becoming "left
        # count crosses a multiple of min_data_per_group" (a static
        # approximation of the same evaluation density).
        with jax.named_scope("cat_subset"):
            l2c = l2 + hp.cat_l2
            subset_feat_ok = is_cat & (levels > hp.max_cat_to_onehot)   # [F]
            if sub is None:
                rows_of = lambda a: a
                spread = lambda a, fill: a
                row_of_feat = lambda f: f
            else:
                rows_of = lambda a: _take_rows(a, sub)
                spread = lambda a, fill: _spread_rows(a, sub, num_f, fill)
                row_of_feat = _row_lookup(sub, num_f)
            gS, hS, nS = rows_of(g), rows_of(h), rows_of(n)
            cand_bin = rows_of(valid_bin & ~is_nan
                               & subset_feat_ok[:, None]) \
                & (nS >= hp.cat_smooth)
            used_bin_s = jnp.sum(cand_bin, axis=1)                    # [Fs]
            max_num_cat_s = jnp.minimum(hp.max_cat_threshold,
                                        (used_bin_s + 1) // 2)
            k_limit = jnp.minimum(used_bin_s, max_num_cat_s)[:, None]
            score = gS / (hS + hp.cat_smooth)
            stats_s = (gS * cand_bin, hS * cand_bin, nS * cand_bin)
            # variant_gain reads the per-feature monotone directions
            mono_s = _take_rows(monotone, sub) \
                if hp.use_monotone and sub is not None else None

            def subset_scan(descending: bool):
                gs, hs, ns, order = sort_by_score(score, cand_bin, stats_s,
                                                  descending)
                glv = _cumsum_bins(gs, exact_scan)
                hlv = _cumsum_bins(hs, exact_scan)
                nlv = _cumsum_bins(ns, exact_scan)
                ok = bin_idx < k_limit
                if hp.min_data_per_group > 1:
                    mdpg = jnp.float32(hp.min_data_per_group)
                    crossed = jnp.floor(nlv / mdpg) \
                        > jnp.floor((nlv - ns) / mdpg)
                    ok = ok & crossed & ((count - nlv) >= mdpg)
                gain = jnp.where(ok, variant_gain(glv, hlv, nlv, l2c,
                                                  mono=mono_s), NEG_INF)
                return spread(gain, NEG_INF), glv, hlv, nlv, order

            gain_fwd, gl_f, hl_f, nl_f, order_f = subset_scan(False)
            gain_bwd, gl_b, hl_b, nl_b, order_b = subset_scan(True)
            used_bin = spread(used_bin_s, 0)
            max_num_cat = spread(max_num_cat_s, 0)
    else:
        neg = jnp.full((num_f, n_b), NEG_INF)
        gain_fwd = gain_bwd = neg
        if not hp.has_categorical:
            gain_cat = neg
        gl_f = hl_f = nl_f = gl_b = hl_b = nl_b = jnp.zeros_like(g)
        used_bin = max_num_cat = jnp.zeros((num_f,), jnp.int32)
        row_of_feat = lambda f: f

    if hp.extra_trees and rng_key is not None:
        # extremely-randomized mode: per (feature, node) keep exactly ONE
        # random candidate threshold per variant family (reference
        # feature_histogram.cpp USE_RAND rand_threshold draws)
        kn, kc, ks = jax.random.split(rng_key, 3)
        u_num = jax.random.uniform(kn, (num_f,))
        rand_num = jnp.floor(
            u_num * jnp.maximum(num_bins - 1, 1).astype(jnp.float32)
        ).astype(jnp.int32)
        keep_num = bin_idx == rand_num[:, None]
        gain_right = jnp.where(keep_num, gain_right, NEG_INF)
        gain_left = jnp.where(keep_num, gain_left, NEG_INF)
        if hp.has_categorical:
            u_cat = jax.random.uniform(kc, (num_f,))
            rand_cat = jnp.floor(
                u_cat * num_bins.astype(jnp.float32)).astype(jnp.int32)
            gain_cat = jnp.where(bin_idx == rand_cat[:, None], gain_cat,
                                 NEG_INF)
            u_sub = jax.random.uniform(ks, (num_f,))
            max_thr = jnp.maximum(jnp.minimum(max_num_cat, used_bin) - 1, 0)
            rand_k = jnp.floor(
                u_sub * (max_thr + 1).astype(jnp.float32)).astype(jnp.int32)
            keep_sub = bin_idx == rand_k[:, None]
            gain_fwd = jnp.where(keep_sub, gain_fwd, NEG_INF)
            gain_bwd = jnp.where(keep_sub, gain_bwd, NEG_INF)

    cand = jnp.stack([gain_right, gain_left, gain_cat, gain_fwd, gain_bwd],
                     axis=-1)                                  # [F, B, V]
    if feature_mask is not None:
        cand = jnp.where(feature_mask[:, None, None], cand, NEG_INF)
    if gain_penalty is not None:
        # CEGB: per-feature acquisition cost subtracted from the split gain
        # before the argmax (cost_effective_gradient_boosting.hpp DeltaGain)
        cand = jnp.where(cand > NEG_INF / 2,
                         cand - gain_penalty[:, None, None], cand)

    if per_feature_out is not None:
        # voting-parallel hook: per-feature best gain before the global
        # argmax (reference voting_parallel_tree_learner.cpp:344 votes on
        # per-feature local split gains)
        per_feature_out.append(jnp.max(cand, axis=(1, 2)) - min_shift)

    if hp.use_monotone and hp.monotone_penalty > 0.0:
        # depth-decaying gain penalty on monotone features, applied to the
        # FINAL gain before cross-feature argmax (serial_tree_learner.cpp:994,
        # monotone_constraints.hpp:357 ComputeMonotoneSplitGainPenalty)
        d = jnp.float32(0 if depth is None else depth)
        p = jnp.float32(hp.monotone_penalty)
        eps = jnp.float32(1e-10)
        pen = jnp.where(p >= d + 1.0, eps,
                        jnp.where(p <= 1.0, 1.0 - p / (2.0 ** d) + eps,
                                  1.0 - 2.0 ** (p - 1.0 - d) + eps))
        pen_f = jnp.where(monotone != 0, pen, 1.0)[:, None, None]
        final = cand - min_shift
        cand = jnp.where(final > 0, final * pen_f, NEG_INF)
        min_shift = jnp.float32(0.0)

    flat = cand.reshape(-1)
    best = jnp.argmax(flat)
    best_gain_raw = flat[best]
    feat = (best // (n_b * NUM_VARIANTS)).astype(jnp.int32)
    rem = best % (n_b * NUM_VARIANTS)
    thr = (rem // NUM_VARIANTS).astype(jnp.int32)
    variant = (rem % NUM_VARIANTS).astype(jnp.int32)

    # recover the winner's left-side stats
    srow = row_of_feat(feat)     # the winner's row of the subset scan
    glw = jnp.stack([gl[feat, thr], gl[feat, thr] + gm[feat, 0], g[feat, thr],
                     gl_f[srow, thr], gl_b[srow, thr]])
    hlw = jnp.stack([hl[feat, thr], hl[feat, thr] + hm[feat, 0], h[feat, thr],
                     hl_f[srow, thr], hl_b[srow, thr]])
    nlw = jnp.stack([nl[feat, thr], nl[feat, thr] + nm[feat, 0], n[feat, thr],
                     nl_f[srow, thr], nl_b[srow, thr]])
    lg = glw[variant]
    lh = hlw[variant]
    ln = nlw[variant]

    if left_bins_out is not None and hp.has_categorical:
        with jax.named_scope("cat_bitset"):
            pos = lax.iota(jnp.int32, n_b)
            left = (variant == VAR_CAT_ONEHOT) & (pos == thr)
            if order_f is not None:
                # a subset winner: the first thr + 1 bins of the winning
                # direction's order, as a [B, B] compare (a scatter by the
                # order is a bin-sized scatter)
                order_w = jnp.where(variant == VAR_CAT_BWD, order_b[srow],
                                    order_f[srow])
                in_prefix = jnp.any((order_w[:, None] == pos[None, :])
                                    & (pos[:, None] <= thr), axis=0)
                left = left | (in_prefix & (variant > VAR_CAT_ONEHOT))
            left_bins_out.append(left)

    gain = best_gain_raw - min_shift
    return SplitResult(
        gain=jnp.where(best_gain_raw <= NEG_INF / 2, jnp.float32(NEG_INF), gain),
        feature=feat,
        threshold=thr,
        default_left=(variant == VAR_NUM_LEFT),
        is_categorical=(variant >= VAR_CAT_ONEHOT),
        variant=variant,
        left_sum_g=lg, left_sum_h=lh, left_count=ln,
        right_sum_g=sum_g - lg, right_sum_h=sum_h - lh, right_count=count - ln,
    )

# ------------------------------------------------------------- the leaves
ROWS, F, B, LEAVES = 2400, 12, 32, 8
#          0   1   2   3   4   5   6   7   8   9  10  11
NUM_BINS = np.array([32, 20, 32, 32, 16, 32, 3, 5, 20, 13, 8, 20], np.int32)
NAN_BIN = np.array([-1, 19, -1, -1, 15, -1, -1, 4, -1, 12, -1, 19], np.int32)
IS_CAT = np.zeros(F, bool)
IS_CAT[6:10] = True              # 6, 7 one-hot (3 and 4 levels); 8, 9 subset
SUBSET = (8, 9)
MONOTONE = np.array([1, -1, 0, 1, 0, 0, 0, 0, 0, 0, -1, 0], np.int32)


def _leaves(seed: int, quantised: bool):
    """Eight leaves of one data set: ``(hist [8, F, B, 4], sum_g, sum_h,
    count [8])``.  Column 2 is column 0 again and column 3 is column 0 with
    its bins in falling order (equal gains in other columns, at the same
    and at another bin), column 11 is column 1 again (the same with a
    missing bin), column 4 has a missing bin that holds no row (its two
    numeric variants are equal everywhere), column 5 holds rows in every
    other bin only (equal gains at neighbouring bins), and the rows of
    column 10's bin 3 have hessian 0.  ``quantised``: gradients and
    hessians are small integers, as the int8 histograms' are, so equal
    sums are equal to the bit."""
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, nb - (nan >= 0), ROWS)
                     for nb, nan in zip(NUM_BINS, NAN_BIN)], axis=1)
    for col in (1, 7, 9, 11):     # some rows in the missing / other bin
        bins[rng.random(ROWS) < 0.15, col] = NAN_BIN[col]
    bins[:, 2] = bins[:, 0]
    bins[:, 3] = NUM_BINS[0] - 1 - bins[:, 0]
    bins[:, 11] = bins[:, 1]
    bins[:, 5] = 2 * (bins[:, 5] // 2)
    signal = (bins[:, 0] > 13) + 0.5 * (bins[:, 8] % 3 == 0) \
        + 0.5 * (bins[:, 6] == 1) - 0.4 * (bins[:, 1] == NAN_BIN[1])
    if quantised:
        g = np.clip(np.round(2 * signal + rng.normal(size=ROWS)), -4, 4)
        h = rng.integers(1, 4, ROWS).astype(np.float64)
    else:
        g = signal + rng.normal(size=ROWS)
        h = rng.random(ROWS) + 0.5
    h[bins[:, 10] == 3] = 0.0
    hist = np.zeros((LEAVES, F, B, 4))
    tot = np.zeros((LEAVES, 3))
    for k in range(LEAVES):
        rows = rng.random(ROWS) < (0.3 + 0.08 * k)
        if k == 3:
            rows &= bins[:, 6] != 2          # a level without rows
        for ch, val in enumerate((g, h, np.ones(ROWS))):
            for f in range(F):
                np.add.at(hist[k, f, :, ch], bins[rows, f], val[rows])
        tot[k] = hist[k, 0, :, :3].sum(axis=0)
    tot = tot.astype(np.float32)
    return (jnp.asarray(hist, jnp.float32), jnp.asarray(tot[:, 0]),
            jnp.asarray(tot[:, 1]), jnp.asarray(tot[:, 2]))


@dataclasses.dataclass(frozen=True)
class Case:
    hp: dict = dataclasses.field(default_factory=dict)
    quantised: bool = False
    categorical: bool = False
    # "random", "categorical", "subset" (those columns), or ONE column
    mask: Optional[object] = None
    penalty: Optional[float] = None     # scale of the per-feature penalty
    monotone: bool = False
    extra_trees: bool = False
    per_feature: bool = False
    bounds: bool = False                # finite leaf_min / leaf_max
    # what the REFERENCE has to find over the eight leaves, so that a case
    # cannot stop exercising what it is named for
    expect: Optional[object] = None


def _cat(**hp):
    return dict(has_categorical=True, min_data_per_group=20, cat_smooth=5.0,
                max_cat_threshold=8, **hp)


_seen = lambda field, *values: lambda res: set(values) <= set(
    np.asarray(getattr(res, field)).tolist())
_all = lambda field, value: lambda res: bool(
    np.all(np.asarray(getattr(res, field)) == value))

CASES = {
    "float_hist": Case(expect=_seen("variant", VAR_NUM_RIGHT)),
    # quantised sums: column 0 wins and ties with columns 2 and 3
    "quantised_ties_across_columns_and_bins": Case(
        quantised=True, hp=dict(hist_dtype="int8"), expect=_all("feature", 0)),
    # the two numeric variants equal in every bin: the lower one's
    "quantised_ties_between_the_numeric_variants": Case(
        quantised=True, hp=dict(hist_dtype="int8"), mask=4,
        expect=_all("variant", VAR_NUM_RIGHT)),
    "missing_goes_left": Case(
        quantised=True, mask=1, expect=_seen("variant", VAR_NUM_LEFT)),
    "empty_bins": Case(quantised=True, hp=dict(hist_dtype="int8"), mask=5,
                       expect=_all("feature", 5)),
    "zero_hessians": Case(quantised=True, mask=10,
                          hp=dict(min_sum_hessian_in_leaf=0.0, lambda_l2=0.0)),
    "l1_l2_min_gain": Case(hp=dict(lambda_l1=0.5, lambda_l2=2.0,
                                   min_gain_to_split=0.1)),
    "path_smooth_max_delta_step": Case(hp=dict(path_smooth=5.0,
                                               max_delta_step=0.3)),
    "one_hot_categorical": Case(
        categorical=True, quantised=True, hp=_cat(cat_subset_cols=()),
        mask="categorical", expect=_all("variant", VAR_CAT_ONEHOT)),
    "subset_categorical_columns_listed": Case(
        categorical=True, hp=_cat(cat_subset_cols=SUBSET), mask="subset",
        expect=_seen("variant", VAR_CAT_FWD, VAR_CAT_BWD)),
    "subset_categorical_columns_unknown": Case(
        categorical=True, quantised=True, hp=_cat(cat_subset_cols=None),
        mask="subset", expect=_seen("variant", VAR_CAT_FWD, VAR_CAT_BWD)),
    "categorical_job_numeric_winner": Case(
        categorical=True, hp=_cat(cat_subset_cols=SUBSET),
        expect=_seen("is_categorical", False)),
    "feature_mask": Case(quantised=True, mask="random"),
    "gain_penalty": Case(penalty=30.0, mask="random"),
    # a penalty far above the gains rounds a column's candidates together:
    # the first bin and the lowest variant win, whatever they were before
    "gain_penalty_that_rounds_gains_together": Case(
        penalty=1e9, mask=1,
        expect=lambda res: bool(np.all(np.asarray(res.gain) < -1e6))),
    "monotone_penalty": Case(
        monotone=True, bounds=True,
        hp=dict(use_monotone=True, monotone_penalty=1.5),
        expect=lambda res: bool(np.any(np.asarray(res.gain) > 0))),
    "monotone_penalty_categorical_job": Case(
        monotone=True, categorical=True, quantised=True,
        hp=_cat(cat_subset_cols=SUBSET, use_monotone=True,
                monotone_penalty=0.5)),
    "extra_trees": Case(extra_trees=True, hp=dict(extra_trees=True)),
    "extra_trees_categorical_job": Case(
        extra_trees=True, categorical=True, mask="categorical",
        hp=_cat(cat_subset_cols=SUBSET, extra_trees=True)),
    "per_feature_out": Case(per_feature=True, quantised=True, penalty=3.0),
    "per_feature_out_monotone_penalty": Case(
        per_feature=True, monotone=True,
        hp=dict(use_monotone=True, monotone_penalty=0.5)),
    "no_valid_candidate": Case(
        quantised=True, hp=dict(min_data_in_leaf=ROWS),
        expect=_all("gain", np.float32(NEG_INF))),
    "no_valid_candidate_categorical_job": Case(
        categorical=True, hp=_cat(cat_subset_cols=None, min_data_in_leaf=ROWS),
        mask="random", per_feature=True,
        expect=_all("gain", np.float32(NEG_INF))),
}


def _search(fn, case: Case):
    """``fn`` (``find_best_split`` or the reference) as a function of one
    leaf's arrays, every output a flat tuple: the result's fields, the
    voting hook's per-feature gains, the winner's left bins."""
    hp = SplitHyper(**{**dict(num_leaves=31, min_data_in_leaf=5, n_bins=B,
                              min_sum_hessian_in_leaf=1e-3), **case.hp})
    is_cat = jnp.asarray(IS_CAT if case.categorical else np.zeros(F, bool))
    monotone = jnp.asarray(np.where(IS_CAT, 0, MONOTONE)) \
        if case.monotone else None

    def one(leaf):
        per_feature = [] if case.per_feature else None
        left_bins = [] if case.categorical else None
        res = fn(leaf["hist"], leaf["sum_g"], leaf["sum_h"], leaf["count"],
                 jnp.asarray(NUM_BINS), jnp.asarray(NAN_BIN), is_cat,
                 leaf.get("mask"), hp, monotone=monotone,
                 parent_output=leaf["parent_output"],
                 leaf_min=leaf["bound"] * -1, leaf_max=leaf["bound"],
                 depth=leaf["depth"], rng_key=leaf.get("key"),
                 per_feature_out=per_feature,
                 gain_penalty=leaf.get("penalty"), left_bins_out=left_bins)
        return res, tuple(per_feature or ()), tuple(left_bins or ())
    return one


def _leaf_arrays(case: Case, seed: int):
    hist, sum_g, sum_h, count = _leaves(seed, case.quantised)
    rng = np.random.default_rng(seed + 1)
    leaves = dict(
        hist=hist, sum_g=sum_g, sum_h=sum_h, count=count,
        parent_output=jnp.asarray(rng.normal(size=LEAVES) * 0.1, jnp.float32),
        depth=jnp.asarray(rng.integers(0, 5, LEAVES), jnp.int32),
        bound=jnp.full((LEAVES,), 1.5 if case.bounds else np.inf,
                       jnp.float32))
    if case.mask == "random":
        leaves["mask"] = jnp.asarray(rng.random((LEAVES, F)) < 0.6)
    elif case.mask == "categorical":
        leaves["mask"] = jnp.asarray(np.tile(IS_CAT, (LEAVES, 1)))
    elif case.mask == "subset":
        leaves["mask"] = jnp.asarray(
            np.tile(np.isin(np.arange(F), SUBSET), (LEAVES, 1)))
    elif case.mask is not None:
        leaves["mask"] = jnp.asarray(
            np.tile(np.arange(F) == case.mask, (LEAVES, 1)))
    if case.penalty is not None:
        leaves["penalty"] = jnp.asarray(
            rng.random((LEAVES, F)) * case.penalty, jnp.float32)
    if case.extra_trees:
        leaves["key"] = jax.random.split(jax.random.PRNGKey(seed), LEAVES)
    return leaves


def _bits(tree):
    return [(np.asarray(a).dtype, np.asarray(a).shape,
             np.asarray(a).tobytes()) for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("how", ["one_leaf", "vmap_8_leaves"])
@pytest.mark.parametrize("name", list(CASES))
def test_find_best_split_is_the_stacked_search_to_the_bit(name, how):
    case = CASES[name]
    leaves = _leaf_arrays(case, seed=47 + list(CASES).index(name))
    runs = {}
    for label, fn in (("stacked", find_best_split_stacked),
                      ("package", find_best_split)):
        one = jax.jit(_search(fn, case))
        if how == "one_leaf":
            # the leaf of the eight on which the case's expectation rests
            # is not known beforehand: take each of the first three
            runs[label] = [one(jax.tree.map(lambda a: a[k], leaves))
                           for k in range(3)]
        else:
            runs[label] = jax.jit(jax.vmap(one))(leaves)
    want, got = runs["stacked"], runs["package"]
    if how == "vmap_8_leaves" and case.expect is not None:
        assert case.expect(want[0]), want[0]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    assert _bits(want) == _bits(got)
