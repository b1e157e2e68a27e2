"""Best-split finding from histograms.

TPU-native re-design of the reference split finder (reference:
src/treelearner/feature_histogram.hpp:832 ``FindBestThresholdSequentially``
CPU scans; src/treelearner/cuda/cuda_best_split_finder.cu:772
``FindBestSplitsForLeafKernel`` — one thread-block per (feature, direction)
with in-block prefix scans + arg-reduction).

On TPU the whole thing is a handful of vector ops over the [F, B] histogram:
cumulative sums along the bin axis give every threshold's left-side stats at
once, both missing-value default directions are evaluated as two candidate
variants (the reference's forward/backward scans), one-hot and sorted-subset
categorical candidates are further variants where the job has such columns,
and the winner is the first maximum in (feature, bin, variant) order: the
variants reduced elementwise, then the bins of a feature, then the features.
Bins beyond a feature's ``num_bin`` and the dedicated NaN bin are masked,
replacing the reference's per-feature loop bounds.

Gain/regularization semantics mirror feature_histogram.hpp:
``ThresholdL1`` soft-shrink, gain = GL'^2/(HL+l2) + GR'^2/(HR+l2), validity =
min_data_in_leaf / min_sum_hessian_in_leaf on both children, reported gain is
the improvement over the parent minus ``min_gain_to_split``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SplitHyper:
    """Static split/growth hyperparameters (subset of reference Config used by
    the learner; config.h learning-control block)."""
    num_leaves: int = 31
    max_depth: int = -1
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    # monotone constraints (basic method, monotone_constraints.hpp:465);
    # use_monotone is the static gate — the per-feature direction vector is a
    # runtime array argument
    use_monotone: bool = False
    monotone_penalty: float = 0.0
    # "basic": midpoint bounds inherited down the path
    # (monotone_constraints.hpp:465); "intermediate": per-leaf bounds from
    # actual adjacent-leaf outputs via dense box adjacency, refreshed every
    # split (learner/monotone.py; reference :516 IntermediateLeafConstraints)
    monotone_method: str = "basic"
    # extra-trees mode: one random threshold per (feature, node)
    # (reference USE_RAND template paths in feature_histogram)
    extra_trees: bool = False
    feature_fraction_bynode: float = 1.0
    # static gate: skip the categorical argsort/cumsum machinery entirely
    # on all-numeric datasets (argsort is expensive on TPU)
    has_categorical: bool = False
    # the packed indices of the categorical columns that take the
    # sorted-subset variant (num_bin without the other bin >
    # max_cat_to_onehot), where they are known at trace time
    # (boosting/gbdt.py from the train set's bin mappers):
    # ``find_best_split``'s subset scan then sorts those rows of the
    # histogram alone, and none where the tuple is empty.  ``None``: not
    # known; every column is scanned, the others masked.  A caller that
    # hands ``find_best_split`` a selection of the features replaces the
    # tuple by ``None`` (learner/grower.py ``pv_vote_best_split``)
    cat_subset_cols: Optional[Tuple[int, ...]] = None
    n_bins: int = 256
    rows_per_block: int = 4096
    path_smooth: float = 0.0
    # MXU contraction dtype.  "float32" (default): fully exact products via
    # multi-pass MXU (Precision.HIGHEST) — the split-parity mode matching
    # the reference's fp64 histograms bit-for-metric.  "bfloat16": exact
    # {0,1} one-hot, f32 accumulation, only grad/hess products take ~2^-9
    # input rounding (measured ~1.1e-4 AUC drift, ~3x faster kernels —
    # docs/PERF_NOTES.md; the speed mode the benchmark uses, analogous to
    # the reference GPU docs recommending single precision).
    hist_dtype: str = "float32"
    # histogram-build formulation (ops/histogram.py HIST_KERNELS):
    # "auto" = measured dispatch incl. the round-6 packed / shared-radix
    # kernels, "onehot" = the flat one-hot reference path, "packed" /
    # "radix2" = force a formulation.  All modes are bit-identical.
    hist_kernel: str = "auto"
    # bounded histogram pool (reference feature_histogram.hpp:1367
    # HistogramPool, serial_tree_learner.cpp:36-47 histogram_pool_size):
    # 0 = one resident histogram per leaf ([L, F, B, 4]); > 0 = that many
    # pool slots with lowest-cached-gain eviction — split parents whose
    # histogram was evicted get BOTH children histogrammed directly
    # (jit-friendly replacement for the reference's LRU + re-fetch).
    # Batched grower only.
    hist_pool_slots: int = 0


#: candidate variants; a winner's place in the search order is
#: ``(feature * n_bins + bin) * NUM_VARIANTS + variant``
VAR_NUM_RIGHT = 0    # numerical, missing goes right
VAR_NUM_LEFT = 1     # numerical, missing goes left
VAR_CAT_ONEHOT = 2   # categorical one-hot: {bin == t} left
VAR_CAT_FWD = 3      # categorical sorted-subset, ascending-score prefix
VAR_CAT_BWD = 4      # categorical sorted-subset, descending-score prefix
NUM_VARIANTS = 5


class SplitResult(NamedTuple):
    """Chosen split for one leaf (reference split_info.hpp:294 ``SplitInfo``)."""
    gain: jax.Array          # f32 — improvement; <= 0 means "don't split"
    feature: jax.Array       # i32 packed feature index
    threshold: jax.Array     # i32 bin threshold (left = bin <= threshold);
                             # for sorted-subset variants: prefix length - 1
    default_left: jax.Array  # bool — missing goes left
    is_categorical: jax.Array  # bool — any categorical variant
    variant: jax.Array       # i32 VAR_* of the winner
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_count: jax.Array
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_count: jax.Array


def _cumsum_bins(x: jax.Array, exact: bool) -> jax.Array:
    """Cumulative sum over the bin axis (last).

    ``exact=False`` (the speed modes — quantized levels make any
    summation order exact) rides an upper-triangular f32 MXU matmul:
    XLA lowers jnp.cumsum to reduce-window, which profiled at ~4.8
    ms/tree across the three split-scan cumsums at K=28 (round 4) while
    the [B, B] matmul is noise.  ``exact=True`` (float32 split-parity
    mode) keeps the sequential cumsum so CPU<->TPU dual parity stays
    bit-identical.  HIGHEST precision: bin sums are integer-valued in
    quantized mode and can exceed bf16's 2^8 mantissa."""
    if exact:
        return jnp.cumsum(x, axis=-1)
    b = x.shape[-1]
    tri = jnp.triu(jnp.ones((b, b), jnp.float32))
    return lax.dot_general(x, tri, (((x.ndim - 1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST)


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    """Soft-threshold (reference feature_histogram.hpp ThresholdL1)."""
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(g: jax.Array, h: jax.Array, l1: float, l2: float) -> jax.Array:
    t = threshold_l1(g, l1)
    return (t * t) / (h + l2 + 1e-15)


def leaf_output(g: jax.Array, h: jax.Array, l1: float, l2: float,
                max_delta_step: float = 0.0) -> jax.Array:
    """CalculateSplittedLeafOutput (feature_histogram.hpp static)."""
    out = -threshold_l1(g, l1) / (h + l2 + 1e-15)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def gain_given_output(g: jax.Array, h: jax.Array, out: jax.Array,
                      l1: float, l2: float) -> jax.Array:
    """GetLeafGainGivenOutput (feature_histogram.hpp): the split objective
    evaluated at an arbitrary (clipped / smoothed) output."""
    return -(2.0 * threshold_l1(g, l1) * out + (h + l2) * out * out)


def smoothed_output(g: jax.Array, h: jax.Array, n: jax.Array,
                    parent_output, l1: float, l2: float,
                    hp: "SplitHyper") -> jax.Array:
    """Leaf output with max_delta_step clipping and path smoothing toward the
    parent (feature_histogram.hpp CalculateSplittedLeafOutput USE_SMOOTHING:
    out' = (n*out + path_smooth*parent) / (n + path_smooth))."""
    out = leaf_output(g, h, l1, l2, hp.max_delta_step)
    if hp.path_smooth > 0.0:
        w = n / (n + hp.path_smooth)
        out = out * w + parent_output * (1.0 - w)
    return out


def parent_gain_shift(sum_g, sum_h, parent_output, hp: "SplitHyper"):
    """``parent gain + min_gain_to_split``: what a split's children have
    to beat.  The closed form g^2/(h+l2) is exact only when the output is
    the unconstrained optimum; smoothing / clipping force the evaluated
    form, at the parent's ACTUAL output (feature_histogram.hpp gain_shift:
    given-output under smoothing, clipped GetLeafGain under
    max_delta_step) — otherwise a clipped parent looks artificially good
    and no split ever clears it."""
    l1, l2 = hp.lambda_l1, hp.lambda_l2
    if hp.path_smooth > 0.0:
        parent_gain = gain_given_output(sum_g, sum_h, parent_output, l1, l2)
    elif hp.max_delta_step > 0.0:
        po = leaf_output(sum_g, sum_h, l1, l2, hp.max_delta_step)
        parent_gain = gain_given_output(sum_g, sum_h, po, l1, l2)
    else:
        parent_gain = leaf_gain(sum_g, sum_h, l1, l2)
    return parent_gain + hp.min_gain_to_split


def children_gain(gl, hl, nl, sum_g, sum_h, count, l2, parent_output,
                  hp: "SplitHyper") -> jax.Array:
    """Gain of the two children a candidate's left sums give (no monotone
    constraint), ``NEG_INF`` where a child falls under ``min_data_in_leaf``
    or ``min_sum_hessian_in_leaf``."""
    l1 = hp.lambda_l1
    gr, hr, nr = sum_g - gl, sum_h - hl, count - nl
    if hp.path_smooth > 0.0 or hp.max_delta_step > 0.0:
        lo = smoothed_output(gl, hl, nl, parent_output, l1, l2, hp)
        ro = smoothed_output(gr, hr, nr, parent_output, l1, l2, hp)
        gain = (gain_given_output(gl, hl, lo, l1, l2)
                + gain_given_output(gr, hr, ro, l1, l2))
    else:
        gain = leaf_gain(gl, hl, l1, l2) + leaf_gain(gr, hr, l1, l2)
    ok = ((nl >= hp.min_data_in_leaf) & (nr >= hp.min_data_in_leaf)
          & (hl >= hp.min_sum_hessian_in_leaf)
          & (hr >= hp.min_sum_hessian_in_leaf))
    return jnp.where(ok, gain, NEG_INF)


def cat_levels(num_bins: jax.Array, nan_bin: jax.Array,
               is_cat: jax.Array) -> jax.Array:
    """Bins that hold a level: ``num_bins`` without a categorical column's
    other bin (its last, where ``nan_bin >= 0``; io/binning.py).  What the
    one-hot / subset choice and every left set go by."""
    return num_bins - (is_cat & (nan_bin >= 0)).astype(num_bins.dtype)


def _take_rows(a: jax.Array, rows: Tuple[int, ...]) -> jax.Array:
    """Rows ``rows`` (static) of ``a``: static slices of runs of
    consecutive rows, joined; no gather."""
    runs, start = [], 0
    for i in range(1, len(rows) + 1):
        if i == len(rows) or rows[i] != rows[i - 1] + 1:
            runs.append(a[rows[start]:rows[i - 1] + 1])
            start = i
    if not runs:
        return a[:0]
    return runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=0)


def _spread_rows(a_s: jax.Array, rows: Tuple[int, ...], num_rows: int,
                 fill) -> jax.Array:
    """``_take_rows`` undone: ``a_s``'s rows back at ``rows`` of an array
    of ``num_rows`` rows, ``fill`` elsewhere; static slices, no scatter."""
    parts, at, k = [], 0, 0
    pad = lambda m: jnp.full((m,) + a_s.shape[1:], fill, a_s.dtype)
    while k < len(rows):
        j = k
        while j + 1 < len(rows) and rows[j + 1] == rows[j] + 1:
            j += 1
        if rows[k] > at:
            parts.append(pad(rows[k] - at))
        parts.append(a_s[k:j + 1])
        at, k = rows[j] + 1, j + 1
    if at < num_rows:
        parts.append(pad(num_rows - at))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _row_lookup(rows: Tuple[int, ...], num_rows: int):
    """Feature index -> its row among ``rows`` (0 for the others, whose
    subset candidates never win)."""
    table = [0] * num_rows
    for k, r in enumerate(rows):
        table[r] = k
    table = jnp.asarray(table, jnp.int32)
    return lambda f: table[f]


def sort_by_score(score: jax.Array, cand_bin: jax.Array, stats,
                  descending: bool):
    """One direction of the sorted-subset scan: ``stats`` (arrays
    ``[F, B]``, zero outside ``cand_bin``) and the bin index, sorted along
    the bin axis by ``score`` (falling where ``descending``), the
    candidate bins first.  ONE variadic stable sort that carries them with
    the key, ties by bin index as an argsort gives them: no argsort and no
    gather a payload.  Returns ``(*sorted stats, order i32 [F, B])``."""
    key = jnp.where(cand_bin, -score if descending else score,
                    jnp.float32(1e30))
    bins = jnp.broadcast_to(lax.iota(jnp.int32, key.shape[1])[None, :],
                            key.shape)
    return lax.sort((key,) + tuple(stats) + (bins,), dimension=1,
                    is_stable=True, num_keys=1)[1:]


def _best_variant(gains: dict):
    """``(best gain, its variant)`` elementwise over the live variants'
    gains ``{VAR_*: [F, B]}``; the lowest variant keeps a tie."""
    (v0, best), *rest = sorted(gains.items())
    var = jnp.full(best.shape, v0, jnp.int32)
    for v, gain in rest:
        better = gain > best
        best = jnp.where(better, gain, best)
        var = jnp.where(better, jnp.int32(v), var)
    return best, var


def _first_max(val: jax.Array, order: jax.Array, axis: int):
    """``(max of val, the lowest order among its maxima)`` along ``axis``:
    an argmax that hands on a position (i32 ``order``) other than the
    index along ``axis``."""
    def pick(a, b):
        (a_val, a_ord), (b_val, b_ord) = a, b
        take_b = (b_val > a_val) | ((b_val == a_val) & (b_ord < a_ord))
        return (jnp.where(take_b, b_val, a_val),
                jnp.where(take_b, b_ord, a_ord))
    return lax.reduce((val, order),
                      (jnp.array(-jnp.inf, val.dtype),
                       jnp.array(jnp.iinfo(order.dtype).max, order.dtype)),
                      pick, (axis,))


def find_best_split(hist: jax.Array, sum_g: jax.Array, sum_h: jax.Array,
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
    """Pick the best (feature, threshold, default-dir) for one leaf.

    hist: f32 [F, B, C>=3] (grad, hess, count); sum_g/sum_h/count: leaf totals.
    num_bins/nan_bin: i32 [F]; is_cat: bool [F]; feature_mask: bool [F] or None.
    monotone: i8/i32 [F] direction per feature (0 none; categorical features
    MUST be 0) when ``hp.use_monotone``; leaf_min/leaf_max: this leaf's output
    bounds (basic-method constraint entry); parent_output: this leaf's own
    output (path smoothing target); depth: leaf depth (monotone penalty).
    left_bins_out: a list the winner's set of LEFT bins (bool [B], all
    False for a numeric winner) is appended to in a job with categorical
    columns, read off the order the scan sorted its candidates into
    (``categorical_left_bitset`` sorts them again).  The subset scan's
    rows of ``hist`` are ``hp.cat_subset_cols``.
    """
    num_f, n_b = hist.shape[0], hist.shape[1]
    g, h, n = hist[..., 0], hist[..., 1], hist[..., 2]
    bin_idx = lax.iota(jnp.int32, n_b)[None, :]                  # [1, B]
    valid_bin = bin_idx < num_bins[:, None]                      # [F, B]
    is_nan = bin_idx == nan_bin[:, None]                         # [F, B]

    # base cumulatives exclude the missing bin; the missing-left variant adds
    # its stats
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
    # the live variants' gains, each [F, B]: a variant the job cannot have
    # (``hp``) has no array anywhere below
    gains = {
        VAR_NUM_RIGHT: jnp.where(thr_ok, variant_gain(
            gl, hl, nl, l2, bnds=adv_bounds), NEG_INF),
        VAR_NUM_LEFT: jnp.where(thr_ok & has_missing, variant_gain(
            gl + gm, hl + hm, nl + nm, l2, bnds=adv_bounds), NEG_INF)}

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
        gains[VAR_CAT_ONEHOT] = jnp.where(valid_bin & ~is_nan & onehot_ok,
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

            gains[VAR_CAT_FWD], gl_f, hl_f, nl_f, order_f = subset_scan(False)
            gains[VAR_CAT_BWD], gl_b, hl_b, nl_b, order_b = subset_scan(True)
            used_bin = spread(used_bin_s, 0)
            max_num_cat = spread(max_num_cat_s, 0)

    if hp.extra_trees and rng_key is not None:
        # extremely-randomized mode: per (feature, node) keep exactly ONE
        # random candidate threshold per variant family (reference
        # feature_histogram.cpp USE_RAND rand_threshold draws)
        kn, kc, ks = jax.random.split(rng_key, 3)
        keep = {}
        u_num = jax.random.uniform(kn, (num_f,))
        rand_num = jnp.floor(
            u_num * jnp.maximum(num_bins - 1, 1).astype(jnp.float32)
        ).astype(jnp.int32)
        keep[VAR_NUM_RIGHT] = keep[VAR_NUM_LEFT] = \
            bin_idx == rand_num[:, None]
        if hp.has_categorical:
            u_cat = jax.random.uniform(kc, (num_f,))
            rand_cat = jnp.floor(
                u_cat * num_bins.astype(jnp.float32)).astype(jnp.int32)
            keep[VAR_CAT_ONEHOT] = bin_idx == rand_cat[:, None]
        if sub != ():
            u_sub = jax.random.uniform(ks, (num_f,))
            max_thr = jnp.maximum(jnp.minimum(max_num_cat, used_bin) - 1, 0)
            rand_k = jnp.floor(
                u_sub * (max_thr + 1).astype(jnp.float32)).astype(jnp.int32)
            keep[VAR_CAT_FWD] = keep[VAR_CAT_BWD] = \
                bin_idx == rand_k[:, None]
        gains = {v: jnp.where(keep[v], c, NEG_INF) for v, c in gains.items()}

    # What is left to do to the candidates is per feature and keeps their
    # order, so the variants could be reduced first; but rounding can make
    # two of a bin's variants EQUAL (a penalty far above the gains), and
    # among equals the lowest variant wins.  So each variant takes it
    # before the reduction: elementwise on [F, B], fused into the one pass.
    if feature_mask is not None:
        gains = {v: jnp.where(feature_mask[:, None], c, NEG_INF)
                 for v, c in gains.items()}
    if gain_penalty is not None:
        # CEGB: per-feature acquisition cost subtracted from the split gain
        # before the argmax (cost_effective_gradient_boosting.hpp DeltaGain)
        gains = {v: jnp.where(c > NEG_INF / 2, c - gain_penalty[:, None], c)
                 for v, c in gains.items()}

    if per_feature_out is not None:
        # voting-parallel hook: per-feature best gain before the global
        # argmax (reference voting_parallel_tree_learner.cpp:344 votes on
        # per-feature local split gains)
        per_feature_out.append(
            jnp.max(_best_variant(gains)[0], axis=1) - min_shift)

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
        pen_f = jnp.where(monotone != 0, pen, 1.0)[:, None]

        def penalised(c, shift=min_shift):
            final = c - shift
            return jnp.where(final > 0, final * pen_f, NEG_INF)
        gains = {v: penalised(c) for v, c in gains.items()}
        min_shift = jnp.float32(0.0)

    # the winner: the first maximum in (feature, bin, variant) order.  No
    # array holds the variants side by side (84 children x 2,000 columns
    # x 256 bins x 5 variants were 0.86 GB a round pass, written with the
    # variants on the sublanes and converted twice before one argmax;
    # PERF.md section 6, PR 47): the variants are reduced elementwise, then
    # the bins of a feature along the lanes, then the features
    gain_b, var_b = _best_variant(gains)                         # [F, B]
    gain_f, place_f = _first_max(
        gain_b, bin_idx * NUM_VARIANTS + var_b, axis=1)          # [F]
    best_gain_raw, best = _first_max(
        gain_f, lax.iota(jnp.int32, num_f) * (n_b * NUM_VARIANTS) + place_f,
        axis=0)
    feat = best // (n_b * NUM_VARIANTS)
    rem = best % (n_b * NUM_VARIANTS)
    thr = rem // NUM_VARIANTS
    variant = rem % NUM_VARIANTS

    # the winner's left-side stats, by its variant
    g_t, h_t, n_t = gl[feat, thr], hl[feat, thr], nl[feat, thr]
    sums = {VAR_NUM_RIGHT: (g_t, h_t, n_t),
            VAR_NUM_LEFT: (g_t + gm[feat, 0], h_t + hm[feat, 0],
                           n_t + nm[feat, 0])}
    if hp.has_categorical:
        sums[VAR_CAT_ONEHOT] = (g[feat, thr], h[feat, thr], n[feat, thr])
    if sub != ():
        srow = row_of_feat(feat)     # the winner's row of the subset scan
        sums[VAR_CAT_FWD] = (gl_f[srow, thr], hl_f[srow, thr],
                             nl_f[srow, thr])
        sums[VAR_CAT_BWD] = (gl_b[srow, thr], hl_b[srow, thr],
                             nl_b[srow, thr])
    lg, lh, ln = sums.pop(VAR_NUM_RIGHT)
    for v, of_v in sums.items():
        lg, lh, ln = (jnp.where(variant == v, a, b)
                      for a, b in zip(of_v, (lg, lh, ln)))

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


def segment_sums(x: jax.Array, off: jax.Array, roff: jax.Array):
    """Segmented sums along the last (bin) axis: ``below[p]`` the sum from
    its segment's first position up to p, ``whole[p]`` its segment's sum.
    ``off`` / ``roff`` [Fb, B] are each position's distances from its
    segment's two ends (io/bundling.py ``bundle_ranges``): a doubling
    scan, log2(B) shifted adds, each taken where the partner lies inside
    the segment — sums never cross a segment, so a small member beside a
    large one keeps its own rounding."""
    width = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1)
    below, above, d = x, x, 1
    while d < width:
        below = jnp.where(off >= d, below + jnp.pad(
            below[..., :-d], pad + [(d, 0)]), below)
        above = jnp.where(roff >= d, above + jnp.pad(
            above[..., d:], pad + [(0, d)]), above)
        d *= 2
    return below, below + above - x


def find_best_split_ranges(hist_b: jax.Array, sum_g: jax.Array,
                           sum_h: jax.Array, count: jax.Array, search,
                           feature_mask: Optional[jax.Array],
                           hp: SplitHyper, parent_output=0.0) -> SplitResult:
    """``find_best_split`` for NUMERIC features without missing bins, on
    the PHYSICAL histogram ``hist_b`` [Fb, B, C] of EFB bundle columns
    (``search``: learner/grower.py ``BundleSearch``), never on the virtual
    ``[Fv, B, C]``: at Fv = 4,228 one-hot features that expansion is 84
    gathers of 17 MB a round pass for features of two bins each.

    A member's bins lie in one segment of its column in order, the
    default bin left out.  So the left sums of "virtual bin <= t" are the
    segment's prefix sum up to t's position, plus the default bin's mass
    (``leaf total - segment total``) where the default bin is <= t.  Each
    physical position p (member f, virtual bin v) stands for two
    candidate thresholds: t = v, and t = v - 1 where v - 1 is f's default
    bin, which has no position of its own.  Gains, the ``min_data`` /
    ``min_sum_hessian`` checks, ``lambda_l1/l2`` and the tie order (lowest
    virtual feature, then lowest threshold) are ``find_best_split``'s, and
    the result states the VIRTUAL feature and bin."""
    ghn = jnp.moveaxis(hist_b[..., :3], -1, 0)                  # [3, Fb, B]
    below, whole = segment_sums(ghn, search.off, search.roff)
    total = jnp.stack([sum_g, sum_h, count])[:, None, None]
    feat = jnp.maximum(search.feat_of, 0)
    live = search.feat_of >= 0
    v, skip = search.vbin_of, search.skip_of                    # [Fb, B]
    rest = total - whole                # the default bin's mass
    # t = v: the prefix, with the default bin's mass where it lies below v
    left_at = below + jnp.where(skip < v, rest, 0.0)
    # t = v - 1 = the default bin: everything below v, and the default
    left_dflt = below - ghn + rest
    ok_at = live & (v < search.last_of)
    ok_dflt = live & (skip == v - 1)
    if feature_mask is not None:
        allowed = feature_mask[feat]
        ok_at, ok_dflt = ok_at & allowed, ok_dflt & allowed
    l2 = hp.lambda_l2

    def gains(left, ok):
        return jnp.where(ok, children_gain(left[0], left[1], left[2], sum_g,
                                           sum_h, count, l2, parent_output,
                                           hp), NEG_INF)
    cand = jnp.stack([gains(left_dflt, ok_dflt), gains(left_at, ok_at)],
                     axis=-1)                                   # [Fb, B, 2]
    thr = jnp.stack([v - 1, v], axis=-1)
    n_b = hist_b.shape[1]
    # find_best_split's argmax takes the first of equal gains in
    # (feature, threshold) order: the same winner here
    order = feat[..., None] * n_b + thr
    best_gain_raw = jnp.max(cand)
    at = jnp.argmin(jnp.where(cand >= best_gain_raw, order,
                              jnp.iinfo(jnp.int32).max).reshape(-1))
    left = jnp.stack([left_dflt, left_at], axis=-1).reshape(3, -1)[:, at]
    min_shift = parent_gain_shift(sum_g, sum_h, parent_output, hp)
    return SplitResult(
        gain=jnp.where(best_gain_raw <= NEG_INF / 2, jnp.float32(NEG_INF),
                       best_gain_raw - min_shift),
        feature=jnp.broadcast_to(feat[..., None], thr.shape)
        .reshape(-1)[at].astype(jnp.int32),
        threshold=jnp.maximum(thr.reshape(-1)[at], 0).astype(jnp.int32),
        default_left=jnp.bool_(False), is_categorical=jnp.bool_(False),
        variant=jnp.int32(VAR_NUM_RIGHT),
        left_sum_g=left[0], left_sum_h=left[1], left_count=left[2],
        right_sum_g=sum_g - left[0], right_sum_h=sum_h - left[1],
        right_count=count - left[2])


def categorical_left_bitset(hist_f: jax.Array, num_bins_f: jax.Array,
                            variant: jax.Array, threshold: jax.Array,
                            hp: SplitHyper) -> jax.Array:
    """Materialize the set of bins going LEFT for a categorical split.

    hist_f: f32 [B, C] — the PARENT leaf's histogram of the split feature;
    num_bins_f: the feature's bins that hold a level (``cat_levels``: the
    other bin is never a candidate);
    variant/threshold: the winning ``SplitResult`` fields.  Returns bool [B].
    For one-hot the set is {threshold}; for sorted-subset it re-derives the
    score ordering (deterministic given the histogram) and takes the first
    ``threshold + 1`` bins of the winning direction — the device-side twin of
    the reference's ``output->cat_threshold`` bitset write
    (feature_histogram.cpp:354-377).
    """
    n_b = hist_f.shape[0]
    g, h, n = hist_f[..., 0], hist_f[..., 1], hist_f[..., 2]
    bin_idx = lax.iota(jnp.int32, n_b)
    cand = (bin_idx < num_bins_f) & (n >= hp.cat_smooth)
    score = g / (h + hp.cat_smooth)
    INF = jnp.float32(1e30)
    key_f = jnp.where(cand, score, INF)
    key_b = jnp.where(cand, -score, INF)
    order = jnp.where(variant == VAR_CAT_BWD, jnp.argsort(key_b),
                      jnp.argsort(key_f))
    rank = jnp.zeros((n_b,), jnp.int32).at[order].set(bin_idx)
    subset_bits = (rank <= threshold) & cand
    onehot_bits = bin_idx == threshold
    return jnp.where(variant == VAR_CAT_ONEHOT, onehot_bits, subset_bits)
