"""Leaf-wise (best-first) tree growth, fully on device.

TPU-native re-design of the reference single-device tree learner (reference:
src/treelearner/serial_tree_learner.cpp:179 ``Train`` and the CUDA blueprint
src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:158 — histogram →
subtract → best-split → partition per leaf).  Two deliberate departures:

  * The reference syncs ~1 SplitInfo device→host per split
    (cuda_single_gpu_tree_learner.cpp:276) — the latency bottleneck SURVEY.md
    §7 calls out.  Here the ENTIRE ``num_leaves - 1`` split loop runs inside
    one jitted ``lax.fori_loop``; early exit (no positive-gain split) becomes
    a sticky ``done`` flag that turns remaining iterations into no-ops.
  * The reference physically re-partitions row indices per split
    (cuda_data_partition.cu:288,907).  TPUs hate scatter, so rows never move:
    a dense ``leaf_of_row`` int32 map is updated with a masked ``where``, and
    per-leaf histograms mask through it.  The histogram-subtraction trick
    (serial_tree_learner.cpp:364-378) survives: only the SMALLER child gets a
    data pass, the sibling is parent − smaller.

Tree topology follows the reference array format (include/LightGBM/tree.h:26):
internal node i created at split i; left child keeps the parent's leaf index,
right child takes leaf index i+1; child pointers encode leaf l as ``-(l+1)``.

Under ``shard_map`` the same code runs data-parallel: histograms and root
stats are ``psum``-ed over the mesh axis, after which every device makes the
identical split decision — the TPU equivalent of the reference's
ReduceScatter/Allreduce dance (data_parallel_tree_learner.cpp:281,441).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import (bins_to_words, hist_dispatch,
                             histogram_for_leaf_masked, root_histogram)
from ..obs.metrics import count_event
from ..ops.split import (NEG_INF, VAR_CAT_BWD, VAR_CAT_FWD, VAR_CAT_ONEHOT,
                         VAR_NUM_RIGHT, SplitHyper, SplitResult,
                         cat_levels, categorical_left_bitset,
                         find_best_split,
                         find_best_split_ranges, leaf_gain, leaf_output,
                         smoothed_output)

_INF_BOUND = 3.0e38  # leaf-output bound sentinel (±"infinity" in f32)


class BundleSearch(NamedTuple):
    """A bundle plan as RANGES on device (io/bundling.py
    ``bundle_ranges``): what the split search, the fused partition and
    the matmul valid scorer need to stay in physical-bin space.  Per
    feature the segment ``[lo, hi]`` of its column and ``skip``, the
    default bin the segment leaves out; per physical position the member
    there (-1 none), its virtual bin, its member's ``skip`` and last
    threshold, and the position's distances from its segment's ends."""
    lo: jax.Array           # i32 [Fv]
    hi: jax.Array           # i32 [Fv]
    skip: jax.Array         # i32 [Fv]
    feat_of: jax.Array      # i32 [Fb, B]
    vbin_of: jax.Array      # i32 [Fb, B]
    skip_of: jax.Array      # i32 [Fb, B]
    last_of: jax.Array      # i32 [Fb, B] — num_bins - 1 of the member
    off: jax.Array          # i32 [Fb, B]
    roff: jax.Array         # i32 [Fb, B]


class DeviceBundle(NamedTuple):
    """EFB tables on device (io/bundling.py BundlePlan): the physical bin
    matrix / histograms cover bundle columns.  ``search`` states the
    layout as ranges; it is None where a member cannot be searched or
    routed by a range — a CATEGORICAL member (its left set is a bitset
    over virtual bins) or a member with a MISSING bin (the bin goes by
    ``default_left`` while the other members' positions go by the default
    bin's side: one flag cannot say both) — and those plans keep the
    expansion to virtual space (``_expand_hist``) and ``inv_table``."""
    feat_col: jax.Array     # i32 [Fv] — physical column of each feature
    src_idx: jax.Array      # i32 [Fv, B] — virtual bin -> bundle bin
    valid: jax.Array        # bool [Fv, B]
    default_bin: jax.Array  # i32 [Fv] — implicit most-frequent bin
    inv_table: jax.Array    # i32 [Fv, B] — bundle value -> virtual bin
    search: Optional[BundleSearch] = None


def searches_in_bundle_space(bundle: Optional[DeviceBundle], hp: SplitHyper,
                             penalised: bool = False) -> bool:
    """Whether a bundled job's split search stays on the physical
    ``[Fb, B]`` histogram (ops/split.py ``find_best_split_ranges``).  Not
    where the plan has no ranges (``DeviceBundle.search``), under
    monotone constraints (bounds per VIRTUAL feature and threshold),
    extra-trees (one random threshold per virtual feature) or a per-feature
    gain penalty (CEGB): those expand (``_expand_hist``)."""
    return (bundle is not None and bundle.search is not None
            and not hp.use_monotone and not hp.extra_trees
            and not penalised)


def split_ranges(feat: jax.Array, thr: jax.Array, dl: jax.Array,
                 nan_bin: jax.Array, bundle: Optional[DeviceBundle],
                 n_bins: int):
    """Splits ``(feature, bin threshold, default_left)`` as range
    predicates on the physical column: ``(column, lo, hi, pos,
    default_left, miss)``.  A row at ``miss``, the position of the
    feature's missing bin (-1 for none), goes by ``default_left``; any
    other goes left when ``lo <= c <= pos``, or when c lies outside
    ``[lo, hi]`` and ``default_left``.  An unbundled feature is the trivial
    range, the whole column with ``pos = thr``, and its missing bin sits
    wherever the bin mapper put it (last, or the zero bin under
    ``zero_as_missing``).  A bundle member's range is its segment; outside
    it the feature sits at its default bin, left when that bin is <= thr,
    and a plan with ranges has no missing bin."""
    if bundle is None:
        return (feat, jnp.zeros_like(feat), jnp.full_like(feat, n_bins - 1),
                thr, dl, nan_bin[feat])
    s = bundle.search
    below = (s.skip[feat] <= thr)
    return (bundle.feat_col[feat], s.lo[feat], s.hi[feat],
            s.lo[feat] + thr - below.astype(jnp.int32), below,
            jnp.full_like(feat, -1))


def _expand_hist(hist_b: jax.Array, bundle: DeviceBundle, sum_g, sum_h,
                 count) -> jax.Array:
    """Bundle-level leaf histogram [Fb, B, C] -> virtual [Fv, B, C].

    Each feature's stored bins are gathered from its bundle column; the
    implicit default bin is completed from the leaf totals (the reference's
    most-freq-bin completion, Dataset::FixHistogram dataset.h:760)."""
    count_event("bundle_expand_calls")      # when traced, not when run
    B = hist_b.shape[1]
    hv = hist_b[bundle.feat_col[:, None], bundle.src_idx]       # [Fv, B, C]
    hv = hv * bundle.valid[..., None]
    rest = jnp.sum(hv, axis=1)                                  # [Fv, C]
    total = jnp.stack([sum_g, sum_h, count,
                       jnp.zeros_like(count)])                  # [C]
    onehot = (lax.iota(jnp.int32, B)[None, :]
              == bundle.default_bin[:, None])                   # [Fv, B]
    return hv + onehot[..., None] * (total[None, None, :] - rest[:, None, :])


def _expand_hist_col(hcol: jax.Array, bundle: DeviceBundle,
                     feat: jax.Array, sum_g, sum_h, count) -> jax.Array:
    """One feature's virtual histogram [B, C] from its bundle COLUMN hist.

    The column must already be globally reduced (psum) before expansion when
    the totals are global — the default-bin completion is total − rest and
    mixing global totals with a local rest double-counts."""
    count_event("bundle_expand_calls")      # when traced, not when run
    hv = hcol[bundle.src_idx[feat]] * bundle.valid[feat][:, None]
    rest = jnp.sum(hv, axis=0)
    total = jnp.stack([sum_g, sum_h, count, jnp.zeros_like(count)])
    return hv.at[bundle.default_bin[feat]].add(total - rest)


def best_split_of_hist(h_phys, g_, h_, c_, num_bins, nan_bin, is_cat, fm,
                       hp: SplitHyper, bundle: Optional[DeviceBundle], *,
                       monotone=None, parent_output=0.0, leaf_min=None,
                       leaf_max=None, depth=None, rng_key=None,
                       gain_penalty=None, adv_bounds=None,
                       left_bins_out=None) -> SplitResult:
    """Best split of one leaf from its PHYSICAL histogram: in bundle
    space where the plan and the job allow it
    (``searches_in_bundle_space``), else ``find_best_split`` on the
    histogram itself (no bundles) or on its expansion to virtual space.
    ``left_bins_out`` is ``find_best_split``'s: a list that takes the
    winner's left bins (none in bundle space, which holds no categorical
    column)."""
    if searches_in_bundle_space(bundle, hp, gain_penalty is not None):
        with jax.named_scope("bundle_search"):
            return find_best_split_ranges(h_phys, g_, h_, c_, bundle.search,
                                          fm, hp,
                                          parent_output=parent_output)
    hv = h_phys if bundle is None else \
        _expand_hist(h_phys, bundle, g_, h_, c_)
    return find_best_split(hv, g_, h_, c_, num_bins, nan_bin, is_cat, fm, hp,
                           monotone=monotone, parent_output=parent_output,
                           leaf_min=leaf_min, leaf_max=leaf_max, depth=depth,
                           rng_key=rng_key, gain_penalty=gain_penalty,
                           adv_bounds=adv_bounds,
                           left_bins_out=left_bins_out)


def _feature_bin_of_rows(bins_t: jax.Array, bundle: Optional[DeviceBundle],
                         feat: jax.Array) -> jax.Array:
    """Virtual bin of every row for feature ``feat`` (partition step).
    ``bins_t`` is the TRANSPOSED [F, n] matrix so the dynamic column access
    is one contiguous row read, not an n-element strided gather."""
    if bundle is None:
        return jnp.take(bins_t, feat, axis=0).astype(jnp.int32)
    col = jnp.take(bins_t, bundle.feat_col[feat], axis=0).astype(jnp.int32)
    return bundle.inv_table[feat, col]


class TreeArrays(NamedTuple):
    """Struct-of-arrays tree (reference tree.h flat arrays)."""
    split_feature: jax.Array   # i32 [L-1] packed feature idx (-1 = unused node)
    split_bin: jax.Array       # i32 [L-1] bin threshold
    default_left: jax.Array    # bool [L-1]
    split_cat: jax.Array       # bool [L-1] one-hot categorical split
    left_child: jax.Array      # i32 [L-1]; >=0 node, negative -(leaf+1)
    right_child: jax.Array     # i32 [L-1]
    split_gain: jax.Array      # f32 [L-1]
    cat_bitset: jax.Array      # bool [L-1, B] — bins going left (cat splits)
    internal_value: jax.Array  # f32 [L-1] node output before split (SHAP)
    internal_count: jax.Array  # f32 [L-1]
    leaf_value: jax.Array      # f32 [L]
    leaf_count: jax.Array      # f32 [L]
    leaf_weight: jax.Array     # f32 [L] sum of hessians
    leaf_depth: jax.Array      # i32 [L]
    leaf_path: jax.Array       # bool [L, F] features on each leaf's path
    num_leaves: jax.Array      # i32 scalar — actual leaves grown


class CegbInput(NamedTuple):
    """Cost-Effective Gradient Boosting penalties + acquisition state
    (reference cost_effective_gradient_boosting.hpp): all pre-multiplied by
    cegb_tradeoff.  ``used_rows`` is None unless lazy penalties are set."""
    split_pen: jax.Array       # f32 scalar — cegb_penalty_split
    coupled_pen: jax.Array     # f32 [F] — once-per-feature penalty
    lazy_pen: jax.Array        # f32 [F] — per-(row,feature) penalty
    feature_used: jax.Array    # bool [F] — features already in the model
    used_rows: Optional[jax.Array]  # bool [n, F] — (row, feature) acquired


class _GrowState(NamedTuple):
    tree: TreeArrays
    leaf_of_row: jax.Array     # i32 [n]
    hist: jax.Array            # f32 [L, F, B, C]
    sum_g: jax.Array           # f32 [L]
    sum_h: jax.Array
    count: jax.Array
    best_gain: jax.Array       # f32 [L]
    best_feat: jax.Array       # i32 [L]
    best_thr: jax.Array
    best_dl: jax.Array         # bool [L]
    best_cat: jax.Array        # bool [L]
    best_var: jax.Array        # i32 [L] winning VAR_* variant
    best_lg: jax.Array         # f32 [L] left child sums of cached best split
    best_lh: jax.Array
    best_lc: jax.Array
    parent_node: jax.Array     # i32 [L] internal node owning this leaf (-1 root)
    parent_side: jax.Array     # i32 [L] 0 left / 1 right
    leaf_min: jax.Array        # f32 [L] output lower bound (monotone)
    leaf_max: jax.Array        # f32 [L] output upper bound
    leaf_lo: jax.Array         # i32 [L, F] bin-space box lower (intermediate
    leaf_hi: jax.Array         # i32 [L, F] monotone method; dummy [1,1] else)
    path_feats: jax.Array      # bool [L, F] features used on leaf's path
    force_failed: jax.Array    # bool scalar — forced-split BFS aborted
    done: jax.Array            # bool scalar
    cegb_used: jax.Array       # bool [F] (dummy [1] when CEGB off)
    cegb_rows: jax.Array       # bool [n, F] (dummy [1, 1] when off/no lazy)


def _empty_tree(num_leaves: int, n_bins: int, num_f: int) -> TreeArrays:
    li = num_leaves - 1
    return TreeArrays(
        split_feature=jnp.full((li,), -1, jnp.int32),
        split_bin=jnp.zeros((li,), jnp.int32),
        default_left=jnp.zeros((li,), bool),
        split_cat=jnp.zeros((li,), bool),
        left_child=jnp.full((li,), -1, jnp.int32),
        right_child=jnp.full((li,), -1, jnp.int32),
        split_gain=jnp.zeros((li,), jnp.float32),
        cat_bitset=jnp.zeros((li, n_bins), bool),
        internal_value=jnp.zeros((li,), jnp.float32),
        internal_count=jnp.zeros((li,), jnp.float32),
        leaf_value=jnp.zeros((num_leaves,), jnp.float32),
        leaf_count=jnp.zeros((num_leaves,), jnp.float32),
        leaf_weight=jnp.zeros((num_leaves,), jnp.float32),
        leaf_depth=jnp.zeros((num_leaves,), jnp.int32),
        leaf_path=jnp.zeros((num_leaves, num_f), bool),
        num_leaves=jnp.int32(1),
    )


def gather_forced_split(hf: jax.Array, pg, ph, pc, ft, is_cat_f, nan_bin_f,
                        hp: SplitHyper):
    """Stats/validity of a PRESCRIBED split from a leaf's histogram column
    (reference FeatureHistogram::GatherInfoForThreshold, invoked by
    ForceSplits serial_tree_learner.cpp:620).  ``hf``: f32 [B, C] expanded
    histogram of the forced feature.  Returns (lg, lh, lc, gain, ok) —
    the SINGLE implementation shared by the strict and batched learners.
    """
    b_i = lax.iota(jnp.int32, hp.n_bins)
    lm = jnp.where(is_cat_f, b_i == ft, (b_i <= ft) & (b_i != nan_bin_f))
    lmf = lm.astype(hf.dtype)
    lg = jnp.sum(hf[:, 0] * lmf)
    lh = jnp.sum(hf[:, 1] * lmf)
    lc = jnp.sum(hf[:, 2] * lmf)
    rg, rh, rc = pg - lg, ph - lh, pc - lc
    gain = (leaf_gain(lg, lh, hp.lambda_l1, hp.lambda_l2)
            + leaf_gain(rg, rh, hp.lambda_l1, hp.lambda_l2)
            - leaf_gain(pg, ph, hp.lambda_l1, hp.lambda_l2)
            - hp.min_gain_to_split)
    ok = ((lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf)
          & (lh >= hp.min_sum_hessian_in_leaf)
          & (rh >= hp.min_sum_hessian_in_leaf) & (gain > 0.0))
    return lg, lh, lc, gain, ok


def sample_features_bynode(mask: Optional[jax.Array], key: jax.Array,
                           frac: float, num_f: int) -> jax.Array:
    """Random per-node feature subset (reference col_sampler.hpp
    feature_fraction_bynode): keep ceil-ish frac of the allowed features,
    uniformly.  SINGLE implementation shared by the strict and batched
    growers so their sampling stays bit-identical."""
    base = jnp.ones((num_f,), bool) if mask is None else mask
    u = jax.random.uniform(key, (num_f,))
    u = jnp.where(base, u, -1.0)
    cnt = jnp.maximum((base.sum() * frac).astype(jnp.int32), 1)
    kth = jnp.sort(u)[num_f - cnt]
    return base & (u >= kth) & (u >= 0)


def pv_vote_best_split(h_phys, g_, h_, c_, depth, fm, parent_output, lmin,
                       lmax, key, *, hp, hp_vote, num_bins, nan_bin, is_cat,
                       monotone, bundle, num_f, top_k, axis_name
                       ) -> "SplitResult":
    """PV-Tree two-phase vote for ONE leaf (reference
    voting_parallel_tree_learner.cpp:151 GlobalVoting + :184
    CopyLocalHistogram), shared by the strict grower's voting branch and
    the batched grower's rounds so the protocol has one definition.

    ``h_phys`` is the leaf's LOCAL shard histogram; ``g_/h_/c_`` are the
    GLOBAL leaf totals.  Phase 1 scores every feature on the local
    histogram at the 1/num_shards-relaxed thresholds in ``hp_vote``;
    phase 2 psums each shard's top-``top_k`` proposals into a vote,
    reduces ONLY the 2·top_k winners' histogram slices globally, and
    finds the split there.  Returned ``feature`` is the global index and
    the gain carries the depth gate."""
    from ..ops.split import find_best_split as _fbs
    lg_ = jnp.sum(h_phys[0, :, 0])
    lh_ = jnp.sum(h_phys[0, :, 1])
    lc_ = jnp.sum(h_phys[0, :, 2])
    hv_local = h_phys if bundle is None else \
        _expand_hist(h_phys, bundle, lg_, lh_, lc_)
    pf: list = []
    _fbs(hv_local, lg_, lh_, lc_, num_bins, nan_bin, is_cat, fm, hp_vote,
         monotone=monotone, parent_output=parent_output, leaf_min=lmin,
         leaf_max=lmax, depth=depth, rng_key=key, per_feature_out=pf)
    gains_local = pf[0]                                        # [F]
    k = min(top_k, num_f)
    _, local_top = lax.top_k(gains_local, k)
    votes = lax.psum(jnp.zeros((num_f,), jnp.float32)
                     .at[local_top].set(1.0), axis_name)
    gain_sum = lax.psum(jnp.clip(gains_local, -1e9, 1e9), axis_name)
    score = votes * 1e12 + gain_sum
    sel_k = min(2 * top_k, num_f)
    _, sel = lax.top_k(score, sel_k)                           # [2k]
    h_sel = lax.psum(hv_local[sel], axis_name)                 # [2k, B, C]
    # a selection of the features: the job's list of subset columns
    # names other rows
    res = _fbs(h_sel, g_, h_, c_, num_bins[sel], nan_bin[sel], is_cat[sel],
               None if fm is None else fm[sel],
               dataclasses.replace(hp, cat_subset_cols=None),
               monotone=None if monotone is None else monotone[sel],
               parent_output=parent_output, leaf_min=lmin, leaf_max=lmax,
               depth=depth, rng_key=key)
    res = res._replace(feature=sel[res.feature])
    depth_ok = (hp.max_depth <= 0) | (depth < hp.max_depth)
    from ..ops.split import NEG_INF as _NEG_INF
    return res._replace(gain=jnp.where(depth_ok, res.gain, _NEG_INF))


def _child_best(hist: jax.Array, g: jax.Array, h: jax.Array, c: jax.Array,
                depth: jax.Array, num_bins, nan_bin, is_cat, feature_mask,
                hp: SplitHyper, monotone=None, parent_output=0.0,
                leaf_min=None, leaf_max=None, rng_key=None) -> SplitResult:
    res = find_best_split(hist, g, h, c, num_bins, nan_bin, is_cat,
                          feature_mask, hp, monotone=monotone,
                          parent_output=parent_output, leaf_min=leaf_min,
                          leaf_max=leaf_max, depth=depth, rng_key=rng_key)
    depth_ok = (hp.max_depth <= 0) | (depth < hp.max_depth)
    return res._replace(gain=jnp.where(depth_ok, res.gain, NEG_INF))


@functools.partial(jax.jit, static_argnames=("hp", "axis_name",
                                             "parallel_mode", "top_k",
                                             "num_shards"))
def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              row_mask: Optional[jax.Array], num_bins: jax.Array,
              nan_bin: jax.Array, is_cat: jax.Array,
              feature_mask: Optional[jax.Array], hp: SplitHyper,
              axis_name: Optional[str] = None,
              monotone: Optional[jax.Array] = None,
              rng_key: Optional[jax.Array] = None,
              interaction_sets: Optional[jax.Array] = None,
              forced: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
              bundle: Optional[DeviceBundle] = None,
              parallel_mode: str = "data", top_k: int = 20,
              num_shards: int = 1,
              cegb: Optional[CegbInput] = None,
              hist_scale: Optional[jax.Array] = None,
              bins_words: Optional[jax.Array] = None):
    """Grow one tree; returns (TreeArrays, leaf_of_row).

    bins: uint8 [n, F]; grad/hess: f32 [n]; row_mask: bool [n] or None
    (bagging); num_bins/nan_bin: i32 [F]; is_cat: bool [F];
    feature_mask: bool [F] or None (feature_fraction).
    rng_key: PRNG key for per-node feature sampling / extra_trees (must be
    identical on all shards under shard_map).  interaction_sets: bool [S, F]
    allowed-together feature sets (reference col_sampler.hpp:91 GetByNode —
    a leaf may only split on features from sets containing its whole path).
    forced: (leaf, feature, bin_threshold) i32 [L-1] arrays (−1 padded) —
    host-precomputed BFS order of forcedsplits_filename JSON (reference
    serial_tree_learner.cpp:620 ForceSplits); a forced entry that fails
    validity (min_data / non-positive gain) aborts the remaining schedule,
    mirroring the reference's ignore-with-warning.
    ``leaf_of_row`` is returned for ALL rows (bagged-out rows included), so the
    boosting score update is a pure gather — the reference's train-score
    shortcut through DataPartition (score_updater.hpp).

    ``bundle``: EFB tables (io/bundling.py).  When set, ``bins`` holds the
    BUNDLED physical columns; histograms are built per bundle and expanded to
    per-feature space only for split finding.

    ``parallel_mode`` selects the distributed strategy under ``axis_name``
    (SURVEY.md §2.7; all three reference parallel learners):
      * "data"    — rows sharded; full-histogram psum (the reference's
                    ReduceScatter+Allreduce dataflow).
      * "voting"  — rows sharded; PV-Tree 2-phase vote: each shard proposes
                    its local top-``top_k`` features by gain, the vote picks
                    2·top_k candidates, and ONLY their histogram slices are
                    psum-ed (voting_parallel_tree_learner.cpp:151,184 —
                    O(top_k·bins) comm, independent of feature count).
                    ``num_shards`` must equal the mesh axis size; local
                    validity thresholds are scaled by 1/num_shards (:62-64).
      * "feature" — FEATURES sharded (bins/num_bins/... hold this shard's
                    columns; every shard holds ALL rows): local best split,
                    cross-shard argmax sync, owner broadcasts the partition
                    (feature_parallel_tree_learner.cpp:62-79
                    SyncUpGlobalBestSplit).  EFB/monotone/forced/interaction
                    are not supported in this mode.
    """
    n = bins.shape[0]
    num_f = bins.shape[1] if bundle is None else bundle.feat_col.shape[0]
    L = hp.num_leaves
    mask_f = jnp.ones_like(grad) if row_mask is None else row_mask.astype(grad.dtype)
    mode = parallel_mode if axis_name is not None else "data"
    if mode == "feature" and axis_name is not None:
        assert bundle is None and forced is None and monotone is None \
            and interaction_sets is None, \
            "feature-parallel composes only with the core split path"
    if cegb is not None:
        assert axis_name is None or mode == "data", \
            "CEGB composes with serial/data-parallel modes only"

    def cegb_penalty(used_f, used_rows, leaf_mask, leaf_count):
        """Per-feature gain penalty for one leaf (CEGB DeltaGain:
        split_pen scales with the leaf's data count)."""
        pen = cegb.split_pen * leaf_count \
            + jnp.where(used_f, 0.0, cegb.coupled_pen)
        if cegb.used_rows is not None:
            cnt = jnp.einsum("n,nf->f", leaf_mask.astype(jnp.float32),
                             (~used_rows).astype(jnp.float32))
            if axis_name is not None:
                cnt = lax.psum(cnt, axis_name)
            pen = pen + cegb.lazy_pen * cnt
        return pen
    # axis passed to histogram builders: only the data mode psums full hists
    hist_axis = axis_name if mode == "data" else None

    use_bynode = hp.feature_fraction_bynode < 1.0 and rng_key is not None

    def node_feature_mask(path_f: jax.Array, key) -> Optional[jax.Array]:
        """Per-node allowed features: tree-level mask ∧ interaction
        constraints ∧ by-node random subset."""
        m = feature_mask
        if interaction_sets is not None:
            fits = jnp.all(interaction_sets | ~path_f[None, :], axis=1)  # [S]
            allowed = jnp.any(interaction_sets & fits[:, None],
                              axis=0) | path_f
            m = allowed if m is None else (m & allowed)
        if use_bynode:
            m = sample_features_bynode(m, key, hp.feature_fraction_bynode,
                                       num_f)
        return m

    # transposed layout once per tree: the histogram kernel and the
    # partition column reads both want rows on the minor (lane) dimension.
    # optimization_barrier forces ONE materialization — without it XLA
    # rematerializes the 28-byte-strided transpose inside every split
    # iteration (measured 2.5x on the whole tree loop)
    bins_t = lax.optimization_barrier(bins.T)
    # packed-word mirror for the round-6 packed histogram mode (kept
    # resident per tree like bins_t; ``bins_words`` lets the booster ship
    # the dataset's construction-time mirror instead of re-deriving it)
    if hist_dispatch(hp.hist_kernel, hp.n_bins).mirror:
        words_t = lax.optimization_barrier(
            (bins_to_words(bins) if bins_words is None else bins_words).T)
    else:
        words_t = None
    # quantized-levels mode (ops/quantize.py): grad/hess hold integer
    # levels; one deterministic multiply restores real units right after
    # each exact integer histogram accumulation
    scale_vec = None
    if hist_scale is not None:
        scale_vec = jnp.concatenate(
            [hist_scale.astype(jnp.float32), jnp.ones((2,), jnp.float32)])

    def _scaled(h):
        return h if scale_vec is None else h * scale_vec

    hist0_b = _scaled(root_histogram(
        bins_t, grad, hess, row_mask, n_bins=hp.n_bins,
        rows_per_block=hp.rows_per_block,
        hist_dtype=hp.hist_dtype, axis_name=hist_axis,
        hist_kernel=hp.hist_kernel, bins_words_t=words_t))
    g0 = jnp.sum(grad * mask_f)
    h0 = jnp.sum(hess * mask_f)
    c0 = jnp.sum(mask_f)
    if hist_scale is not None:
        g0 = g0 * hist_scale[0]
        h0 = h0 * hist_scale[1]
    if axis_name is not None and mode != "feature":
        # feature mode holds ALL rows on every shard: sums already global
        # one [3]-vector psum, not three scalar collectives
        g0, h0, c0 = lax.psum(jnp.stack([g0, h0, c0]), axis_name)

    if mode == "voting" and axis_name is not None:
        # locally relaxed validity thresholds
        # (voting_parallel_tree_learner.cpp:62-64)
        hp_vote = dataclasses.replace(
            hp, min_data_in_leaf=max(1, hp.min_data_in_leaf // num_shards),
            min_sum_hessian_in_leaf=hp.min_sum_hessian_in_leaf / num_shards)

    def child_best(h_phys, g_, h_, c_, depth, fm, parent_output, lmin, lmax,
                   key, pen=None, adv=None) -> SplitResult:
        """Best split for one leaf from its PHYSICAL (bundle-column)
        histogram — local shard hist under voting/feature modes, global
        otherwise.  Returns a SplitResult whose ``feature`` is the virtual
        (voting) / global (feature-parallel) index."""
        if mode == "voting" and axis_name is not None:
            return pv_vote_best_split(
                h_phys, g_, h_, c_, depth, fm, parent_output, lmin, lmax,
                key, hp=hp, hp_vote=hp_vote, num_bins=num_bins,
                nan_bin=nan_bin, is_cat=is_cat, monotone=monotone,
                bundle=bundle, num_f=num_f, top_k=top_k,
                axis_name=axis_name)
        if mode == "feature" and axis_name is not None:
            res = _child_best(h_phys, g_, h_, c_, depth, num_bins, nan_bin,
                              is_cat, fm, hp, parent_output=parent_output,
                              leaf_min=lmin, leaf_max=lmax, rng_key=key)
            # cross-shard best-split argmax (SyncUpGlobalBestSplit,
            # feature_parallel_tree_learner.cpp:62-79): gather the packed
            # candidate of every shard, keep the best, globalize the index
            rank = lax.axis_index(axis_name)
            gfeat = res.feature + rank * num_f
            packed = jnp.stack([
                res.gain, gfeat.astype(jnp.float32),
                res.threshold.astype(jnp.float32),
                res.default_left.astype(jnp.float32),
                res.is_categorical.astype(jnp.float32),
                res.variant.astype(jnp.float32),
                res.left_sum_g, res.left_sum_h, res.left_count,
                res.right_sum_g, res.right_sum_h, res.right_count])
            allp = lax.all_gather(packed, axis_name)           # [d, 12]
            b = allp[jnp.argmax(allp[:, 0])]
            return SplitResult(
                gain=b[0], feature=b[1].astype(jnp.int32),
                threshold=b[2].astype(jnp.int32),
                default_left=b[3] > 0.5, is_categorical=b[4] > 0.5,
                variant=b[5].astype(jnp.int32),
                left_sum_g=b[6], left_sum_h=b[7], left_count=b[8],
                right_sum_g=b[9], right_sum_h=b[10], right_count=b[11])
        res = best_split_of_hist(h_phys, g_, h_, c_, num_bins, nan_bin,
                                 is_cat, fm, hp, bundle, monotone=monotone,
                                 parent_output=parent_output, leaf_min=lmin,
                                 leaf_max=lmax, depth=depth, rng_key=key,
                                 gain_penalty=pen, adv_bounds=adv)
        depth_ok = (hp.max_depth <= 0) | (depth < hp.max_depth)
        return res._replace(gain=jnp.where(depth_ok, res.gain, NEG_INF))

    root_out = leaf_output(g0, h0, hp.lambda_l1, hp.lambda_l2,
                           hp.max_delta_step)
    inf = jnp.float32(_INF_BOUND)
    empty_path = jnp.zeros((num_f,), bool)
    if rng_key is not None:
        key_root, key_er = jax.random.split(jax.random.fold_in(rng_key, L))
    else:
        key_root = key_er = None
    fm_root = node_feature_mask(empty_path, key_root)
    if cegb is not None:
        cegb_used0 = cegb.feature_used
        cegb_rows0 = cegb.used_rows if cegb.used_rows is not None \
            else jnp.zeros((1, 1), bool)
        pen0 = cegb_penalty(cegb_used0, cegb_rows0, mask_f, c0)
    else:
        cegb_used0 = jnp.zeros((1,), bool)
        cegb_rows0 = jnp.zeros((1, 1), bool)
        pen0 = None
    best0 = child_best(hist0_b, g0, h0, c0, jnp.int32(0), fm_root,
                       root_out, -inf, inf, key_er, pen=pen0)

    use_boxes = hp.use_monotone and hp.monotone_method in ("intermediate", "advanced")
    use_adv = hp.use_monotone and hp.monotone_method == "advanced"
    tree = _empty_tree(L, hp.n_bins, num_f)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(root_out),
        leaf_count=tree.leaf_count.at[0].set(c0),
        leaf_weight=tree.leaf_weight.at[0].set(h0),
    )
    C = hist0_b.shape[-1]
    n_cols = bins.shape[1]  # physical histogram columns (== num_f unbundled)
    state = _GrowState(
        tree=tree,
        leaf_of_row=jnp.zeros((n,), jnp.int32),
        hist=jnp.zeros((L, n_cols, hp.n_bins, C),
                       jnp.float32).at[0].set(hist0_b),
        sum_g=jnp.zeros((L,), jnp.float32).at[0].set(g0),
        sum_h=jnp.zeros((L,), jnp.float32).at[0].set(h0),
        count=jnp.zeros((L,), jnp.float32).at[0].set(c0),
        best_gain=jnp.full((L,), NEG_INF, jnp.float32).at[0].set(best0.gain),
        best_feat=jnp.zeros((L,), jnp.int32).at[0].set(best0.feature),
        best_thr=jnp.zeros((L,), jnp.int32).at[0].set(best0.threshold),
        best_dl=jnp.zeros((L,), bool).at[0].set(best0.default_left),
        best_cat=jnp.zeros((L,), bool).at[0].set(best0.is_categorical),
        best_var=jnp.zeros((L,), jnp.int32).at[0].set(best0.variant),
        best_lg=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_sum_g),
        best_lh=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_sum_h),
        best_lc=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_count),
        parent_node=jnp.full((L,), -1, jnp.int32),
        parent_side=jnp.zeros((L,), jnp.int32),
        leaf_min=jnp.full((L,), -_INF_BOUND, jnp.float32),
        leaf_max=jnp.full((L,), _INF_BOUND, jnp.float32),
        leaf_lo=(jnp.zeros((L, num_f), jnp.int32)
                 if use_boxes else jnp.zeros((1, 1), jnp.int32)),
        leaf_hi=(jnp.zeros((L, num_f), jnp.int32)
                 .at[0].set(num_bins.astype(jnp.int32))
                 if use_boxes else jnp.zeros((1, 1), jnp.int32)),
        path_feats=jnp.zeros((L, num_f), bool),
        force_failed=jnp.bool_(False),
        done=jnp.bool_(False),
        cegb_used=cegb_used0,
        cegb_rows=cegb_rows0,
    )

    def body(i, st: _GrowState) -> _GrowState:
        bl = jnp.argmax(st.best_gain).astype(jnp.int32)
        feat = st.best_feat[bl]
        thr = st.best_thr[bl]
        dl = st.best_dl[bl]
        catl = st.best_cat[bl]
        var = st.best_var[bl]
        gain_rec = st.best_gain[bl]
        ch_lg, ch_lh, ch_lc = st.best_lg[bl], st.best_lh[bl], st.best_lc[bl]
        do = (~st.done) & (gain_rec > 0.0)

        if forced is not None:
            f_leaf, f_feat, f_thr = forced
            f_active = (f_leaf[i] >= 0) & ~st.force_failed & ~st.done
            fl = jnp.maximum(f_leaf[i], 0)
            ff, ft = f_feat[i], f_thr[i]
            hf_col = st.hist[fl, ff if bundle is None
                             else bundle.feat_col[ff]]         # [B, C]
            if mode == "voting" and axis_name is not None:
                hf_col = lax.psum(hf_col, axis_name)  # local -> global
            hf = hf_col if bundle is None else \
                _expand_hist_col(hf_col, bundle, ff, st.sum_g[fl],
                                 st.sum_h[fl], st.count[fl])
            pgf, phf, pcf = st.sum_g[fl], st.sum_h[fl], st.count[fl]
            lgf, lhf, lcf, gf, ok_f = gather_forced_split(
                hf, pgf, phf, pcf, ft, is_cat[ff], nan_bin[ff], hp)
            use_f = f_active & ok_f
            st = st._replace(force_failed=st.force_failed
                             | (f_active & ~ok_f))
            bl = jnp.where(use_f, fl, bl)
            feat = jnp.where(use_f, ff, feat)
            thr = jnp.where(use_f, ft, thr)
            dl = jnp.where(use_f, False, dl)
            catl = jnp.where(use_f, is_cat[ff], catl)
            var = jnp.where(use_f,
                            jnp.where(is_cat[ff], VAR_CAT_ONEHOT,
                                      VAR_NUM_RIGHT), var)
            gain_rec = jnp.where(use_f, gf, gain_rec)
            ch_lg = jnp.where(use_f, lgf, st.best_lg[bl])
            ch_lh = jnp.where(use_f, lhf, st.best_lh[bl])
            ch_lc = jnp.where(use_f, lcf, st.best_lc[bl])
            do = (~st.done) & (use_f | (st.best_gain[bl] > 0.0))

        def no_split(st: _GrowState) -> _GrowState:
            return st._replace(done=jnp.bool_(True))

        def split(st: _GrowState) -> _GrowState:
            t = st.tree
            new_leaf = i + 1

            # feature-parallel: locate the owning shard of the winning
            # (global) feature; only it holds the column/histogram
            if mode == "feature" and axis_name is not None:
                rank = lax.axis_index(axis_name)
                f_local = feat - rank * num_f
                owns = (f_local >= 0) & (f_local < num_f)
                f_safe = jnp.clip(f_local, 0, num_f - 1)
            else:
                owns = jnp.bool_(True)
                f_safe = feat

            # left-category bitset, derived from the PARENT histogram (still
            # at st.hist[bl] at this point)
            if hp.has_categorical:
                pf_col = st.hist[bl, f_safe if bundle is None
                                 else bundle.feat_col[f_safe]]
                if mode == "voting" and axis_name is not None:
                    pf_col = lax.psum(pf_col, axis_name)
                hist_pf = pf_col if bundle is None else \
                    _expand_hist_col(pf_col, bundle, f_safe,
                                     st.sum_g[bl], st.sum_h[bl],
                                     st.count[bl])
                bitset = categorical_left_bitset(
                    hist_pf, cat_levels(num_bins, nan_bin, is_cat)[f_safe],
                    var, thr, hp)
                if mode == "feature" and axis_name is not None:
                    # owner broadcasts its bitset
                    bitset = lax.psum(
                        jnp.where(owns, bitset.astype(jnp.float32), 0.0),
                        axis_name) > 0.5
                bitset = bitset & catl
            else:
                bitset = jnp.zeros((hp.n_bins,), bool)

            # -- link the parent's child pointer to the new internal node i
            p = st.parent_node[bl]
            side = st.parent_side[bl]
            ps = jnp.maximum(p, 0)
            lc_arr = t.left_child.at[ps].set(
                jnp.where((p >= 0) & (side == 0), i, t.left_child[ps]))
            rc_arr = t.right_child.at[ps].set(
                jnp.where((p >= 0) & (side == 1), i, t.right_child[ps]))

            # -- record split at internal node i
            pg, ph, pc = st.sum_g[bl], st.sum_h[bl], st.count[bl]
            lc_arr = lc_arr.at[i].set(-(bl + 1))
            rc_arr = rc_arr.at[i].set(-(new_leaf + 1))
            t = t._replace(
                split_feature=t.split_feature.at[i].set(feat),
                split_bin=t.split_bin.at[i].set(thr),
                default_left=t.default_left.at[i].set(dl),
                split_cat=t.split_cat.at[i].set(catl),
                left_child=lc_arr, right_child=rc_arr,
                split_gain=t.split_gain.at[i].set(gain_rec),
                cat_bitset=t.cat_bitset.at[i].set(bitset),
                internal_value=t.internal_value.at[i].set(
                    leaf_output(pg, ph, hp.lambda_l1, hp.lambda_l2,
                                hp.max_delta_step)),
                internal_count=t.internal_count.at[i].set(pc),
                num_leaves=jnp.int32(i + 2),
            )

            # -- partition (dense map update, no data movement); under
            # feature-parallel only the owner has the column, so its go-left
            # vector is broadcast (the reference instead re-splits from the
            # synced SplitInfo since every rank holds all features' data —
            # here columns are truly sharded, so one [n] psum replaces it)
            col = _feature_bin_of_rows(bins_t, bundle, f_safe)
            nb = nan_bin[f_safe]
            go_left_num = jnp.where(col == nb, dl, col <= thr)
            # bitset[col] is an n-row table gather — skip it entirely on
            # all-numeric datasets (gathers are the slowest TPU primitive)
            go_left = jnp.where(catl, bitset[col], go_left_num) \
                if hp.has_categorical else go_left_num
            if mode == "feature" and axis_name is not None:
                go_left = lax.psum(
                    jnp.where(owns, go_left.astype(jnp.float32), 0.0),
                    axis_name) > 0.5
            active = st.leaf_of_row == bl
            leaf_of_row = jnp.where(
                active, jnp.where(go_left, bl, new_leaf), st.leaf_of_row)

            # -- children stats from the cached best split (or forced gather)
            lg, lh, lcn = ch_lg, ch_lh, ch_lc
            rg, rh, rcn = pg - lg, ph - lh, pc - lcn

            # -- children outputs: variant-dependent l2 (sorted-subset adds
            # cat_l2, feature_histogram.cpp:250), path smoothing toward the
            # parent, monotone [min,max] clipping (basic method)
            l2_eff = hp.lambda_l2 + jnp.where(
                (var == VAR_CAT_FWD) | (var == VAR_CAT_BWD), hp.cat_l2, 0.0)
            parent_out = t.leaf_value[bl]
            lo = smoothed_output(lg, lh, lcn, parent_out, hp.lambda_l1,
                                 l2_eff, hp)
            ro = smoothed_output(rg, rh, rcn, parent_out, hp.lambda_l1,
                                 l2_eff, hp)
            lmin_p, lmax_p = st.leaf_min[bl], st.leaf_max[bl]
            # use_boxes closes over grow_tree's definition — keep ONE source
            if hp.use_monotone:
                lo = jnp.clip(lo, lmin_p, lmax_p)
                ro = jnp.clip(ro, lmin_p, lmax_p)
            if hp.use_monotone and use_boxes:
                # sibling-ordering repair: clipping both children to the
                # parent's [min, max] can leave out[left] > out[right] under
                # mono>0 (or the mirror) when the raw outputs were inverted
                # but clipped equal at evaluation time; the box refresh below
                # bounds OTHER leaves but not this pair's relative order, so
                # collapse inverted siblings to their midpoint like the basic
                # method's swap (monotone_constraints.hpp BasicLeafConstraints)
                mono_sf = monotone[feat]
                inv = (~catl) & (((mono_sf > 0) & (lo > ro))
                                 | ((mono_sf < 0) & (lo < ro)))
                mid_sib = jnp.clip((lo + ro) * 0.5, lmin_p, lmax_p)
                lo = jnp.where(inv, mid_sib, lo)
                ro = jnp.where(inv, mid_sib, ro)
            if hp.use_monotone and not use_boxes:
                mono_f = monotone[feat]
                is_num = ~catl
                mid = (lo + ro) * 0.5
                lmax_l = jnp.where(is_num & (mono_f > 0),
                                   jnp.minimum(lmax_p, mid), lmax_p)
                lmin_l = jnp.where(is_num & (mono_f < 0),
                                   jnp.maximum(lmin_p, mid), lmin_p)
                lmin_r = jnp.where(is_num & (mono_f > 0),
                                   jnp.maximum(lmin_p, mid), lmin_p)
                lmax_r = jnp.where(is_num & (mono_f < 0),
                                   jnp.minimum(lmax_p, mid), lmax_p)
            else:
                lmin_l = lmin_r = lmin_p
                lmax_l = lmax_r = lmax_p

            # -- histogram: one masked data pass for the smaller child,
            # subtract for the sibling
            smaller = jnp.where(lcn <= rcn, bl, new_leaf)
            h_small = _scaled(histogram_for_leaf_masked(
                bins_t, grad, hess, leaf_of_row, smaller, row_mask,
                n_bins=hp.n_bins, rows_per_block=hp.rows_per_block,
                hist_dtype=hp.hist_dtype, axis_name=hist_axis,
                hist_kernel=hp.hist_kernel, bins_words_t=words_t))
            h_parent = st.hist[bl]
            h_large = h_parent - h_small
            left_small = lcn <= rcn
            h_left = jnp.where(left_small, h_small, h_large)
            h_right = jnp.where(left_small, h_large, h_small)
            hist = st.hist.at[bl].set(h_left).at[new_leaf].set(h_right)

            d = t.leaf_depth[bl] + 1
            t = t._replace(
                leaf_depth=t.leaf_depth.at[bl].set(d).at[new_leaf].set(d),
                leaf_value=t.leaf_value.at[bl].set(lo).at[new_leaf].set(ro),
                leaf_count=t.leaf_count.at[bl].set(lcn).at[new_leaf].set(rcn),
                leaf_weight=t.leaf_weight.at[bl].set(lh).at[new_leaf].set(rh),
            )

            if use_boxes:
                # intermediate monotone: update bin-space boxes, then refresh
                # EVERY leaf's [min, max] from the actual current outputs via
                # dense box adjacency (learner/monotone.py — the TPU-native
                # equivalent of the reference's GoUp/GoDown constraint walks,
                # monotone_constraints.hpp:516+).  Cached best-split GAINS of
                # other leaves may lag one refresh (the reference re-queues
                # them); output CLIPPING always uses fresh bounds, so grown
                # trees stay monotone either way.
                from .monotone import box_bounds, split_boxes
                leaf_lo, leaf_hi = split_boxes(
                    st.leaf_lo, st.leaf_hi, bl, new_leaf, f_safe, thr, ~catl)
                mono_lower, mono_upper = box_bounds(
                    leaf_lo, leaf_hi, t.leaf_value, monotone,
                    jnp.int32(new_leaf) + 1)
                lmin_l, lmax_l = mono_lower[bl], mono_upper[bl]
                lmin_r, lmax_r = mono_lower[new_leaf], mono_upper[new_leaf]
                leaf_min_new = mono_lower
                leaf_max_new = mono_upper
            else:
                leaf_lo, leaf_hi = st.leaf_lo, st.leaf_hi
                leaf_min_new = st.leaf_min.at[bl].set(lmin_l) \
                                          .at[new_leaf].set(lmin_r)
                leaf_max_new = st.leaf_max.at[bl].set(lmax_l) \
                                          .at[new_leaf].set(lmax_r)

            child_path = st.path_feats[bl].at[f_safe].set(True)
            if rng_key is not None:
                k_l, k_r, k_el, k_er2 = jax.random.split(
                    jax.random.fold_in(rng_key, i), 4)
            else:
                k_l = k_r = k_el = k_er2 = None
            fm_l = node_feature_mask(child_path, k_l)
            fm_r = node_feature_mask(child_path, k_r)
            if cegb is not None:
                # this split acquires `feat` for the whole parent leaf —
                # the BAGGED-IN rows only: the reference's DataPartition
                # holds just the bag subset, so bagged-out rows never
                # traverse the split during training and their feature
                # stays un-acquired (cost_effective_gradient_boosting.hpp
                # iterates the partition's indices).  Masking here also
                # keeps batch_grower's round-batched update (same mask)
                # bit-identical at batch=1 under bagging.
                cegb_used = st.cegb_used.at[feat].set(True)
                if cegb.used_rows is not None:
                    in_parent = active & (mask_f > 0)
                    cegb_rows = st.cegb_rows | (
                        in_parent[:, None]
                        & (lax.iota(jnp.int32, num_f)[None, :] == feat))
                else:
                    cegb_rows = st.cegb_rows
                pen_l = cegb_penalty(cegb_used, cegb_rows,
                                     (leaf_of_row == bl) & (mask_f > 0), lcn)
                pen_r = cegb_penalty(cegb_used, cegb_rows,
                                     (leaf_of_row == new_leaf) & (mask_f > 0),
                                     rcn)
            else:
                cegb_used, cegb_rows = st.cegb_used, st.cegb_rows
                pen_l = pen_r = None
            if use_adv:
                # advanced monotone: per-(feature, threshold) bounds for
                # each child's upcoming split evaluation
                from .monotone import advanced_split_bounds
                adv_l = advanced_split_bounds(
                    leaf_lo, leaf_hi, t.leaf_value, monotone,
                    jnp.int32(i) + 2, bl, hp.n_bins)
                adv_r = advanced_split_bounds(
                    leaf_lo, leaf_hi, t.leaf_value, monotone,
                    jnp.int32(i) + 2, new_leaf, hp.n_bins)
            else:
                adv_l = adv_r = None
            bs_l = child_best(h_left, lg, lh, lcn, d, fm_l, lo, lmin_l,
                              lmax_l, k_el, pen=pen_l, adv=adv_l)
            bs_r = child_best(h_right, rg, rh, rcn, d, fm_r, ro, lmin_r,
                              lmax_r, k_er2, pen=pen_r, adv=adv_r)

            return st._replace(
                tree=t,
                leaf_of_row=leaf_of_row,
                hist=hist,
                sum_g=st.sum_g.at[bl].set(lg).at[new_leaf].set(rg),
                sum_h=st.sum_h.at[bl].set(lh).at[new_leaf].set(rh),
                count=st.count.at[bl].set(lcn).at[new_leaf].set(rcn),
                best_gain=st.best_gain.at[bl].set(bs_l.gain)
                                       .at[new_leaf].set(bs_r.gain),
                best_feat=st.best_feat.at[bl].set(bs_l.feature)
                                       .at[new_leaf].set(bs_r.feature),
                best_thr=st.best_thr.at[bl].set(bs_l.threshold)
                                     .at[new_leaf].set(bs_r.threshold),
                best_dl=st.best_dl.at[bl].set(bs_l.default_left)
                                   .at[new_leaf].set(bs_r.default_left),
                best_cat=st.best_cat.at[bl].set(bs_l.is_categorical)
                                     .at[new_leaf].set(bs_r.is_categorical),
                best_var=st.best_var.at[bl].set(bs_l.variant)
                                     .at[new_leaf].set(bs_r.variant),
                best_lg=st.best_lg.at[bl].set(bs_l.left_sum_g)
                                   .at[new_leaf].set(bs_r.left_sum_g),
                best_lh=st.best_lh.at[bl].set(bs_l.left_sum_h)
                                   .at[new_leaf].set(bs_r.left_sum_h),
                best_lc=st.best_lc.at[bl].set(bs_l.left_count)
                                   .at[new_leaf].set(bs_r.left_count),
                parent_node=st.parent_node.at[bl].set(i).at[new_leaf].set(i),
                parent_side=st.parent_side.at[bl].set(0).at[new_leaf].set(1),
                leaf_min=leaf_min_new,
                leaf_max=leaf_max_new,
                leaf_lo=leaf_lo,
                leaf_hi=leaf_hi,
                path_feats=st.path_feats.at[bl].set(child_path)
                                        .at[new_leaf].set(child_path),
                cegb_used=cegb_used,
                cegb_rows=cegb_rows,
            )

        return lax.cond(do, split, no_split, st)

    state = lax.fori_loop(0, L - 1, body, state)
    tree_out = state.tree._replace(leaf_path=state.path_feats)
    if cegb is not None:
        new_cegb = cegb._replace(
            feature_used=state.cegb_used,
            used_rows=None if cegb.used_rows is None else state.cegb_rows)
        return tree_out, state.leaf_of_row, new_cegb
    return tree_out, state.leaf_of_row
