"""Batched-round tree growth: K splits per data pass.

The strict leaf-wise learner (learner/grower.py, mirroring reference
serial_tree_learner.cpp) needs one data pass per split because the next
best leaf depends on the children of the last split.  On TPU that pass is
bound by one-hot construction in the histogram kernel, so 254 splits cost
254 passes regardless of leaf sizes.

This grower relaxes strict best-first order to BATCHED best-first: each
round splits the current top-``batch`` leaves by cached gain, then computes
all K smaller-child histograms in ONE widened-channel kernel pass
(ops/histogram.py ``histogram_for_leaves_masked``) — the one-hot work is
shared, so K splits cost ~one pass.  With batch=1 the trees are IDENTICAL
to the strict learner; with batch=k each round's selections are the same
leaves a strict learner would pick in its next k steps UNLESS a fresh child
out-gains a queued leaf mid-round — in practice metric curves track the
strict learner closely (tests/test_batch_grower.py) at up to ~k× the
throughput.  The reference has no counterpart; its CPU learner pays
O(child rows) per split and needs no such amortization.

Supported feature set: numerical splits with missing handling, categorical
splits (one-hot + sorted-subset, applied via per-split bitsets),
basic/intermediate monotone constraints, interaction constraints, path
smoothing, forced splits (K=1 prefix phase), extra_trees + per-node
feature sampling, EFB bundles, bagging row masks, per-tree feature
sampling, depth limits, data-parallel ``shard_map`` (axis psum),
voting-parallel (PV-Tree two-phase vote with local histogram state),
CEGB penalties (serial mode; split/coupled/lazy with round-batched
acquisition updates), all three monotone methods (advanced computes
per-(feature, threshold) child bounds for the whole round's kids from
the round-refreshed boxes), and linear trees (returned trees carry
leaf_path, so the post-growth ridge fit composes unchanged).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import (bins_to_words, hist_dispatch,
                             histogram_for_leaves_auto, root_histogram)
from ..ops.round_fuse import (pack_left_bins, partition_select_pallas,
                              use_fused_partition)
from ..ops.table import sum_small_table
from ..ops.split import (NEG_INF, VAR_CAT_BWD, VAR_CAT_FWD, SplitHyper,
                         cat_levels, categorical_left_bitset, leaf_output)
from .grower import (CegbInput, DeviceBundle, TreeArrays, _INF_BOUND,
                     _empty_tree, _expand_hist_col, _feature_bin_of_rows,
                     best_split_of_hist, pv_vote_best_split,
                     sample_features_bynode, split_ranges)

#: data size below which warmup width-matching is never worth its extra
#: kernel compilations (tests patch this to exercise the ladder cheaply)
_WARMUP_MIN_ROWS = 65536
#: rows (over all shards) from which on a tree's f32 counts can round
_F32_EXACT_ROWS = 1 << 24


def warmup_widths(n: int, K: int, hp: SplitHyper, forced) -> list:
    """Leaves per pass of the warm-up rounds a tree of ``n`` rows a shard
    runs before its full-width (``K``) loop: 1, 4, 16, ... below ``K``
    where the ladder pays (``grow_tree_batched`` says why), none
    otherwise.  The grower's loops and the booster's count of what a
    tree all-reduces (``GBDT._pass_widths``) both read it here."""
    if n < _WARMUP_MIN_ROWS or forced is not None \
            or not hist_dispatch(hp.hist_kernel, hp.n_bins).ladder:
        return []
    widths, kw = [], 1
    while kw < K:
        widths.append(kw)
        kw *= 4
    return widths


def _recount_leaves(leaf_of_row: jax.Array, mask_f: jax.Array, size: int,
                    axis_name: Optional[str]) -> jax.Array:
    """f32 ``[size]`` counts of the masked-in rows of every leaf, from
    where the rows are.  The counts a tree carries while it grows are
    f32 histogram sums, ``parent - left`` down every path, exact only
    while no node holds ``2**24`` rows; from there on (53M rows over
    four shards) a rounded parent leaves its last leaf a few rows off.
    This one is exact while a SHARD's leaf stays under ``2**24`` rows:
    f32 sums of ones a shard, added over the shards as int32."""
    with jax.named_scope("leaf_recount"):
        counts = sum_small_table(leaf_of_row, mask_f, mask_f, None,
                                 size)[0].astype(jnp.int32)
        if axis_name is not None:
            with jax.named_scope("stats_allreduce"):
                counts = lax.psum(counts, axis_name)
        return counts.astype(jnp.float32)


def _planes(h: jax.Array) -> jax.Array:
    """``[..., F, B, C]`` as the per-leaf state carries it, ``[..., C, F, B]``."""
    return jnp.moveaxis(h, -1, -3)


def _channels_last(s: jax.Array) -> jax.Array:
    """Slabs of the per-leaf state ``[..., C, F, B]`` as the histogram
    kernels hand them over and the split search takes them,
    ``[..., F, B, C]``."""
    return jnp.moveaxis(s, -3, -1)


def read_slabs(hist: jax.Array, rows: jax.Array) -> jax.Array:
    """The slabs ``hist[rows]`` of the per-leaf state, ``[K, C, F, B]``, one
    leading-axis slice a slot as ``write_children`` writes them: a gather
    of K rows has the compiler copy the state first, seven column blocks of
    it at 2,000 columns and all of it re-tiled at 67 (PERF.md section 6)."""
    return lax.map(
        lambda r: lax.dynamic_index_in_dim(hist, r, keepdims=False), rows)


def write_children(hist: jax.Array, parents: jax.Array,
                   new_leaves: jax.Array, valid: jax.Array,
                   h_left: jax.Array, h_right: jax.Array) -> jax.Array:
    """A round's update of the per-leaf histogram state: each valid slot's
    left child ``h_left[j]`` takes the place ``parents[j]``, its right
    child ``h_right[j]`` the place ``new_leaves[j]``; an invalid slot
    leaves both of its places as they were.

    ``hist`` is the state as the tree loop carries it, ``f32 [rows + 1, C,
    F, B]``: a leaf's (or pool slot's) slab contiguous, the channel planes
    g, h, n and a zero one ahead of the columns, the bins on the lanes, and
    a last row that no one reads, where an invalid slot's two slabs go.
    The children come as the kernels and the search have them, ``[K, F, B,
    C]``.  ONE layout for the whole loop: the reads of ``[K, ...]`` slabs
    (``read_slabs``) and these writes, one leading-axis
    ``dynamic_update_slice`` a slab, address the same buffer in place, and
    nothing of the state's size is copied or re-tiled inside a tree
    (``chip_smoke.py`` ``hist_state_copies``; PERF.md section 5)."""
    trash = hist.shape[0] - 1
    for slabs, places in ((h_left, parents), (h_right, new_leaves)):
        slabs = _planes(slabs)                                 # [K, C, F, B]
        places = jnp.where(valid, places, trash)

        def put(j, hist, slabs=slabs, places=places):
            return lax.dynamic_update_slice(
                hist, lax.dynamic_index_in_dim(slabs, j, keepdims=True),
                (places[j], 0, 0, 0))

        hist = lax.fori_loop(0, valid.shape[0], put, hist)
    return hist


def fuses_partition(bundle: Optional[DeviceBundle]) -> bool:
    """Whether a round's row partition runs in ops/round_fuse.py's
    kernel: a Pallas backend, and every split a range predicate on its
    physical column or a set of its bins (no bundle plan, or one with
    ranges); else the XLA path (counter ``fused_partition_declined``)."""
    return use_fused_partition() and (bundle is None
                                      or bundle.search is not None)


@functools.partial(jax.jit, static_argnames=("hp", "batch", "axis_name",
                                             "warmup", "parallel_mode",
                                             "top_k", "num_shards"))
def grow_tree_batched(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                      row_mask: Optional[jax.Array], num_bins: jax.Array,
                      nan_bin: jax.Array, is_cat: jax.Array,
                      feature_mask: Optional[jax.Array], hp: SplitHyper,
                      batch: int = 8,
                      bundle: Optional[DeviceBundle] = None,
                      monotone: Optional[jax.Array] = None,
                      axis_name: Optional[str] = None,
                      warmup: bool = True,
                      hist_scale: Optional[jax.Array] = None,
                      interaction_sets: Optional[jax.Array] = None,
                      rng_key: Optional[jax.Array] = None,
                      forced: Optional[Tuple[jax.Array, jax.Array,
                                             jax.Array]] = None,
                      parallel_mode: str = "data", top_k: int = 20,
                      num_shards: int = 1,
                      cegb: Optional[CegbInput] = None,
                      bins_words: Optional[jax.Array] = None):
    """Grow one tree with ``batch`` splits per histogram pass.

    Same operands and return contract as ``grow_tree`` (a 3-tuple with
    the updated ``CegbInput`` when ``cegb`` is passed).  Supports
    interaction constraints (per-leaf path-feature masks), ALL monotone
    methods (intermediate/advanced refresh every leaf's bounds from
    dense box adjacency after EACH split, the strict learner's cadence,
    so splits later in a round see earlier splits' outputs; advanced
    additionally threads per-(feature, threshold) child bounds into the
    round's split evaluations; cached candidate GAINS of unsplit leaves
    may lag a round, the same class of lag the strict learner
    documents), path smoothing, CEGB penalties (acquisitions batch per
    round), and linear trees (returned trees carry ``leaf_path``).

    Under ``axis_name`` with ``parallel_mode="voting"`` the rounds run
    the PV-Tree protocol (reference voting_parallel_tree_learner.cpp,
    round-4 lift of the batched-grower cliff): histogram state stays
    LOCAL per shard, each child's best split does a two-phase vote —
    local per-feature gains at 1/num_shards-relaxed thresholds, a
    ``psum`` vote over each shard's top-``top_k`` features, then a
    ``psum`` of ONLY the 2·top_k voted histogram slices — so per-round
    communication is O(K · top_k · bins), independent of feature count,
    while K splits still share one local histogram pass.
    """
    voting = parallel_mode == "voting" and axis_name is not None
    # collectives the histogram ops should use: none under voting (the
    # vote psums slices itself)
    hist_axis = None if voting else axis_name
    if hp.use_monotone:
        assert monotone is not None and hp.monotone_method in (
            "basic", "intermediate", "advanced"), \
            f"unknown monotone method {hp.monotone_method!r}"
    if voting:
        assert forced is None, "forced splits need the strict learner " \
            "under voting"
        assert not (hp.use_monotone
                    and hp.monotone_method == "advanced"), \
            "advanced monotone under voting needs the strict learner " \
            "(the vote path does not thread per-threshold bounds)"
    if cegb is not None:
        assert axis_name is None, \
            "batched CEGB runs the serial learner only (the distributed " \
            "modes route through the strict grower)"
    use_lazy = cegb is not None and cegb.used_rows is not None
    use_boxes = hp.use_monotone and hp.monotone_method in (
        "intermediate", "advanced")
    use_adv = hp.use_monotone and hp.monotone_method == "advanced"
    use_paths = interaction_sets is not None
    use_smooth = hp.path_smooth > 0.0
    use_bynode = hp.feature_fraction_bynode < 1.0 and rng_key is not None
    use_rng = rng_key is not None and (hp.extra_trees or use_bynode)
    n = bins.shape[0]
    num_f = bins.shape[1] if bundle is None else bundle.feat_col.shape[0]
    L = hp.num_leaves
    K = min(batch, L - 1)
    if use_lazy:
        # row-block geometry for the lazy-acquisition scans (bounds the
        # per-round f32 transients to [K, blk] instead of [K, n])
        cegb_blk = min(1 << 18, n)
        cegb_pad = (-n) % cegb_blk
        cegb_nb = (n + cegb_pad) // cegb_blk
    with jax.named_scope("tree_root"):
        mask_f = jnp.ones_like(grad) if row_mask is None \
            else row_mask.astype(grad.dtype)
        bins_t = lax.optimization_barrier(bins.T)
        # tree-invariant i32 word view of the row-major bins, hoisted out of
        # the round loop: every compacted round's payload reuses it.  The
        # booster passes the dataset's construction-time packed mirror
        # (io/dataset.py packed_mirror) so serial trees skip even the
        # one-time bitcast; derived in-jit otherwise (distributed shards).
        bins_words = lax.optimization_barrier(
            bins_to_words(bins) if bins_words is None else bins_words)
        # transposed packed mirror for the round-6 packed histogram kernel
        words_t = lax.optimization_barrier(bins_words.T) \
            if hist_dispatch(hp.hist_kernel, hp.n_bins).mirror else None
    # fused partition+key kernel (ops/round_fuse.py): splits a range of
    # the physical column states (grower.py split_ranges) — numeric
    # features, bundled or not — and, in a job with categorical columns,
    # a slot's left set as 8 words of 32 bins.  The EFB inverse table of
    # a plan without ranges is a per-row gather, kept on the XLA path
    fuse_partition = fuses_partition(bundle)
    # bins that hold a level: a column's other bin is in no left set
    levels = cat_levels(num_bins, nan_bin, is_cat) \
        if hp.has_categorical else num_bins
    pooled = 0 < hp.hist_pool_slots < hp.num_leaves
    from ..ops.histogram import use_pallas as _use_pallas
    INF = jnp.float32(_INF_BOUND)

    def node_mask(path_f, key=None):
        """Per-leaf allowed features: interaction constraints (reference
        col_sampler.hpp:91 GetByNode — a leaf may split only on features
        from constraint sets containing its whole path) composed with the
        per-node random subset (feature_fraction_bynode)."""
        m = feature_mask
        if use_paths:
            fits = jnp.all(interaction_sets | ~path_f[None, :], axis=1)
            allowed = jnp.any(interaction_sets & fits[:, None],
                              axis=0) | path_f
            m = allowed if m is None else (m & allowed)
        if use_bynode and key is not None:
            m = sample_features_bynode(m, key, hp.feature_fraction_bynode,
                                       num_f)
        return m

    if voting:
        import dataclasses as _dc
        # locally relaxed validity thresholds
        # (voting_parallel_tree_learner.cpp:62-64)
        hp_vote = _dc.replace(
            hp, min_data_in_leaf=max(1, hp.min_data_in_leaf // num_shards),
            min_sum_hessian_in_leaf=hp.min_sum_hessian_in_leaf / num_shards)

    def cegb_penalty(used_f, used_rows_cnt, leaf_count):
        """Per-feature gain penalty for one leaf (CEGB DeltaGain,
        cost_effective_gradient_boosting.hpp — same math as the strict
        grower's cegb_penalty, with the lazy row count precomputed by
        the caller's batched matmul)."""
        pen = cegb.split_pen * leaf_count \
            + jnp.where(used_f, 0.0, cegb.coupled_pen)
        if use_lazy:
            pen = pen + cegb.lazy_pen * used_rows_cnt
        return pen

    def child_best(h_phys, g_, h_, c_, depth, lmin, lmax, fm, pout,
                   key=None, pen=None, adv=None):
        """``(best split, its left bins)`` of one leaf; the left bins
        (bool [B], ``None`` in a job without categorical columns) are
        what the split search's own sort gives, or under voting
        ``winner_bitset``'s second sort of the psum-ed column."""
        if voting:
            # PV-Tree two-phase vote per child — ONE protocol definition
            # shared with the strict grower (learner/grower.py
            # pv_vote_best_split)
            res = pv_vote_best_split(
                h_phys, g_, h_, c_, depth, fm, pout, lmin, lmax, key,
                hp=hp, hp_vote=hp_vote, num_bins=num_bins,
                nan_bin=nan_bin, is_cat=is_cat, monotone=monotone,
                bundle=bundle, num_f=num_f, top_k=top_k,
                axis_name=axis_name)
            return res, (winner_bitset(h_phys, g_, h_, c_, res.feature,
                                       res.variant, res.threshold)
                         if hp.has_categorical else None)
        left_bins = [] if hp.has_categorical else None
        res = best_split_of_hist(h_phys, g_, h_, c_, num_bins, nan_bin,
                                 is_cat, fm, hp, bundle, monotone=monotone,
                                 leaf_min=lmin, leaf_max=lmax, depth=depth,
                                 parent_output=pout, rng_key=key,
                                 gain_penalty=pen, adv_bounds=adv,
                                 left_bins_out=left_bins)
        depth_ok = (hp.max_depth <= 0) | (depth < hp.max_depth)
        return (res._replace(gain=jnp.where(depth_ok, res.gain, NEG_INF)),
                left_bins[0] if left_bins else None)

    def forced_col_hist(ff, lor_now, fl):
        """[B, C] VIRTUAL histogram column of leaf ``fl`` for feature
        ``ff``, computed directly from the data in row blocks.

        The pooled forced phase uses this instead of ``st["hist"]``: a
        forced BFS schedule can prescribe a split for a leaf whose pool
        slot was evicted rounds ago, so the column is re-derived from
        the rows themselves (same exact sums; may differ from the
        subtraction-chain histogram only in f32 rounding — the same
        deviation class as the pool's direct child rebuilds).  Virtual
        bins via ``_feature_bin_of_rows`` make EFB default-bin
        completion unnecessary."""
        colv = _feature_bin_of_rows(bins_t, bundle, ff)
        selm = (lor_now == fl) & (mask_f > 0)
        iota_b = lax.iota(jnp.int32, hp.n_bins)
        blk_ = min(1 << 17, n)
        pad_ = (-n) % blk_
        nb_ = (n + pad_) // blk_

        def block(acc, xs):
            colv_b, g_b, h_b, sel_b = xs
            oh = (colv_b[None, :] == iota_b[:, None]).astype(jnp.float32)
            gm = jnp.where(sel_b, g_b, 0.0)
            hm = jnp.where(sel_b, h_b, 0.0)
            vals = jnp.stack([gm, hm, sel_b.astype(jnp.float32),
                              jnp.zeros_like(g_b)])          # [C, blk]
            return acc + lax.dot_general(
                vals, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST).T, None     # [B, C]

        acc0 = jnp.zeros((hp.n_bins, 4), jnp.float32)
        hf, _ = lax.scan(block, acc0, (
            jnp.pad(colv, (0, pad_), constant_values=-1)
            .reshape(nb_, blk_),
            jnp.pad(grad, (0, pad_)).reshape(nb_, blk_),
            jnp.pad(hess, (0, pad_)).reshape(nb_, blk_),
            jnp.pad(selm, (0, pad_)).reshape(nb_, blk_)))
        return _scaled(hf)

    def winner_bitset(h_phys, g_, h_, c_, feat, var, thr):
        """Left-category bitset of a CACHED best split, computed from the
        leaf's own histogram at best-split time (same inputs as the
        strict learner's split-time computation, so identical output).
        Caching it in state removes the record phase's parent-histogram
        read — the step that kept the bounded pool and categorical
        splits apart (an evicted parent has no histogram to read).
        Under voting the state holds LOCAL histograms, so the winning
        feature's column is psum-ed first — one [B, C] column per split,
        the strict learner's cadence (grower.py split())."""
        col_of = feat if bundle is None else bundle.feat_col[feat]
        pf_col = h_phys[col_of]
        if voting:
            pf_col = lax.psum(pf_col, axis_name)
        hist_col = pf_col if bundle is None else \
            _expand_hist_col(pf_col, bundle, feat, g_, h_, c_)
        return categorical_left_bitset(
            hist_col, levels[feat], var, thr, hp) & is_cat[feat]

    # quantized-levels mode (ops/quantize.py): grad/hess hold integer
    # levels; one deterministic multiply restores real units right after
    # each exact integer histogram accumulation
    scale_vec = None
    if hist_scale is not None:
        with jax.named_scope("tree_root"):
            scale_vec = jnp.concatenate(
                [hist_scale.astype(jnp.float32), jnp.ones((2,), jnp.float32)])

    def _scaled(h):
        return h if scale_vec is None else h * scale_vec

    with jax.named_scope("tree_root"):
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
        if axis_name is not None:
            # one [3]-vector psum, not three scalar collectives
            with jax.named_scope("stats_allreduce"):
                g0, h0, c0 = lax.psum(jnp.stack([g0, h0, c0]), axis_name)
        root_out = leaf_output(g0, h0, hp.lambda_l1, hp.lambda_l2,
                               hp.max_delta_step)
        empty_path = jnp.zeros((num_f,), bool)
        key_root = jax.random.fold_in(rng_key, 0) if use_rng else None
        if cegb is not None:
            cnt0 = (jnp.einsum("n,nf->f", mask_f.astype(jnp.float32),
                               (~cegb.used_rows).astype(jnp.float32))
                    if use_lazy else None)
            pen0 = cegb_penalty(cegb.feature_used, cnt0, c0)
        else:
            pen0 = None
        best0, bits0 = child_best(hist0_b, g0, h0, c0, jnp.int32(0), -INF,
                                  INF, node_mask(empty_path, key_root),
                                  root_out, key_root, pen=pen0)

        tree = _empty_tree(L, hp.n_bins, num_f)
        tree = tree._replace(
            leaf_value=tree.leaf_value.at[0].set(root_out),
            leaf_count=tree.leaf_count.at[0].set(c0),
            leaf_weight=tree.leaf_weight.at[0].set(h0))
        C = hist0_b.shape[-1]
        n_cols = bins.shape[1]
        # bounded histogram pool (SplitHyper.hist_pool_slots): P slots + one
        # trash row; leaf_slot/slot_leaf carry the mapping, with trash entries
        # at index L / P so masked scatters need no branches
        # (``pooled`` itself is derived up top, before the partition-fusion
        # gates)
        P = hp.hist_pool_slots
        if pooled:
            assert P >= 3 * K + 2, \
                "hist_pool_slots must be >= 3*batch+2 for worst-case rounds"
        state = dict(
            tree=tree,
            leaf_of_row=jnp.zeros((n,), jnp.int32),
            # the per-leaf histograms, a row a leaf (a pool slot under the
            # bounded pool) and a last row for the writes of invalid slots,
            # in the ONE form the loop carries them (``write_children``)
            hist=jnp.zeros(((P if pooled else L) + 1, C, n_cols, hp.n_bins),
                           jnp.float32).at[0].set(_planes(hist0_b)),
            sum_g=jnp.zeros((L,), jnp.float32).at[0].set(g0),
            sum_h=jnp.zeros((L,), jnp.float32).at[0].set(h0),
            count=jnp.zeros((L,), jnp.float32).at[0].set(c0),
            best_gain=jnp.full((L,), NEG_INF, jnp.float32).at[0].set(best0.gain),
            best_feat=jnp.zeros((L,), jnp.int32).at[0].set(best0.feature),
            best_thr=jnp.zeros((L,), jnp.int32).at[0].set(best0.threshold),
            best_dl=jnp.zeros((L,), bool).at[0].set(best0.default_left),
            best_var=jnp.zeros((L,), jnp.int32).at[0].set(best0.variant),
            best_lg=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_sum_g),
            best_lh=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_sum_h),
            best_lc=jnp.zeros((L,), jnp.float32).at[0].set(best0.left_count),
            leaf_min=jnp.full((L,), -INF, jnp.float32),
            leaf_max=jnp.full((L,), INF, jnp.float32),
            parent_node=jnp.full((L,), -1, jnp.int32),
            parent_side=jnp.zeros((L,), jnp.int32),
            n_splits=jnp.int32(0),
            progress=jnp.bool_(True),
        )
        if hp.has_categorical:
            state["best_bitset"] = jnp.zeros((L, hp.n_bins),
                                             bool).at[0].set(bits0)
        if cegb is not None:
            state["cegb_used"] = cegb.feature_used
            if use_lazy:
                state["cegb_rows"] = cegb.used_rows
        # leaf path features: tracked unconditionally ([L, F] bool is tiny)
        # so returned trees carry leaf_path like the strict learner's — the
        # linear-tree ridge fit (learner/linear.py fit_linear_leaves) selects
        # each leaf's numeric path features from it
        state["path_f"] = jnp.zeros((L, num_f), bool)
        if use_boxes:
            # bin-space boxes: root spans every bin (hi exclusive); dead slots
            # hold empty boxes so box_bounds ignores them
            state["leaf_lo"] = jnp.zeros((L, num_f), jnp.int32)
            state["leaf_hi"] = jnp.zeros((L, num_f), jnp.int32).at[0].set(
                num_bins.astype(jnp.int32))
        if forced is not None:
            # composes with the bounded pool since round 6: the forced phase
            # derives evicted leaves' columns directly (forced_col_hist)
            state["force_failed"] = jnp.bool_(False)
        if pooled:
            state["leaf_slot"] = jnp.full((L + 1,), -1, jnp.int32).at[0].set(0)
            state["slot_leaf"] = jnp.full((P + 1,), -1, jnp.int32).at[0].set(0)

    def make_round_body(Kr, use_forced=False):
      def round_body(st):
          if use_forced:
              # forced-split round (reference serial_tree_learner.cpp:620
              # ForceSplits; same math as the strict learner's forced
              # gather): entry index == split counter, stats gathered at
              # the PRESCRIBED threshold from the leaf's histogram, staged
              # into the cached-best slots so the normal record machinery
              # applies them
              from ..ops.split import VAR_CAT_ONEHOT, VAR_NUM_RIGHT
              from .grower import gather_forced_split
              f_leaf, f_feat, f_thr = forced
              i = jnp.minimum(st["n_splits"], f_leaf.shape[0] - 1)
              f_active = (f_leaf[i] >= 0) & ~st["force_failed"]
              fl = jnp.maximum(f_leaf[i], 0)
              ff, ft = f_feat[i], f_thr[i]
              if pooled:
                  # resident pool slot -> one [B, C] slot read (the
                  # common case: forced prefixes are shallow and the
                  # pool holds >= 3K+2 slots); evicted -> re-derive the
                  # virtual column from the data in row blocks
                  # (round-6 lift of the forced x hist-pool carve-out)
                  slot = st["leaf_slot"][fl]
                  resident = (slot >= 0) & (slot < P)

                  def hf_from_pool(_):
                      hc = st["hist"][jnp.clip(slot, 0, P), :,
                                      ff if bundle is None
                                      else bundle.feat_col[ff]].T
                      return hc if bundle is None else \
                          _expand_hist_col(hc, bundle, ff,
                                           st["sum_g"][fl],
                                           st["sum_h"][fl],
                                           st["count"][fl])

                  hf = lax.cond(resident, hf_from_pool,
                                lambda _: forced_col_hist(
                                    ff, st["leaf_of_row"], fl), None)
              else:
                  hf_col = st["hist"][fl, :, ff if bundle is None
                                      else bundle.feat_col[ff]].T  # [B, C]
                  hf = hf_col if bundle is None else \
                      _expand_hist_col(hf_col, bundle, ff,
                                       st["sum_g"][fl],
                                       st["sum_h"][fl], st["count"][fl])
              pgf, phf, pcf = st["sum_g"][fl], st["sum_h"][fl], \
                  st["count"][fl]
              lgf, lhf, lcf, gf, ok_f = gather_forced_split(
                  hf, pgf, phf, pcf, ft, is_cat[ff], nan_bin[ff], hp)
              use_f = f_active & ok_f
              st = dict(st)
              st["force_failed"] = st["force_failed"] | (f_active & ~ok_f)

              def sset(name, val):
                  st[name] = st[name].at[fl].set(
                      jnp.where(use_f, val, st[name][fl]))

              sset("best_gain", gf)
              sset("best_feat", ff)
              sset("best_thr", ft)
              sset("best_dl", jnp.bool_(False))
              sset("best_var", jnp.where(is_cat[ff], VAR_CAT_ONEHOT,
                                         VAR_NUM_RIGHT))
              sset("best_lg", lgf)
              sset("best_lh", lhf)
              sset("best_lc", lcf)
              if hp.has_categorical:
                  var_f = jnp.where(is_cat[ff], VAR_CAT_ONEHOT,
                                    VAR_NUM_RIGHT)
                  if pooled:
                      # same direct column carries the bitset (the pool
                      # may not hold this leaf's histogram)
                      bs_f = categorical_left_bitset(
                          hf, levels[ff], var_f, ft, hp) & is_cat[ff]
                  else:
                      bs_f = winner_bitset(_channels_last(st["hist"][fl]),
                                           pgf, phf, pcf, ff, var_f, ft)
                  st["best_bitset"] = st["best_bitset"].at[fl].set(
                      jnp.where(use_f, bs_f, st["best_bitset"][fl]))
              forced_sel = (fl, use_f)
          else:
              forced_sel = None
          topg, parents = lax.top_k(st["best_gain"], Kr)          # [K]
          if forced_sel is not None:
              # the forced leaf is the round's ONLY candidate (Kr == 1)
              parents = jnp.where(forced_sel[1], forced_sel[0][None],
                                  parents)
              topg = jnp.where(forced_sel[1], st["best_gain"][parents[0]]
                               [None], topg)
          room = st["n_splits"] + lax.iota(jnp.int32, Kr) < L - 1
          valid = (topg > 0.0) & room
          if forced_sel is not None:
              valid = valid & forced_sel[1][None]
          rank = jnp.cumsum(valid.astype(jnp.int32)) - 1          # [K]
          node_ids = st["n_splits"] + rank                        # [K]
          new_leaves = node_ids + 1                               # [K]

          t = st["tree"]
          lor = st["leaf_of_row"]
          if not use_boxes:
              # ---- vectorized record: ONE batched scatter per array.
              # The sequential per-slot loop below (kept for the
              # box-based monotone methods, whose per-split bound
              # refresh makes later slots depend on earlier outputs)
              # cost ~17 ms/tree at K=42 in pure scatter-chain latency
              # (round-4 e2e profile); all its reads/writes touch
              # DISTINCT indices across slots — parents are distinct
              # top-k leaves, new node/leaf ids are distinct, and a
              # shared grandparent node is written on complementary
              # child sides — so the loop folds into masked scatters
              # (invalid slots aim out of bounds, mode="drop").
              ok = valid                                          # [K]
              bl = parents
              feat = st["best_feat"][bl]
              thr = st["best_thr"][bl]
              dl = st["best_dl"][bl]
              var = st["best_var"][bl]
              catl = is_cat[feat]
              pg, ph, pc = st["sum_g"][bl], st["sum_h"][bl], \
                  st["count"][bl]
              lg, lh, lcn = st["best_lg"][bl], st["best_lh"][bl], \
                  st["best_lc"][bl]
              rg, rh, rcn = pg - lg, ph - lh, pc - lcn
              if hp.has_categorical:
                  bitsets_arr = st["best_bitset"][bl]             # [K, B]
              else:
                  bitsets_arr = jnp.zeros((Kr, hp.n_bins), bool)

              ni = L - 1
              p, side = st["parent_node"][bl], st["parent_side"][bl]
              nid_m = jnp.where(ok, node_ids, ni)                 # drop idx
              lc = t.left_child.at[
                  jnp.where(ok & (p >= 0) & (side == 0), p, ni)
              ].set(node_ids, mode="drop")
              lc = lc.at[nid_m].set(-(bl + 1), mode="drop")
              rc = t.right_child.at[
                  jnp.where(ok & (p >= 0) & (side == 1), p, ni)
              ].set(node_ids, mode="drop")
              rc = rc.at[nid_m].set(-(new_leaves + 1), mode="drop")

              l2_eff = hp.lambda_l2 + jnp.where(
                  (var == VAR_CAT_FWD) | (var == VAR_CAT_BWD),
                  hp.cat_l2, 0.0)
              if use_smooth:
                  from ..ops.split import smoothed_output
                  pout_k = t.leaf_value[bl]
                  lo = smoothed_output(lg, lh, lcn, pout_k,
                                       hp.lambda_l1, l2_eff, hp)
                  ro = smoothed_output(rg, rh, rcn, pout_k,
                                       hp.lambda_l1, l2_eff, hp)
              else:
                  lo = leaf_output(lg, lh, hp.lambda_l1, l2_eff,
                                   hp.max_delta_step)
                  ro = leaf_output(rg, rh, hp.lambda_l1, l2_eff,
                                   hp.max_delta_step)
              if hp.use_monotone:
                  # basic method only here (box methods take the
                  # sequential branch): clip into the parent's range,
                  # tighten each child's box at the midpoint
                  lmin_p = st["leaf_min"][bl]
                  lmax_p = st["leaf_max"][bl]
                  lo = jnp.clip(lo, lmin_p, lmax_p)
                  ro = jnp.clip(ro, lmin_p, lmax_p)
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
              d = t.leaf_depth[bl] + 1

              # leaf-indexed arrays: one [2K] scatter (bl existing ids,
              # new_leaves fresh ids — provably disjoint)
              idx2 = jnp.concatenate([jnp.where(ok, bl, L),
                                      jnp.where(ok, new_leaves, L)])

              def w2(arr, vb, vn):
                  return arr.at[idx2].set(
                      jnp.concatenate([vb, vn]), mode="drop")

              new_path = st["path_f"][bl] | (
                  feat[:, None] == lax.iota(jnp.int32, num_f)[None, :])
              st["path_f"] = st["path_f"].at[idx2].set(
                  jnp.concatenate([new_path, new_path]), mode="drop")

              t = t._replace(
                  split_feature=t.split_feature.at[nid_m].set(
                      feat, mode="drop"),
                  split_bin=t.split_bin.at[nid_m].set(thr, mode="drop"),
                  default_left=t.default_left.at[nid_m].set(
                      dl, mode="drop"),
                  split_cat=t.split_cat.at[nid_m].set(catl, mode="drop"),
                  cat_bitset=t.cat_bitset.at[nid_m].set(
                      bitsets_arr, mode="drop"),
                  left_child=lc, right_child=rc,
                  split_gain=t.split_gain.at[nid_m].set(
                      st["best_gain"][bl], mode="drop"),
                  internal_value=t.internal_value.at[nid_m].set(
                      leaf_output(pg, ph, hp.lambda_l1, hp.lambda_l2,
                                  hp.max_delta_step), mode="drop"),
                  internal_count=t.internal_count.at[nid_m].set(
                      pc, mode="drop"),
                  leaf_depth=w2(t.leaf_depth, d, d),
                  leaf_value=w2(t.leaf_value, lo, ro),
                  leaf_count=w2(t.leaf_count, lcn, rcn),
                  leaf_weight=w2(t.leaf_weight, lh, rh),
                  num_leaves=t.num_leaves
                  + jnp.sum(valid.astype(jnp.int32)),
              )
              st["sum_g"] = w2(st["sum_g"], lg, rg)
              st["sum_h"] = w2(st["sum_h"], lh, rh)
              st["count"] = w2(st["count"], lcn, rcn)
              st["parent_node"] = w2(st["parent_node"], node_ids,
                                     node_ids)
              st["parent_side"] = w2(st["parent_side"],
                                     jnp.zeros((Kr,), jnp.int32),
                                     jnp.ones((Kr,), jnp.int32))
              if hp.use_monotone:
                  st["leaf_min"] = w2(st["leaf_min"], lmin_l, lmin_r)
                  st["leaf_max"] = w2(st["leaf_max"], lmax_l, lmax_r)
              st["best_gain"] = st["best_gain"].at[
                  jnp.where(ok, bl, L)].set(NEG_INF, mode="drop")

          # record + partition each slot (cheap [L]/[n] ops, no data
          # passes) — sequential branch for the box-based monotone
          # methods; MUST mirror the vectorized branch above
          bitsets = []
          for j in (range(Kr) if use_boxes else ()):
              ok = valid[j]
              bl = parents[j]
              nid = node_ids[j]
              nl = jnp.where(ok, new_leaves[j], L - 1)  # safe dummy index
              feat = st["best_feat"][bl]
              thr = st["best_thr"][bl]
              dl = st["best_dl"][bl]
              var = st["best_var"][bl]
              catl = is_cat[feat]
              pg, ph, pc = st["sum_g"][bl], st["sum_h"][bl], st["count"][bl]
              lg, lh, lcn = st["best_lg"][bl], st["best_lh"][bl], \
                  st["best_lc"][bl]
              rg, rh, rcn = pg - lg, ph - lh, pc - lcn

              # left-category bitset CACHED at best-split time (state
              # best_bitset, winner_bitset) — identical to computing it
              # from the parent histogram here, but works when the pool
              # evicted that histogram
              if hp.has_categorical:
                  bitset = st["best_bitset"][bl]
              else:
                  bitset = jnp.zeros((hp.n_bins,), bool)
              bitsets.append(bitset)

              p, side = st["parent_node"][bl], st["parent_side"][bl]
              ps = jnp.maximum(p, 0)
              lc_arr = t.left_child.at[ps].set(
                  jnp.where(ok & (p >= 0) & (side == 0), nid,
                            t.left_child[ps]))
              rc_arr = t.right_child.at[ps].set(
                  jnp.where(ok & (p >= 0) & (side == 1), nid,
                            t.right_child[ps]))
              lc_arr = lc_arr.at[nid].set(
                  jnp.where(ok, -(bl + 1), lc_arr[nid]))
              rc_arr = rc_arr.at[nid].set(
                  jnp.where(ok, -(nl + 1), rc_arr[nid]))

              # sorted-subset categorical children use l2 + cat_l2, matching
              # the strict learner and feature_histogram.cpp:250; path
              # smoothing pulls children toward the parent's output exactly
              # like the strict learner (grower.py smoothed_output)
              l2_eff = hp.lambda_l2 + jnp.where(
                  (var == VAR_CAT_FWD) | (var == VAR_CAT_BWD), hp.cat_l2, 0.0)
              if use_smooth:
                  from ..ops.split import smoothed_output
                  parent_out_j = t.leaf_value[bl]
                  lo = smoothed_output(lg, lh, lcn, parent_out_j,
                                       hp.lambda_l1, l2_eff, hp)
                  ro = smoothed_output(rg, rh, rcn, parent_out_j,
                                       hp.lambda_l1, l2_eff, hp)
              else:
                  lo = leaf_output(lg, lh, hp.lambda_l1, l2_eff,
                                   hp.max_delta_step)
                  ro = leaf_output(rg, rh, hp.lambda_l1, l2_eff,
                                   hp.max_delta_step)
              if hp.use_monotone:
                  # all methods clip children into the parent's box
                  # (monotone_constraints.hpp); basic additionally tightens
                  # each child's box at the midpoint along the split
                  # direction, intermediate/advanced refresh boxes per split
                  lmin_p, lmax_p = st["leaf_min"][bl], st["leaf_max"][bl]
                  lo = jnp.clip(lo, lmin_p, lmax_p)
                  ro = jnp.clip(ro, lmin_p, lmax_p)
                  if use_boxes:
                      # sibling-ordering repair (one source of truth with
                      # the strict learner, grower.py: clipping both
                      # children to the parent's range can inverse their
                      # order under the split feature's constraint;
                      # collapse inverted pairs to the midpoint)
                      mono_sf = monotone[feat]
                      inv = (~catl) & (((mono_sf > 0) & (lo > ro))
                                       | ((mono_sf < 0) & (lo < ro)))
                      mid_sib = jnp.clip((lo + ro) * 0.5, lmin_p, lmax_p)
                      lo = jnp.where(inv, mid_sib, lo)
                      ro = jnp.where(inv, mid_sib, ro)
                  if not use_boxes:
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
              # children inherit the path plus the split feature
              new_path = st["path_f"][bl].at[feat].set(True)
              st["path_f"] = st["path_f"].at[bl].set(
                  jnp.where(ok, new_path, st["path_f"][bl]))
              st["path_f"] = st["path_f"].at[nl].set(
                  jnp.where(ok, new_path, st["path_f"][nl]))
              if use_boxes:
                  from .monotone import box_bounds, split_boxes
                  n_lo, n_hi = split_boxes(
                      st["leaf_lo"], st["leaf_hi"], bl, nl, feat, thr,
                      ~catl)
                  st["leaf_lo"] = jnp.where(ok, n_lo, st["leaf_lo"])
                  st["leaf_hi"] = jnp.where(ok, n_hi, st["leaf_hi"])
              d = t.leaf_depth[bl] + 1

              def w(arr, idx, val):
                  return arr.at[idx].set(jnp.where(ok, val, arr[idx]))

              t = t._replace(
                  split_feature=w(t.split_feature, nid, feat),
                  split_bin=w(t.split_bin, nid, thr),
                  default_left=w(t.default_left, nid, dl),
                  split_cat=w(t.split_cat, nid, catl),
                  cat_bitset=t.cat_bitset.at[nid].set(
                      jnp.where(ok, bitset, t.cat_bitset[nid])),
                  left_child=lc_arr, right_child=rc_arr,
                  split_gain=w(t.split_gain, nid, st["best_gain"][bl]),
                  internal_value=w(t.internal_value, nid,
                                   leaf_output(pg, ph, hp.lambda_l1,
                                               hp.lambda_l2,
                                               hp.max_delta_step)),
                  internal_count=w(t.internal_count, nid, pc),
                  leaf_depth=w(w(t.leaf_depth, bl, d), nl, d),
                  leaf_value=w(w(t.leaf_value, bl, lo), nl, ro),
                  leaf_count=w(w(t.leaf_count, bl, lcn), nl, rcn),
                  leaf_weight=w(w(t.leaf_weight, bl, lh), nl, rh),
                  num_leaves=jnp.where(ok, nl + 1, t.num_leaves),
              )
              st["sum_g"] = w(w(st["sum_g"], bl, lg), nl, rg)
              st["sum_h"] = w(w(st["sum_h"], bl, lh), nl, rh)
              st["count"] = w(w(st["count"], bl, lcn), nl, rcn)
              st["parent_node"] = w(w(st["parent_node"], bl, nid), nl, nid)
              st["parent_side"] = w(w(st["parent_side"], bl, 0), nl, 1)
              if hp.use_monotone:
                  st["leaf_min"] = w(w(st["leaf_min"], bl, lmin_l), nl, lmin_r)
                  st["leaf_max"] = w(w(st["leaf_max"], bl, lmax_l), nl, lmax_r)
              # split leaves' cached gains are consumed
              st["best_gain"] = st["best_gain"].at[bl].set(
                  jnp.where(ok, NEG_INF, st["best_gain"][bl]))
              if use_boxes:
                  # per-SPLIT bound refresh, same cadence as the strict
                  # learner: a leaf split later in this round sees the
                  # updated outputs of leaves split earlier (without this,
                  # two order-adjacent leaves split in one round could
                  # violate the constraint)
                  lower, upper = box_bounds(
                      st["leaf_lo"], st["leaf_hi"], t.leaf_value,
                      monotone, t.num_leaves)
                  st["leaf_min"] = jnp.where(ok, lower, st["leaf_min"])
                  st["leaf_max"] = jnp.where(ok, upper, st["leaf_max"])

          # smaller-child bookkeeping first: the fused partition kernel
          # emits the NEXT histogram pass's compaction keys, so it needs
          # the smaller-leaf set up front (state counts are already
          # updated by the record loop above)
          safe_nl = jnp.where(valid, new_leaves, L - 1)
          l_cnt = st["count"][parents]
          r_cnt = st["count"][safe_nl]
          smaller = jnp.where(l_cnt <= r_cnt, parents, safe_nl)

          if cegb is not None:
              # the round's K splits acquire their features for their
              # whole parent leaves (strict grower: cegb_used.at[feat],
              # cegb_rows |= in_parent & feat — here as one scatter-or +
              # one [n, K] x [K, F] matmul while ``lor`` still maps rows
              # to the split parents).  Splits later in this round see
              # earlier splits' acquisitions only at the NEXT round's
              # penalty refresh — the same one-round lag the batched
              # monotone/interaction paths document.
              feats_c = st["best_feat"][parents]                   # [K]
              st["cegb_used"] = st["cegb_used"].at[
                  jnp.where(valid, feats_c, 0)].max(valid)
              if use_lazy:
                  feat_oh = ((feats_c[:, None]
                              == lax.iota(jnp.int32, num_f)[None, :])
                             & valid[:, None]).astype(jnp.float32)  # [K, F]

                  # block-scanned [blk, K] x [K, F] matmuls: a single
                  # dense [K, n] f32 operand would be ~1.7 GB at 1e7
                  # rows x K=42 — the scan keeps the transient at
                  # [K, blk] while computing the identical result
                  def mark_block(_, xs):
                      lor_b, m_b = xs
                      ip = ((lor_b[None, :] == parents[:, None])
                            & valid[:, None]
                            & (m_b > 0)[None, :])                  # [K, blk]
                      return None, lax.dot_general(
                          ip.astype(jnp.float32).T, feat_oh,
                          (((1,), (0,)), ((), ()))) > 0.0          # [blk, F]

                  _, upd = lax.scan(
                      mark_block, None,
                      (jnp.pad(lor, (0, cegb_pad), constant_values=-1)
                       .reshape(cegb_nb, cegb_blk),
                       jnp.pad(mask_f, (0, cegb_pad))
                       .reshape(cegb_nb, cegb_blk)))
                  st["cegb_rows"] = st["cegb_rows"] | \
                      upd.reshape(-1, num_f)[:n]

          # ---- all K partitions in ONE widened pass (each row belongs to
          # at most one split parent, so the K moves compose by summation)
          sort_key = None
          with jax.named_scope("partition"):
              feats_k = st["best_feat"][parents]                      # [K]
              if fuse_partition:
                  col_k, lo_k, hi_k, pos_k, dl_k, miss_k = split_ranges(
                      feats_k, st["best_thr"][parents],
                      st["best_dl"][parents], nan_bin, bundle, hp.n_bins)
                  sets_k = ()
                  if hp.has_categorical:
                      # the slots' left sets as words of 32 bins, and which
                      # slots go by theirs: the split search's part of the
                      # hand-over, under its scope
                      with jax.named_scope("find_splits"), \
                              jax.named_scope("cat_bitset"):
                          sets_k = (
                              pack_left_bins(jnp.stack(bitsets) if use_boxes
                                             else bitsets_arr),
                              is_cat[feats_k].astype(jnp.int32))
                  lor, sort_key = partition_select_pallas(
                      bins_t, lor, mask_f.astype(jnp.int32),
                      col_k, lo_k, hi_k, pos_k, dl_k.astype(jnp.int32),
                      miss_k.astype(jnp.int32),
                      parents, new_leaves, valid.astype(jnp.int32),
                      smaller, *sets_k,
                      rows_per_block=min(hp.rows_per_block, 2048),
                      interpret=not _use_pallas())
              else:
                  cols_k = jax.vmap(
                      lambda f: _feature_bin_of_rows(bins_t, bundle, f))(
                          feats_k)
                  thr_k = st["best_thr"][parents][:, None]
                  dl_k = st["best_dl"][parents][:, None]
                  nanb_k = nan_bin[feats_k][:, None]
                  go_left_k = jnp.where(cols_k == nanb_k, dl_k,
                                        cols_k <= thr_k)
                  if hp.has_categorical:
                      bitsets_k = (jnp.stack(bitsets) if use_boxes
                                   else bitsets_arr)              # [K, B]
                      cat_k = is_cat[feats_k][:, None]                # [K, 1]
                      go_cat_k = jnp.take_along_axis(bitsets_k, cols_k,
                                                     axis=1)
                      go_left_k = jnp.where(cat_k, go_cat_k, go_left_k)
                  in_parent = (lor[None, :] == parents[:, None]) \
                      & valid[:, None]                                # [K, n]
                  move = in_parent & ~go_left_k                       # [K, n]
                  target = jnp.sum(move * new_leaves[:, None], axis=0)  # [n]
                  lor = jnp.where(jnp.any(move, axis=0), target, lor)

          st["tree"] = t
          st["leaf_of_row"] = lor
          st["n_splits"] = st["n_splits"] + jnp.sum(valid.astype(jnp.int32))
          st["progress"] = jnp.any(valid)

          # ---- ONE widened pass: histograms of the K smaller children
          with jax.named_scope("round_hist"):
              # masked row count of each smaller child (0 for invalid
              # slots) saves the membership reduction in the compaction
              # path.  Under shard_map the state counts are GLOBAL
              # (psum-ed) while compaction is per-shard, so pass no counts
              # there (recomputed locally).
              with jax.named_scope("hist_update"):
                  small_cnt = (jnp.where(valid, jnp.minimum(l_cnt, r_cnt),
                                         0.0)
                               if axis_name is None else None)

              def hist_call(lv, cnts, skey=None):
                  h = histogram_for_leaves_auto(
                      bins, bins_t, grad, hess, lor, lv, row_mask,
                      n_bins=hp.n_bins, rows_per_block=hp.rows_per_block,
                      hist_dtype=hp.hist_dtype, axis_name=hist_axis,
                      counts=cnts, bins_words=bins_words, sort_key=skey,
                      hist_kernel=hp.hist_kernel, bins_words_t=words_t)
                  with jax.named_scope("hist_update"):
                      return _scaled(h)

              # hist_update: what the round does around the histogram
              # call — parent minus smaller child, the writes into the
              # histogram state or pool, slot allocation
              left_small = (l_cnt <= r_cnt)[:, None, None, None]
              if not pooled:
                  # the fused kernel's keys target exactly the `smaller`
                  # set; the pooled path's extended leaf set rebuilds its
                  # own keys
                  h_small = hist_call(smaller, small_cnt, sort_key)
                  with jax.named_scope("hist_update"):
                      h_parent = _channels_last(read_slabs(st["hist"], parents))
                      h_large = h_parent - h_small
                      h_left = jnp.where(left_small, h_small, h_large)
                      h_right = jnp.where(left_small, h_large, h_small)
                      st["hist"] = write_children(
                          st["hist"], parents, safe_nl, valid, h_left,
                          h_right)
              else:
                  # -- bounded pool: parents with an evicted histogram get
                  # BOTH children computed directly (no subtraction);
                  # the widened pass carries K smaller + up-to-K larger
                  with jax.named_scope("hist_update"):
                      p_slot = st["leaf_slot"][parents]            # [K]
                      present = (p_slot >= 0) & valid
                      larger = jnp.where(l_cnt <= r_cnt, safe_nl, parents)
                      need_direct = valid & ~present
                      large_cnt = jnp.where(need_direct,
                                            jnp.maximum(l_cnt, r_cnt), 0.0)
                      leaves_ext = jnp.concatenate(
                          [smaller, jnp.where(need_direct, larger, L - 1)])
                      # counts are GLOBAL under shard_map while compaction is
                      # per-shard — same gate as the non-pooled path: let the
                      # histogram op recompute local counts there
                      ext_cnt = (jnp.concatenate([small_cnt, large_cnt])
                                 if axis_name is None else None)
                  h_ext = hist_call(leaves_ext, ext_cnt)
                  with jax.named_scope("hist_update"):
                      h_small = h_ext[:Kr]
                      h_parent = _channels_last(
                          read_slabs(st["hist"], jnp.maximum(p_slot, 0)))
                      h_large = jnp.where(present[:, None, None, None],
                                          h_parent - h_small, h_ext[Kr:])
                      h_left = jnp.where(left_small, h_small, h_large)
                      h_right = jnp.where(left_small, h_large, h_small)

                      # -- slot allocation: free slots first, then evict the
                      # lowest-cached-gain occupants; this round's parent
                      # slots are locked (they become the left children's)
                      slot_leaf = st["slot_leaf"]                  # [P+1]
                      leaf_slot = st["leaf_slot"]                  # [L+1]
                      locked = jnp.zeros((P + 1,), bool).at[
                          jnp.where(present, p_slot, P)].set(True)[:P]
                      occ = slot_leaf[:P]
                      occ_gain = jnp.where(occ >= 0,
                                           st["best_gain"][jnp.maximum(occ, 0)],
                                           -jnp.inf)
                      order = jnp.argsort(
                          jnp.where(locked, jnp.inf, occ_gain))    # [P]
                      req = jnp.concatenate([need_direct, valid])  # [2K]
                      pos = jnp.cumsum(req.astype(jnp.int32)) - 1
                      alloc = jnp.where(req, order[jnp.clip(pos, 0, P - 1)], P)
                      # evict old occupants of granted slots
                      evicted = jnp.where(alloc < P,
                                          slot_leaf[jnp.minimum(alloc, P)], -1)
                      leaf_slot = leaf_slot.at[
                          jnp.where(evicted >= 0, evicted, L)].set(-1)
                      slot_l = jnp.where(present, p_slot, alloc[:Kr])
                      slot_r = alloc[Kr:]
                      tgt_l = jnp.where(valid, slot_l, P)
                      tgt_r = jnp.where(valid, slot_r, P)
                      st["hist"] = write_children(
                          st["hist"], slot_l, slot_r, valid, h_left, h_right)
                      slot_leaf = slot_leaf.at[tgt_l].set(
                          jnp.where(valid, parents, -1))
                      slot_leaf = slot_leaf.at[tgt_r].set(
                          jnp.where(valid, safe_nl, -1))
                      leaf_slot = leaf_slot.at[
                          jnp.where(valid, parents, L)].set(slot_l)
                      leaf_slot = leaf_slot.at[
                          jnp.where(valid, safe_nl, L)].set(slot_r)
                      st["slot_leaf"] = slot_leaf.at[P].set(-1)
                      st["leaf_slot"] = leaf_slot.at[L].set(-1)

          # ---- child best splits, vmapped over the 2K children
          with jax.named_scope("find_splits"):
              kids = jnp.concatenate([parents, safe_nl])              # [2K]
              kid_hist = jnp.concatenate([h_left, h_right], axis=0)
              depths = st["tree"].leaf_depth[kids]
              # deterministic per-node keys folded on (split node id, side)
              # — unique per evaluation (a leaf id would COLLIDE between a
              # parent and its left child, freezing the by-node subset down
              # every left spine); same uniqueness source as the strict
              # learner's split-counter fold
              sides = jnp.concatenate([jnp.zeros((Kr,), jnp.int32),
                                       jnp.ones((Kr,), jnp.int32)])
              node2 = jnp.concatenate([node_ids, node_ids])
              keys = (jax.vmap(lambda nd, sd: jax.random.fold_in(
                          rng_key, nd * 2 + sd + 1))(node2, sides)
                      if use_rng else None)
              if use_paths or use_bynode:
                  paths_k = (st["path_f"][kids] if use_paths else
                             jnp.zeros((2 * Kr, num_f), bool))
                  fms = jax.vmap(node_mask)(
                      paths_k, keys) if use_bynode else \
                      jax.vmap(node_mask)(paths_k)
              else:
                  fms = (jnp.broadcast_to(feature_mask, (2 * Kr,)
                                          + feature_mask.shape)
                         if feature_mask is not None else None)
              pouts = st["tree"].leaf_value[kids]
              if cegb is not None:
                  # per-child penalty vectors from the round-updated
                  # acquisition state; the lazy not-yet-computed row
                  # counts for all 2K children come from block-scanned
                  # [2K, blk] x [blk, F] contractions over the
                  # POST-partition row map (bounded transients, same
                  # result as one [2K, n] x [n, F] matmul)
                  if use_lazy:
                      def count_block(acc, xs):
                          lor_b, m_b, rows_b = xs
                          ks = ((lor_b[None, :] == kids[:, None])
                                & (m_b > 0)[None, :])       # [2K, blk]
                          return acc + lax.dot_general(
                              ks.astype(jnp.float32),
                              (~rows_b).astype(jnp.float32),
                              (((1,), (0,)), ((), ()))), None

                      cnt_k, _ = lax.scan(
                          count_block,
                          jnp.zeros((2 * Kr, num_f), jnp.float32),
                          (jnp.pad(st["leaf_of_row"], (0, cegb_pad),
                                   constant_values=-1)
                           .reshape(cegb_nb, cegb_blk),
                           jnp.pad(mask_f, (0, cegb_pad))
                           .reshape(cegb_nb, cegb_blk),
                           jnp.pad(st["cegb_rows"],
                                   ((0, cegb_pad), (0, 0)),
                                   constant_values=True)
                           .reshape(cegb_nb, cegb_blk, num_f)))
                  else:
                      cnt_k = None
                  pens = jax.vmap(cegb_penalty, in_axes=(None, 0, 0))(
                      st["cegb_used"],
                      cnt_k if use_lazy else jnp.zeros((2 * Kr, 1)),
                      st["count"][kids])
              else:
                  pens = None
              if use_adv:
                  # advanced monotone: per-(feature, threshold) child
                  # bounds for each kid's upcoming split evaluation,
                  # from the round-refreshed boxes (strict learner
                  # computes the same right after each split; here the
                  # kids see ALL of this round's box updates)
                  from .monotone import advanced_split_bounds
                  advs = jax.vmap(
                      lambda lf: advanced_split_bounds(
                          st["leaf_lo"], st["leaf_hi"],
                          st["tree"].leaf_value, monotone,
                          st["tree"].num_leaves, lf, hp.n_bins))(kids)
              else:
                  advs = None
              res, kb = jax.vmap(
                  child_best,
                  in_axes=(0, 0, 0, 0, 0, 0, 0,
                           None if fms is None else 0, 0,
                           None if keys is None else 0,
                           None if pens is None else 0,
                           None if advs is None else 0))(
                  kid_hist, st["sum_g"][kids],
                  st["sum_h"][kids], st["count"][kids],
                  depths, st["leaf_min"][kids],
                  st["leaf_max"][kids], fms, pouts, keys, pens, advs)
              ok2 = jnp.concatenate([valid, valid])
              gains2 = jnp.where(ok2, res.gain, st["best_gain"][kids])
              st["best_gain"] = st["best_gain"].at[kids].set(gains2)
              for name, field in (("best_feat", res.feature),
                                  ("best_thr", res.threshold),
                                  ("best_var", res.variant),
                                  ("best_lg", res.left_sum_g),
                                  ("best_lh", res.left_sum_h),
                                  ("best_lc", res.left_count)):
                  st[name] = st[name].at[kids].set(
                      jnp.where(ok2, field, st[name][kids]))
              st["best_dl"] = st["best_dl"].at[kids].set(
                  jnp.where(ok2, res.default_left, st["best_dl"][kids]))
              if hp.has_categorical:
                  st["best_bitset"] = st["best_bitset"].at[kids].set(
                      jnp.where(ok2[:, None], kb, st["best_bitset"][kids]))
          return st

      return round_body

    # Warmup: the masked histogram kernel's MXU cost scales with its 3*K
    # value channels, so rounds whose frontier holds < K splittable leaves
    # burn ~K/frontier of a full pass for nothing (profiled: the first ~5
    # rounds were 6 full-width passes = 35 ms of a 94 ms tree).  Early
    # rounds therefore run width-matched bodies (K=1,2,4,...) — identical
    # selection semantics, just fewer masked channels per pass.  Gated on
    # data size (static at trace time): each width is its own kernel
    # compilation, worth it only when passes are expensive.
    # the round loops: what a round does outside its three scopes
    # (choosing the K parents, the tree's bookkeeping, CEGB) is
    # ``tree_select``; partition / round_hist / find_splits nest inside
    with jax.named_scope("tree_select"):
        if forced is not None:
            # forced-split phase: one K=1 round per schedule entry, in BFS
            # order (entry index == split counter, as in the strict learner);
            # a failed entry aborts the remaining schedule
            f_leaf0 = forced[0]
            state = lax.while_loop(
                lambda st: (st["n_splits"] < L - 1) & ~st["force_failed"]
                & (f_leaf0[jnp.minimum(st["n_splits"],
                                       f_leaf0.shape[0] - 1)] >= 0),
                make_round_body(1, use_forced=True), state)
            # a failed/exhausted forced round leaves progress False; the
            # gain-based loops below must still run
            state["progress"] = jnp.bool_(True)
        if warmup:
            # width QUADRUPLING (1, 4, 16, ...): each width always covers the
            # frontier (it at most doubles per round), and since kernel cost
            # is K-independent below 128 channels (docs/PERF_NOTES.md round
            # 3), fewer warmup rounds beat finer width matching — profiled
            # ~2 full passes saved per tree vs doubling.  Skipped after a
            # forced phase: the forced frontier can exceed the warmup widths.
            # Round 6: the ladder only pays where the K<=4 masked pass takes
            # the radix-JOINT kernel (auto dispatch at >= 128 bins); every
            # other mode's kernel is K-independent, so those configs SEED the
            # round loop at full width straight from the root histogram —
            # identical selections (top-k of a sub-K frontier picks the same
            # leaves at any width), ~2 fewer compiled round bodies and no
            # narrow warmup passes (ops/histogram.py hist_dispatch).
            for kw in warmup_widths(n, K, hp, forced):
                state = lax.cond(state["progress"] & (state["n_splits"] < L - 1),
                                 make_round_body(kw), lambda st: st, state)
        # loop until the tree is full or a round makes no progress — a fixed
        # ceil((L-1)/K) budget would starve narrow-frontier (chain-shaped) trees
        # where only ~1 leaf per round carries positive gain
        state = lax.while_loop(
            lambda st: st["progress"] & (st["n_splits"] < L - 1),
            make_round_body(K), state)
    tree_out = state["tree"]._replace(leaf_path=state["path_f"])
    if n * num_shards >= _F32_EXACT_ROWS:
        tree_out = tree_out._replace(leaf_count=_recount_leaves(
            state["leaf_of_row"], mask_f, L, axis_name))
    if cegb is not None:
        new_cegb = cegb._replace(
            feature_used=state["cegb_used"],
            used_rows=state["cegb_rows"] if use_lazy else None)
        return tree_out, state["leaf_of_row"], new_cegb
    return tree_out, state["leaf_of_row"]
