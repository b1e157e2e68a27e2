"""On-device prediction over binned data.

TPU-native re-design of the reference score updater / predictor (reference:
src/boosting/score_updater.hpp:21 valid-score ``AddScore`` via full tree
traversal; src/boosting/cuda/cuda_score_updater.hpp:17).  The branchy
per-row walk (tree.h:137 ``Predict``) becomes a frontier iteration: every row
carries its current node id, each step gathers that node's split and moves
one level — all rows advance in lockstep under ``lax.while_loop``, so one
tree costs depth × O(n) gathers instead of per-row branching.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..learner.grower import TreeArrays, split_ranges
from ..ops.round_fuse import bin_in_set, pack_left_bins, xor_ranges


@functools.partial(jax.jit, static_argnames=("has_categorical",))
def predict_bins_tree(tree: TreeArrays, bins: jax.Array,
                      nan_bin: jax.Array, bundle=None,
                      has_categorical: bool = True) -> jax.Array:
    """Leaf VALUE per row for one device tree over binned features.

    tree: TreeArrays (packed feature indices, bin thresholds);
    bins: uint8 [n, F]; nan_bin: i32 [F]; bundle: optional EFB tables
    (learner/grower.py DeviceBundle) when ``bins`` is bundled.
    ``has_categorical=False`` skips the per-row cat-bitset table gather
    (the slowest TPU primitive) on all-numeric models.
    """
    leaf = predict_bins_leaf(tree, bins, nan_bin, bundle, has_categorical)
    return tree.leaf_value[leaf]


@functools.partial(jax.jit, static_argnames=("has_categorical",))
def predict_bins_leaf(tree: TreeArrays, bins: jax.Array,
                      nan_bin: jax.Array, bundle=None,
                      has_categorical: bool = True) -> jax.Array:
    n = bins.shape[0]
    rows = lax.iota(jnp.int32, n)
    node0 = jnp.zeros((n,), jnp.int32)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        active = node >= 0
        safe = jnp.maximum(node, 0)
        feat = jnp.maximum(tree.split_feature[safe], 0)
        thr = tree.split_bin[safe]
        dl = tree.default_left[safe]
        cat = tree.split_cat[safe]
        if bundle is None:
            col = bins[rows, feat].astype(jnp.int32)
        else:
            phys = bins[rows, bundle.feat_col[feat]].astype(jnp.int32)
            col = bundle.inv_table[feat, phys]
        nb = nan_bin[feat]
        go_num = col <= thr
        if has_categorical:
            cat_left = tree.cat_bitset[safe, col]
            go_num = jnp.where(cat, cat_left, go_num)
        go_left = jnp.where(col == nb, dl, go_num)
        nxt = jnp.where(go_left, tree.left_child[safe], tree.right_child[safe])
        return jnp.where(active, nxt, node)

    node = lax.while_loop(cond, body, node0)
    return (-node - 1).astype(jnp.int32)


def tree_path_masks(tree: TreeArrays):
    """DEVICE-side leaf path-direction masks from a grown tree's arrays.

    The forest predictors build mpos/mneg on the host from the model
    list; in-training valid scoring (the fused scan, the classic loop's
    per-iteration update) only has the traced ``TreeArrays``, so the
    masks are derived on device: child pointers invert into parent
    pointers with masked scatters (leaf l is encoded ``-(l+1)``; node
    validity is ``i < num_leaves - 1`` since nodes are created
    sequentially — a valid node's ``left_child == -1`` genuinely means
    leaf 0), then every leaf walks up its ancestor chain in lockstep
    (``lax.while_loop``, bounded by tree depth, [L, ni]-sized work).

    Returns (mpos bf16 [L, ni], mneg bf16 [L, ni], depth i32 [L]) —
    depth is counted during the walk, NOT read from ``leaf_depth``, so
    stub arrays (model-file imports) work too."""
    ni = tree.left_child.shape[0]
    L = ni + 1
    iota_n = jnp.arange(ni, dtype=jnp.int32)
    valid_node = iota_n < tree.num_leaves - 1
    lc, rc = tree.left_child, tree.right_child

    def scatter(dst, tgt, val):
        return dst.at[tgt].set(val, mode="drop")

    node_par = jnp.full((ni + 1,), -1, jnp.int32)
    node_side = jnp.zeros((ni + 1,), jnp.int32)
    node_par = scatter(node_par, jnp.where(valid_node & (lc >= 0), lc,
                                           ni + 1), iota_n)
    node_par = scatter(node_par, jnp.where(valid_node & (rc >= 0), rc,
                                           ni + 1), iota_n)
    node_side = scatter(node_side, jnp.where(valid_node & (rc >= 0), rc,
                                             ni + 1), 1)
    leaf_par = jnp.full((L,), -1, jnp.int32)
    leaf_side = jnp.zeros((L,), jnp.int32)
    leaf_par = scatter(leaf_par, jnp.where(valid_node & (lc < 0),
                                           -lc - 1, L), iota_n)
    leaf_par = scatter(leaf_par, jnp.where(valid_node & (rc < 0),
                                           -rc - 1, L), iota_n)
    leaf_side = scatter(leaf_side, jnp.where(valid_node & (rc < 0),
                                             -rc - 1, L), 1)
    rows = jnp.arange(L)

    def cond(c):
        return jnp.any(c[0] >= 0)

    def body(c):
        cur, side, mp, mn, dep = c
        act = cur >= 0
        tgt = jnp.where(act, cur, ni)
        mp = mp.at[rows, tgt].add(
            jnp.where(act & (side == 0), 1.0, 0.0), mode="drop")
        mn = mn.at[rows, tgt].add(
            jnp.where(act & (side == 1), 1.0, 0.0), mode="drop")
        safe = jnp.maximum(cur, 0)
        nxt = jnp.where(act, node_par[safe], -1)
        nside = jnp.where(act, node_side[safe], 0)
        return (nxt, nside, mp, mn, dep + act.astype(jnp.int32))

    zero = jnp.zeros((L, ni), jnp.float32)
    _, _, mpos, mneg, depth = lax.while_loop(
        cond, body, (leaf_par, leaf_side, zero, zero,
                     jnp.zeros((L,), jnp.int32)))
    return (mpos.astype(jnp.bfloat16), mneg.astype(jnp.bfloat16), depth)


#: row-block width for predict_bins_tree_matmul — bounds the [ni, blk]
#: decision-bit planes (~66 MB bf16 at 255 leaves)
_MATMUL_VALID_BLOCK = 131_072


@functools.partial(jax.jit, static_argnames=("n_bins", "has_categorical"))
def predict_bins_tree_matmul(tree: TreeArrays, bins_t: jax.Array,
                             nan_bin: jax.Array, bundle=None,
                             n_bins: int = 256,
                             has_categorical: bool = False) -> jax.Array:
    """Leaf VALUE per row for one device tree — the matmul
    path-aggregation formulation of ``predict_bins_tree`` (round-6
    fused-valid lift, VERDICT r5 #4: the per-iteration frontier walk
    cost ~107 ms/iter at 1M/200k — depth x O(n) random gathers, the
    slowest TPU primitive).  NUMERIC trees whose every split is a range
    predicate on its physical column (learner/grower.py
    ``split_ranges``): unbundled features, and members of an EFB plan
    with ranges (``bundle.search``).  With ``has_categorical`` a node
    that splits by a set of its column's bins is decided as the partition
    kernel decides it (ops/round_fuse.py): the set as words of 32 bins,
    the word by compares on ``bin >> 5``, the bit by a shift.  The inverse
    table of a plan without ranges is a per-row gather; those models keep
    the frontier walk.

    ``bins_t``: u8/i32 [F, n] TRANSPOSED valid bins (cached by the
    booster; bundle columns under a plan).  Every node's decision bit
    comes from one contiguous row
    gather; rows match leaves by counting satisfied path conditions
    (two [L, ni] x [ni, blk] bf16 matmuls per row block — small-integer
    exact, so the output is BIT-identical to the frontier walk: exactly
    one real leaf matches per row and dead slots contribute +0.0)."""
    n = bins_t.shape[1]
    mpos, mneg, depth = tree_path_masks(tree)
    col, *ranges = split_ranges(
        jnp.maximum(tree.split_feature, 0), tree.split_bin,
        tree.default_left, nan_bin, bundle, n_bins)
    # the partition kernel's two ranges: left is being in exactly one
    a1, n1, a2, n2 = (a[:, None] for a in xor_ranges(*ranges))
    value = tree.leaf_value
    if has_categorical:
        words = pack_left_bins(tree.cat_bitset)             # [W, ni]
        by_set = tree.split_cat[:, None]

    def block(b0, rows):
        cols = lax.dynamic_slice_in_dim(bins_t, b0, rows, axis=1)[col] \
            .astype(jnp.int32)                              # [ni, blk]
        go = ((cols >= a1) & (cols - a1 < n1)) \
            != ((cols >= a2) & (cols - a2 < n2))
        if has_categorical:
            go = jnp.where(by_set, bin_in_set(cols, list(words)) != 0, go)
        bits = go.astype(jnp.bfloat16)
        counts = lax.dot_general(
            mpos, bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + lax.dot_general(
            mneg, 1.0 - bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [L, blk]
        sel = counts.astype(jnp.int32) == depth[:, None]
        return jnp.sum(value[:, None] * sel.astype(jnp.float32), axis=0)

    outs = []
    b0 = 0
    while b0 < n:
        rows = min(_MATMUL_VALID_BLOCK, n - b0)
        outs.append(block(b0, rows))
        b0 += rows
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


class ForestArrays(NamedTuple):
    """Stacked per-tree operands for the matmul batch predictor
    (``predict_numeric_forest``).  Built host-side by
    boosting/gbdt.py ``_forest_arrays`` from the trained model list."""
    feat: jax.Array     # i32 [T, ni] packed split feature per node
    thr: jax.Array      # i32 [T, ni] bin threshold per node
    dl: jax.Array       # bool [T, ni] missing default-left
    nanb: jax.Array     # i32 [T, ni] nan bin of the node's feature
    mpos: jax.Array     # bf16 [T, L, ni] 1 where leaf's path expects LEFT
    mneg: jax.Array     # bf16 [T, L, ni] 1 where leaf's path expects RIGHT
    depth: jax.Array    # i32 [T, L] path length (-1 for dead leaf slots)
    value: jax.Array    # f32 [T, L] leaf values (shrunk, bias included)
    cls: jax.Array      # i32 [T] score column (tree index % num_class)


class BitsetForest(NamedTuple):
    """Stacked operands for the GENERAL matmul batch predictor
    (``predict_bitset_forest``) — categorical, EFB-bundled and linear
    models included.  Decisions evaluate in LOGICAL bin space
    (Dataset.bin_external_pred), where numeric nodes are plain
    ``bin <= thr`` compares even under EFB bundling, and only TRUE
    categorical nodes carry bitsets — over the narrow categorical bin
    range Bc (max cat bins + 2 sentinel bins for unseen-category / NaN,
    reproducing the reference raw-space walk, tree.cpp
    CategoricalDecision).  A first full-width-bitset formulation
    measured 28.6 s at 1M x 100 trees — HBM-bound on [B2, n] one-hot
    planes; the hybrid keeps the numeric path's traffic and adds only
    [Bc, n] planes for the few categorical features.  Built by
    boosting/gbdt.py ``_forest_bitset_arrays``."""
    feat: jax.Array     # i32 [T, ni] packed LOGICAL feature per node
    thr: jax.Array      # i32 [T, ni] logical-bin threshold per node
    dl: jax.Array       # bool [T, ni] missing default-left
    nanb: jax.Array     # i32 [T, ni] nan bin of the node's feature
    catn: jax.Array     # i32 [T, C] cat node ids (ni = dead pad slot)
    catf: jax.Array     # i32 [T, C] cat node's packed feature
    catb: jax.Array     # bf16 [T, C, Bc] bin-membership incl sentinels
    mpos: jax.Array     # bf16 [T, L, ni] 1 where leaf's path expects LEFT
    mneg: jax.Array     # bf16 [T, L, ni] 1 where leaf's path expects RIGHT
    depth: jax.Array    # i32 [T, L] path length (-1 for dead leaf slots)
    value: jax.Array    # f32 [T, L] leaf values (shrunk, bias included)
    cls: jax.Array      # i32 [T] score column (tree index % num_class)


class LinearLeaves(NamedTuple):
    """Optional linear-leaf extension for ``predict_bitset_forest``
    (reference tree.h:587 linear branch): out = const + x·coeff per
    leaf, falling back to the plain leaf value when any of the leaf's
    features is NaN."""
    const: jax.Array     # f32 [T, L] leaf intercept minus tree bias
    coeff: jax.Array     # f32 [T, L, Fr] dense coefficients (raw cols)
    featmask: jax.Array  # bf16 [T, L, Fr] 1 where the leaf uses the col


def _leaf_onehot(feat, thr, dl, nanb, mpos, mneg, depth, bins_t,
                 cat=None, int8: bool = False):
    """Boolean leaf one-hot [L, n] for ONE stacked tree: decision bits
    from contiguous row gathers, rows matched to leaves by counting
    satisfied path conditions with two [L, ni] x [ni, n] matmuls.

    Shared by the value predictors and ``predict_forest_leaves``.  All
    operands are small integers, so the counts are exact in either
    operand dtype: bf16 ops / f32 accumulation (``int8=False``, the MXU
    default) or int8 ops / i32 accumulation (``int8=True``) produce the
    SAME integer counts — the leaf selection is dtype-invariant, which
    is what lets serving offer int8 inference without an output change.
    ``cat``: optional (catn, catf, catb, cat_feats, iota_b) categorical
    extension (see ``BitsetForest``)."""
    op_t = jnp.int8 if int8 else jnp.bfloat16
    acc_t = jnp.int32 if int8 else jnp.float32
    one = 1 if int8 else 1.0
    cols = bins_t[feat].astype(jnp.int32)               # [ni, n]
    go = jnp.where(cols == nanb[:, None], dl[:, None],
                   cols <= thr[:, None])
    bits = go.astype(op_t)
    if cat is not None:
        catn, catf, catb, cat_feats, iota_b = cat
        cbits = jnp.zeros((catn.shape[0], bins_t.shape[1]), acc_t)
        catb_op = catb.astype(op_t)
        for cf in cat_feats:
            oh_cf = (bins_t[cf][None, :] == iota_b[:, None]
                     ).astype(op_t)                     # [Bc, n]
            sel_cf = (catf == cf).astype(op_t)[:, None]
            cbits = cbits + lax.dot_general(
                catb_op * sel_cf, oh_cf, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_t)           # [C, n]
        # dead pad slots aim at row ni and drop
        bits = bits.at[catn].set(cbits.astype(op_t), mode="drop")
    counts = lax.dot_general(
        mpos.astype(op_t), bits, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_t) + lax.dot_general(
        mneg.astype(op_t), one - bits, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_t)                   # [L, n] exact ints
    return (counts.astype(jnp.int32) == depth[:, None]) \
        & (depth[:, None] >= 0)


@functools.partial(jax.jit, static_argnames=("k", "cat_feats", "int8"))
def predict_bitset_forest(fb: BitsetForest, bins_t: jax.Array, k: int,
                          cat_feats: tuple = (),
                          lin: "LinearLeaves" = None,
                          raw: jax.Array = None,
                          raw_nan: jax.Array = None,
                          int8: bool = False) -> jax.Array:
    """Batched prediction over ANY stacked forest — the round-5
    generalization of ``predict_numeric_forest`` to categorical /
    EFB-bundled / linear models (VERDICT r4 #5: those kept
    15-30x-slower walks).

    bins_t: i32 [F, n] LOGICAL bins (categorical columns sentinel-coded
    for unseen/NaN — Dataset.bin_external_pred).  Numeric decisions are
    threshold compares exactly like the numeric path; each categorical
    node's bit is ``catb[c, bins_t[catf_c, r]]``, computed without
    per-row gathers as one narrow one-hot contraction per categorical
    feature (oh_cf [Bc, n]; products {0,1} exact in bf16) and
    row-scattered over the numeric bits.  ``cat_feats``: static tuple of
    packed categorical feature ids.

    ``lin``/``raw``/``raw_nan``: linear-leaf extension — raw [n, Fr] f32
    (NaN-zeroed), raw_nan bf16 [Fr, n] NaN indicators.
    """
    n = bins_t.shape[1]
    Bc = fb.catb.shape[-1]
    iota_b = lax.iota(jnp.int32, Bc)

    def tree_body(out, xs):
        if lin is not None:
            feat, thr, dl, nanb, catn, catf, catb, mpos, mneg, depth, \
                value, cls, lconst, lcoeff, lmask = xs
        else:
            feat, thr, dl, nanb, catn, catf, catb, mpos, mneg, depth, \
                value, cls = xs
        cat = (catn, catf, catb, cat_feats, iota_b) if cat_feats else None
        sel = _leaf_onehot(feat, thr, dl, nanb, mpos, mneg, depth,
                           bins_t, cat=cat, int8=int8)      # [L, n]
        if lin is None:
            contrib = jnp.sum(value[:, None] * sel.astype(jnp.float32),
                              axis=0)
        else:
            # linear leaves: const + raw·coeff, NaN rows in the leaf's
            # feature set fall back to the plain leaf value
            lin_out = lax.dot_general(
                lcoeff, raw, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                + lconst[:, None]                           # [L, n]
            nan_bad = lax.dot_general(
                lmask, raw_nan, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5   # [L, n]
            has_lin = jnp.any(lmask > 0, axis=1)[:, None]   # [L, 1]
            leaf_out = jnp.where(has_lin & ~nan_bad, lin_out,
                                 value[:, None])
            contrib = jnp.sum(jnp.where(sel, leaf_out, 0.0), axis=0)
        return out.at[:, cls].add(contrib), None

    out0 = jnp.zeros((n, k), jnp.float32)
    xs = fb if lin is None else tuple(fb) + tuple(lin)
    out, _ = lax.scan(tree_body, out0, xs)
    return out


@functools.partial(jax.jit, static_argnames=("k", "int8"))
def predict_numeric_forest(fa: ForestArrays, bins_t: jax.Array,
                           k: int, int8: bool = False) -> jax.Array:
    """Batched prediction over a stacked all-numeric forest — the
    matmul reformulation of tree traversal (TPU redesign of the
    reference's per-row walk, tree.h:137 ``Predict``).

    The frontier walk (``predict_bins_leaf``) pays depth x O(n) RANDOM
    gathers per tree — measured 0.68 s/tree at 1M rows on a v5e, gather
    being the slowest TPU primitive.  Here each tree instead computes
    every node's decision bit at once (``bins_t[feat]`` is a CONTIGUOUS
    row gather), then matches rows to leaves by counting satisfied
    path conditions with two [L, ni] x [ni, n] matmuls: a row lands in
    leaf l iff its count equals l's path length.  All operands are
    small integers, exact in bf16 (<= 256), so the MXU result is exact;
    the leaf one-hot contracts with the value vector for the output.
    ~250 GFLOP per 100-tree x 1M-row call — milliseconds of MXU time
    instead of seconds of gathers.
    """
    n = bins_t.shape[1]

    def tree_body(out, xs):
        feat, thr, dl, nanb, mpos, mneg, depth, value, cls = xs
        sel = _leaf_onehot(feat, thr, dl, nanb, mpos, mneg, depth,
                           bins_t, int8=int8)            # [L, n]
        contrib = jnp.sum(value[:, None] * sel.astype(jnp.float32),
                          axis=0)                        # [n]
        return out.at[:, cls].add(contrib), None

    out0 = jnp.zeros((n, k), jnp.float32)
    out, _ = lax.scan(tree_body, out0, fa)
    return out


@functools.partial(jax.jit, static_argnames=("cat_feats", "int8"))
def predict_forest_leaves(fb: BitsetForest, bins_t: jax.Array,
                          cat_feats: tuple = (),
                          int8: bool = False) -> jax.Array:
    """LEAF INDEX per row for every tree of a stacked forest — i32
    [T, n].  The serving tier's exact-mode device program: because the
    path-count matmuls are integer-exact (``_leaf_onehot``), the leaf a
    row lands in is independent of batch padding AND of the operand
    dtype (bf16 vs int8), so the host can finish the prediction in f64
    (gather leaf values, accumulate in tree order) and match the
    reference host walk BIT-FOR-BIT on the unpadded rows.

    Rows that are pure padding still land in SOME leaf (bin 0
    everywhere descends deterministically); callers slice them off.
    """
    Bc = fb.catb.shape[-1]
    iota_b = lax.iota(jnp.int32, Bc)

    def tree_body(carry, xs):
        feat, thr, dl, nanb, catn, catf, catb, mpos, mneg, depth, \
            value, cls = xs
        cat = (catn, catf, catb, cat_feats, iota_b) if cat_feats else None
        sel = _leaf_onehot(feat, thr, dl, nanb, mpos, mneg, depth,
                           bins_t, cat=cat, int8=int8)   # [L, n]
        # exactly one live leaf matches per row; argmax picks it
        return carry, jnp.argmax(sel, axis=0).astype(jnp.int32)

    _, leaves = lax.scan(tree_body, 0, tuple(fb))
    return leaves
