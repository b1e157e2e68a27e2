"""Exclusive Feature Bundling (EFB).

TPU-native re-design of the reference's feature bundling (reference:
src/io/dataset.cpp:107 ``FindGroups`` greedy conflict-bounded graph coloring,
``FastFeatureBundling`` :246, invoked from ``Dataset::Construct`` :362-366):
mutually-exclusive sparse features share one physical bin column, shrinking
the histogram pass (the dominant cost) from O(F_used) to O(F_bundled)
columns.

Layout differences from the reference are deliberate.  The reference's
``FeatureGroup`` owns per-group bin storage and split finding walks group
offsets; here the packed matrix simply has one uint8 column per bundle, and
two small host-precomputed index tables make the learner bundle-agnostic:

  * ``src_idx[f, b]``  — where virtual (per-feature) bin ``b`` of feature
    ``f`` lives inside its bundle column's histogram.  The per-leaf bundle
    histogram ``[Fb, B, C]`` is expanded to the virtual ``[Fv, B, C]`` by one
    gather, and each feature's *default* (most frequent) bin — which the
    bundle does not store — is reconstructed as ``leaf_total − rest``,
    exactly the reference's most-freq-bin completion
    (``Dataset::FixHistogram``, dataset.h:760).
  * ``inv_table[f, v]`` — bundle column value ``v`` → virtual bin of feature
    ``f`` (default bin when ``v`` belongs to another member).  Used by the
    partition step.

  * ``bundle_ranges`` — the same layout as RANGES: member ``f`` holds one
    contiguous segment ``[lo_f, hi_f]`` of its column, its bins in order
    with the default bin left out, so "virtual bin of f <= t" is the range
    predicate ``lo_f <= c <= pos_f(t)`` on the physical value ``c``, OR-ed
    with "c outside the segment" when the default bin is <= t.  The split
    search, the fused partition and the matmul valid scorer work on that
    predicate and never visit ``[Fv, B]`` space (learner/grower.py
    ``BundleSearch``); the two tables above remain for the members a
    range cannot state (categorical members, NaN bins).

Bundle encoding: column value 0 = every member at its default bin; member
``k`` with non-default bin ``b`` writes ``offset_k + rank_k(b)`` where
``rank_k`` skips the default bin (order-preserving, so numerical thresholds
survive).  Conflicting rows (two members non-default; possible only when
``max_conflict_rate > 0``) keep the first member, like the reference's
first-writer-wins push.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

from .binning import columns_first

MAX_BUNDLE_BINS = 256  # uint8 storage


class BundlePlan(NamedTuple):
    """Host-side bundling plan over the packed (used) features."""
    bundles: List[List[int]]      # per bundle: packed feature indices
    feat_col: np.ndarray          # i32 [Fv] — bundle column of each feature
    src_idx: np.ndarray           # i32 [Fv, B] — virtual bin -> bundle bin
    valid: np.ndarray             # bool [Fv, B] — virtual bin stored in bundle
    default_bin: np.ndarray       # i32 [Fv] — most frequent (implicit) bin
    inv_table: np.ndarray         # i32 [Fv, B] — bundle value -> virtual bin
    num_bundles: int

    @property
    def is_trivial(self) -> bool:
        return self.num_bundles == len(self.feat_col)


def plan_bundles(bins: np.ndarray, num_bins: np.ndarray,
                 max_conflict_rate: float = 0.0,
                 sample_cnt: int = 100_000,
                 max_total_bins: int = MAX_BUNDLE_BINS
                 ) -> Optional[BundlePlan]:
    """Greedy conflict-bounded bundling over the binned matrix.

    bins: uint8 [n, Fv] (virtual/used features); num_bins: i32 [Fv].
    ``max_total_bins`` caps a bundle's bin count — pass the dataset's
    pre-EFB device histogram width so bundling can only SHRINK the
    histogram tensor (fewer columns, same bin axis), never widen it.
    Returns None when bundling cannot merge anything (dense data).
    """
    n, num_f = bins.shape
    if num_f < 2:
        return None
    sample = bins if n <= sample_cnt else bins[
        np.random.default_rng(3).choice(n, sample_cnt, replace=False)]
    ns = sample.shape[0]

    # default (most frequent) bin per feature + non-default rows of the sample
    default_bin = np.zeros(num_f, np.int32)
    nz_rows = []
    sample = columns_first(sample)      # a feature's bins contiguous
    for f in range(num_f):
        counts = np.bincount(sample[f], minlength=int(num_bins[f]))
        default_bin[f] = int(np.argmax(counts))
        nz_rows.append(np.flatnonzero(sample[f] != default_bin[f]))
    return _plan_from_rows(nz_rows.__getitem__,
                           np.array([len(r) for r in nz_rows], np.int64),
                           default_bin, num_bins, ns, max_conflict_rate,
                           max_total_bins)


def plan_bundles_sparse(nz_rows, nz_counts: np.ndarray, num_bins: np.ndarray,
                        default_bin: np.ndarray, ns: int,
                        max_conflict_rate: float = 0.0,
                        max_total_bins: int = MAX_BUNDLE_BINS
                        ) -> Optional[BundlePlan]:
    """Bundling plan from each feature's non-default ROWS — the
    sparse-ingestion entry that never sees a dense [n, F] matrix (reference
    sparse_bin.hpp data feeding FastFeatureBundling).  ``nz_rows(f)`` gives
    feature f's rows among ``ns`` (a CSC column's indices: no copy),
    ``nz_counts`` their numbers; ``default_bin`` must be each feature's
    zero bin (implicit rows ARE zeros).  Conflicts are counted over all
    ``ns`` rows, so with ``ns`` the whole data set and
    ``max_conflict_rate = 0`` no row of it holds two members of a column."""
    if len(nz_counts) < 2:
        return None
    return _plan_from_rows(nz_rows, np.asarray(nz_counts, np.int64),
                           np.asarray(default_bin, np.int32), num_bins, ns,
                           max_conflict_rate, max_total_bins)


_WORD = 64      # bundles a packed mask word holds


def _plan_from_rows(nz_rows, nz_counts: np.ndarray, default_bin: np.ndarray,
                    num_bins: np.ndarray, ns: int, max_conflict_rate: float,
                    max_total_bins: int) -> Optional[BundlePlan]:
    num_f = len(nz_counts)
    max_total_bins = min(max_total_bins, MAX_BUNDLE_BINS)
    B = MAX_BUNDLE_BINS
    max_conflicts = int(max_conflict_rate * ns)
    # sparsest-last order (reference sorts by conflict degree; nonzero count
    # is the cheap proxy): densest features claim bundles first
    order = np.argsort(-nz_counts, kind="stable")

    bundle_members: List[List[int]] = []
    bundle_bins: List[int] = []
    # which bundles hold a non-default member in each row, packed: bit b of
    # covered[w][row] is bundle 64 w + b.  One gather of a feature's rows
    # answers "does it conflict" for 64 bundles at once, where a mask per
    # bundle costs a pass per bundle tried (4,228 features x 50 bundles
    # over 13M rows: the whole of set-up)
    covered: List[np.ndarray] = []
    for f in map(int, order):
        extra = int(num_bins[f]) - 1          # bins beyond the default
        rows = nz_rows(f)
        at = -1
        # a feature whose non-defaults cover most rows can't bundle usefully
        if nz_counts[f] * 2 < ns:
            for w, word in enumerate(covered):
                got = word[rows]
                hit = int(np.bitwise_or.reduce(got)) if len(got) else 0
                for b in range(min(_WORD, len(bundle_members) - _WORD * w)):
                    bi = _WORD * w + b
                    if bundle_bins[bi] + extra > max_total_bins:
                        continue
                    if (hit >> b) & 1 and (max_conflicts == 0 or int(
                            ((got >> np.uint64(b)) & np.uint64(1)).sum())
                            > max_conflicts):
                        continue
                    at = bi
                    break
                if at >= 0:
                    break
        if at >= 0:
            bundle_members[at].append(f)
            bundle_bins[at] += extra
        else:
            at = len(bundle_members)
            bundle_members.append([f])
            bundle_bins.append(1 + extra)
            if at % _WORD == 0:
                covered.append(np.zeros(ns, np.uint64))
        covered[at // _WORD][rows] |= np.uint64(1 << (at % _WORD))
    del covered

    if len(bundle_members) == num_f:
        return None

    feat_col = np.zeros(num_f, np.int32)
    src_idx = np.zeros((num_f, B), np.int32)
    valid = np.zeros((num_f, B), bool)
    inv_table = np.zeros((num_f, B), np.int32)
    b_idx = np.arange(B)
    for col, members in enumerate(bundle_members):
        if len(members) == 1:
            # singleton: identity layout, default bin stored physically but
            # still reconstructed from totals (same value, one code path)
            f = members[0]
            feat_col[f] = col
            nb = int(num_bins[f])
            valid[f] = (b_idx < nb) & (b_idx != default_bin[f])
            src_idx[f] = np.minimum(b_idx, B - 1)
            inv_table[f] = np.where(b_idx < nb, b_idx, default_bin[f])
            continue
        offset = 0
        for f in members:
            feat_col[f] = col
            nb = int(num_bins[f])
            d = int(default_bin[f])
            # order-preserving rank that skips the default bin
            rank = np.where(b_idx < d, b_idx + 1, b_idx)   # in [1, nb-1]
            stored = (b_idx < nb) & (b_idx != d)
            src_idx[f] = np.where(stored, offset + rank, 0)
            valid[f] = stored
            inv = np.full(B, d, np.int32)
            vbins = b_idx[stored]
            inv[src_idx[f][stored]] = vbins
            inv_table[f] = inv
            offset += nb - 1
    return BundlePlan(bundles=bundle_members, feat_col=feat_col,
                      src_idx=src_idx, valid=valid, default_bin=default_bin,
                      inv_table=inv_table, num_bundles=len(bundle_members))


class BundleRanges(NamedTuple):
    """A plan's layout as ranges (module docstring).  Per feature: its
    segment ``[lo, hi]`` and ``skip``, the default bin the segment leaves
    out (``NO_SKIP`` for a singleton, whose column is the feature's own
    bins, default included).  Per physical position: the member there
    (-1: value 0 of a shared column, or past the last member), its
    virtual bin, and the position's offsets from its segment's two ends."""
    lo: np.ndarray        # i32 [Fv]
    hi: np.ndarray        # i32 [Fv]
    skip: np.ndarray      # i32 [Fv]
    feat_of: np.ndarray   # i32 [Fb, B]
    vbin_of: np.ndarray   # i32 [Fb, B]
    off: np.ndarray       # i32 [Fb, B] — position - lo of its member
    roff: np.ndarray      # i32 [Fb, B] — hi of its member - position


NO_SKIP = 1 << 20   # above every bin: "default bin <= t" never holds


def bundle_ranges(plan: BundlePlan, num_bins: np.ndarray,
                  width: int = MAX_BUNDLE_BINS) -> BundleRanges:
    """``plan`` as ranges, ``width`` physical positions a column."""
    num_f = len(plan.feat_col)
    lo = np.zeros(num_f, np.int32)
    hi = np.full(num_f, width - 1, np.int32)
    skip = np.full(num_f, NO_SKIP, np.int32)
    feat_of = np.full((plan.num_bundles, width), -1, np.int32)
    vbin_of = np.zeros((plan.num_bundles, width), np.int32)
    off = np.zeros((plan.num_bundles, width), np.int32)
    roff = np.zeros((plan.num_bundles, width), np.int32)
    for col, members in enumerate(plan.bundles):
        if len(members) == 1:
            f, nb = members[0], int(num_bins[members[0]])
            feat_of[col, :nb] = f
            vbin_of[col, :nb] = np.arange(nb)
            off[col, :nb] = np.arange(nb)
            roff[col, :nb] = nb - 1 - np.arange(nb)
            continue
        for f in members:
            vb = np.flatnonzero(plan.valid[f])
            at = plan.src_idx[f][vb]
            assert len(at) and (np.diff(at) == 1).all(), \
                f"feature {f}: its stored bins are no contiguous segment"
            lo[f], hi[f], skip[f] = at[0], at[-1], plan.default_bin[f]
            feat_of[col, at] = f
            vbin_of[col, at] = vb
            off[col, at] = at - at[0]
            roff[col, at] = at[-1] - at
    return BundleRanges(lo, hi, skip, feat_of, vbin_of, off, roff)


def apply_bundles(bins: np.ndarray, plan: BundlePlan) -> np.ndarray:
    """Produce the bundled physical matrix uint8 [n, Fb]."""
    n = bins.shape[0]
    out = np.zeros((n, plan.num_bundles), np.uint8)
    for col, members in enumerate(plan.bundles):
        if len(members) == 1:
            out[:, col] = bins[:, members[0]]
            continue
        acc = np.zeros(n, np.int32)
        for f in members:
            v = bins[:, f].astype(np.int64)
            stored = plan.valid[f][v]          # non-default rows
            write = stored & (acc == 0)        # first member wins conflicts
            acc = np.where(write, plan.src_idx[f][v], acc)
        out[:, col] = acc.astype(np.uint8)
    return out
