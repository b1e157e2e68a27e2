"""Per-feature quantization (binning).

TPU-native re-design of the reference bin-mapping layer (reference:
include/LightGBM/bin.h:85 ``BinMapper``, src/io/bin.cpp:311 ``FindBin``).
Binning is one-time host preprocessing, so this is NumPy; the output feeds the
packed device bin tensor.  Semantics preserved from the reference:

  * equal-count greedy binning over sampled distinct values
    (``GreedyFindBin``): values with count >= mean bin size get their own bin,
    the rest are cut greedily at the running mean of the remaining budget;
  * zero always isolated in its own bin ([-1e-35, 1e-35], reference
    ``kZeroThreshold`` bin.cpp) with the negative/positive value ranges binned
    separately with proportional bin budgets (``FindBinWithZeroAsOneBin``);
  * missing handling (bin.h:27 ``MissingType``): None / Zero (zero bin doubles
    as the missing bin) / NaN (dedicated last bin);
  * categorical bins ordered by descending frequency (bin.cpp categorical
    branch), ``bin_2_categorical`` kept for model serialization.  The
    ``max_bin - 1`` most frequent levels get a bin each, bins
    ``0..k-1``.  A column whose levels do NOT all get a bin (more levels
    than that, or negative / NaN values among the sampled rows) keeps ONE
    more bin for every row that has none, the OTHER bin: the LAST one,
    ``num_bin - 1 = k`` (``BinMapper.other_bin``; upstream keeps bin 0 for
    them and counts the kept levels from 1).  It stands where a numeric
    column's NaN bin stands (``nan_bin``), no level of
    ``bin_2_categorical`` maps to it, and no categorical split's left set
    ever holds it (ops/split.py), so such a row goes RIGHT in the training
    partition, in valid scoring and in the stated model alike, whose sets
    are over raw codes (a code in no set goes right).  A column whose
    levels all fit has no such bin and keeps bins ``0..k-1`` alone; there
    a level first seen after binning still folds into bin 0, the most
    frequent level's.  That holds for a level of the TRAINING rows too
    that the binning sample (``bin_construct_sample_cnt`` rows) never
    met: its rows go with bin 0 in the training partition and right in
    the stated model, so the guarantee above is the column's with an
    other bin, and a fully sampled column's;
  * trivial features (num_bin <= 1) are flagged so the Dataset can drop them
    (reference ``feature_pre_filter``, dataset.cpp).

Unlike the reference's dense bins we do NOT elide the most-frequent bin from
storage — every bin is stored explicitly in the packed tensor, so the
``FixHistogram`` completion step (dataset.h:760) has no TPU counterpart.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..utils import log

K_ZERO_THRESHOLD = 1e-35  # reference bin.cpp kZeroThreshold

# MissingType (reference bin.h:27)
MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

BIN_NUMERICAL = 0
BIN_CATEGORICAL = 1


def _greedy_find_bin(distinct_values: np.ndarray, counts: np.ndarray,
                     max_bin: int, total_cnt: int, min_data_in_bin: int) -> List[float]:
    """Equal-count greedy binning over (distinct value, count) pairs.

    Returns the list of bin upper bounds (last = +inf).  Mirrors the behavior
    of reference ``BinMapper::GreedyFindBin`` (src/io/bin.cpp): small distinct
    sets get one bin per value (merged up to ``min_data_in_bin``), large sets
    are cut greedily so each bin holds ~mean count, with heavy hitters
    guaranteed their own bin.
    """
    num_distinct = len(distinct_values)
    if num_distinct == 0:
        return []
    bounds: List[float] = []
    if num_distinct <= max_bin:
        # one bin per distinct value, merging tiny bins forward
        if min_data_in_bin > 0 and total_cnt > 2 * min_data_in_bin:
            cur = 0
            i = 0
            while i < num_distinct:
                cur += int(counts[i])
                if cur >= min_data_in_bin:
                    if i + 1 < num_distinct:
                        bounds.append((float(distinct_values[i]) +
                                       float(distinct_values[i + 1])) / 2.0)
                    cur = 0
                i += 1
            bounds.append(np.inf)
        else:
            for i in range(num_distinct - 1):
                bounds.append((float(distinct_values[i]) +
                               float(distinct_values[i + 1])) / 2.0)
            bounds.append(np.inf)
        return bounds

    # large distinct set: greedy equal-count.  The reference walks the
    # distinct values one by one and cuts when the running bin is full,
    # the value is big, or the next is big; here the walk jumps from cut
    # to cut over the cumulative counts (at most ``max_bin`` steps for a
    # column of any number of distinct values), and cuts where the walk
    # would: a count reaches a float mean when it reaches its ceiling.
    max_bin = max(1, max_bin)
    mean_bin_size = total_cnt / max_bin
    # heavy values get dedicated bins
    is_big = counts >= mean_bin_size
    big_at = np.flatnonzero(is_big)
    rest_total = total_cnt - int(counts[is_big].sum())
    rest_bins = max_bin - len(big_at)
    if rest_bins > 0:
        mean_bin_size = rest_total / rest_bins
    cum = np.zeros(num_distinct + 1, dtype=np.int64)
    np.cumsum(counts, out=cum[1:])
    cum_rest = cum
    if len(big_at):
        cum_rest = np.zeros(num_distinct + 1, dtype=np.int64)
        np.cumsum(np.where(is_big, 0, counts), out=cum_rest[1:])
    upper_bounds: List[float] = []
    lower_bounds: List[float] = []
    bin_cnt = 0
    start = 0
    while start < num_distinct:
        base = int(cum[start])
        full = base + math.ceil(mean_bin_size)
        i = max(int(cum.searchsorted(full, side="left")), start + 1) - 1
        nxt = int(big_at.searchsorted(start, side="left"))
        if nxt < len(big_at):
            big = int(big_at[nxt])
            half = math.ceil(max(1.0, mean_bin_size * 0.5))
            if big > start and int(cum[big]) - base >= half:
                big -= 1
            i = min(i, big)
        if i >= num_distinct:
            break
        upper_bounds.append(float(distinct_values[i]))
        lower_bounds.append(float(distinct_values[start]))
        bin_cnt += 1
        start = i + 1
        if not is_big[i] and rest_bins > bin_cnt:
            mean_bin_size = (rest_total - int(cum_rest[start])) \
                / (rest_bins - bin_cnt)
        if bin_cnt >= max_bin - 1:
            break
    # boundaries are midpoints between a bin's max and the next bin's min
    for i in range(len(upper_bounds) - 1):
        bounds.append((upper_bounds[i] + lower_bounds[i + 1]) / 2.0)
    # everything after the last cut falls into the final bin
    bounds.append(np.inf)
    return bounds


def columns_first(a: np.ndarray, into: Optional[np.ndarray] = None,
                  tile: int = 1024) -> np.ndarray:
    """``a.T`` as a C-contiguous array (or written to ``into``), copied a
    tile of the long axis at a time so that both sides of the transposition
    stay in cache: NumPy's own strided copy of a ``[400000, 64]`` block
    takes ten times as long."""
    long_axis = 0 if into is None else 1
    if into is None:
        into = np.empty(a.shape[::-1], dtype=a.dtype)
    for r0 in range(0, a.shape[long_axis], tile):
        if long_axis == 0:
            into[:, r0:r0 + tile] = a[r0:r0 + tile].T
        else:
            into[r0:r0 + tile] = a[:, r0:r0 + tile].T
    return into


class BinMapper:
    """Maps raw feature values to integer bins (reference bin.h:85)."""

    def __init__(self) -> None:
        self.num_bin: int = 1
        self.bin_type: int = BIN_NUMERICAL
        self.missing_type: int = MISSING_NONE
        self.bin_upper_bound: np.ndarray = np.array([np.inf])
        self.bin_2_categorical: List[int] = []
        self._cat_2_bin: Optional[dict] = None
        self.default_bin: int = 0        # bin of value 0.0 (reference GetDefaultBin)
        self.min_val: float = 0.0
        self.max_val: float = 0.0

    # ------------------------------------------------------------------ find
    @classmethod
    def find_bin(cls, values: np.ndarray, total_sample_cnt: int, max_bin: int,
                 min_data_in_bin: int, use_missing: bool, zero_as_missing: bool,
                 is_categorical: bool = False,
                 forced_bounds: Optional[Sequence[float]] = None) -> "BinMapper":
        """Construct a mapper from sampled values (reference bin.cpp:311).

        ``values``: sampled raw values for this feature, possibly containing
        NaN.  ``total_sample_cnt`` may exceed ``len(values)`` when zeros were
        elided by a sparse sampler; the difference is counted as zeros.
        """
        values = np.asarray(values, dtype=np.float64)
        na_cnt = int(np.isnan(values).sum())
        # np.unique(values without NaN, return_counts=True) in its own
        # steps (NaN sorts last), without its copies
        values = np.sort(values)[:len(values) - na_cnt]
        first = np.ones(len(values), dtype=bool)
        np.not_equal(values[1:], values[:-1], out=first[1:])
        at = np.flatnonzero(first)
        dv, cnts = values[at], np.diff(at, append=len(values))
        return cls.find_bin_from_dist(
            dv, cnts, na_cnt=na_cnt, total_sample_cnt=total_sample_cnt,
            max_bin=max_bin, min_data_in_bin=min_data_in_bin,
            use_missing=use_missing, zero_as_missing=zero_as_missing,
            is_categorical=is_categorical, forced_bounds=forced_bounds)

    @classmethod
    def find_bin_from_dist(cls, distinct_values: np.ndarray,
                           counts: np.ndarray, *, na_cnt: int,
                           total_sample_cnt: int, max_bin: int,
                           min_data_in_bin: int, use_missing: bool,
                           zero_as_missing: bool, is_categorical: bool = False,
                           forced_bounds: Optional[Sequence[float]] = None
                           ) -> "BinMapper":
        """``find_bin`` on a (distinct value, count) summary instead of raw
        values — THE shared construction path.  ``find_bin`` reduces its
        sample through ``np.unique`` and delegates here, so a streamed
        exact tally (io/streaming.py pass 1) that reproduces the same
        distinct/count multiset produces a bit-identical mapper.  NaN must
        already be stripped from ``distinct_values`` and tallied in
        ``na_cnt``; zeros elided upstream (sparse/streamed sources) are
        recovered from ``total_sample_cnt`` exactly like ``find_bin``.
        """
        m = cls()
        dv = np.asarray(distinct_values, dtype=np.float64)
        cnts = np.asarray(counts, dtype=np.int64)

        if not use_missing:
            m.missing_type = MISSING_NONE
        elif zero_as_missing:
            m.missing_type = MISSING_ZERO
        elif na_cnt > 0:
            m.missing_type = MISSING_NAN
        else:
            m.missing_type = MISSING_NONE

        if is_categorical:
            m._find_bin_categorical(dv, cnts, total_sample_cnt, max_bin,
                                    na_cnt)
            return m

        m._find_bin_numerical(dv, cnts, total_sample_cnt, max_bin,
                              min_data_in_bin, na_cnt, forced_bounds)
        return m

    def _find_bin_numerical(self, dv: np.ndarray, cnts: np.ndarray,
                            total_sample_cnt: int,
                            max_bin: int, min_data_in_bin: int, na_cnt: int,
                            forced_bounds: Optional[Sequence[float]]) -> None:
        self.bin_type = BIN_NUMERICAL
        n_values = int(cnts.sum())
        zero_cnt = max(0, total_sample_cnt - n_values - na_cnt)
        # zeros elided by sparse sampling come back as explicit zeros here
        # (the distinct values come sorted: negatives, zeros, positives)
        neg_end = int(dv.searchsorted(-K_ZERO_THRESHOLD, side="left"))
        pos_at = int(dv.searchsorted(K_ZERO_THRESHOLD, side="right"))
        zero_cnt += int(cnts[neg_end:pos_at].sum())
        dv_nz, c_nz = dv, cnts
        if pos_at > neg_end:
            dv_nz = np.concatenate((dv[:neg_end], dv[pos_at:]))
            c_nz = np.concatenate((cnts[:neg_end], cnts[pos_at:]))
        if len(dv_nz):
            self.min_val = float(dv_nz[0])
            self.max_val = float(dv_nz[-1])

        budget = max_bin - (1 if self.missing_type == MISSING_NAN else 0)
        budget = max(budget, 2)

        # forced bounds are GUARANTEED boundaries; the remaining budget is
        # still filled with data-driven bins (reference forced-bins
        # semantics, dataset_loader.cpp forced_upper_bounds: forcing a few
        # boundaries must not collapse the feature's split resolution)
        fb = sorted(float(b) for b in forced_bounds) if forced_bounds else []
        if fb:
            budget = max(budget - len(fb), 2)
        neg_mask = slice(0, neg_end)
        pos_mask = slice(neg_end, None)
        n_neg = int(c_nz[neg_mask].sum())
        n_pos = int(c_nz[pos_mask].sum())
        n_nonzero = n_neg + n_pos
        bounds = []
        if n_nonzero == 0:
            bounds = [np.inf]
        elif zero_cnt == 0:
            # no zeros sampled (dense feature): bin the raw value range
            # directly, no dedicated zero bin
            bounds = _greedy_find_bin(dv_nz, c_nz, budget, n_nonzero,
                                      min_data_in_bin)
        else:
            # proportional budget split around the dedicated zero bin
            # (reference FindBinWithZeroAsOneBin)
            left_budget = int(round(n_neg / n_nonzero * (budget - 1)))
            if n_neg > 0:
                left_budget = max(left_budget, 1)
            right_budget = budget - 1 - left_budget
            if n_pos > 0:
                right_budget = max(right_budget, 1)
            if n_neg > 0:
                nb = _greedy_find_bin(dv_nz[neg_mask], c_nz[neg_mask],
                                      left_budget,
                                      n_neg + zero_cnt // 2, min_data_in_bin)
                if nb:
                    nb[-1] = -K_ZERO_THRESHOLD  # close negatives below zero bin
                bounds.extend(nb)
            bounds.append(K_ZERO_THRESHOLD)  # zero bin upper bound
            if n_pos > 0:
                pb = _greedy_find_bin(dv_nz[pos_mask], c_nz[pos_mask],
                                      right_budget,
                                      n_pos + zero_cnt - zero_cnt // 2,
                                      min_data_in_bin)
                bounds.extend(pb)
            else:
                bounds[-1] = np.inf
            if bounds[-1] != np.inf:
                bounds.append(np.inf)
        bounds = list(bounds) + fb
        # dedupe while preserving order
        ub = np.array(sorted(set(bounds)), dtype=np.float64)
        self.bin_upper_bound = ub
        self.num_bin = len(ub)
        if self.missing_type == MISSING_NAN:
            self.num_bin += 1  # dedicated NaN bin appended last
        self.default_bin = int(np.searchsorted(ub, 0.0, side="left"))

    def _find_bin_categorical(self, dv: np.ndarray, dcnts: np.ndarray,
                              total_sample_cnt: int,
                              max_bin: int, na_cnt: int) -> None:
        self.bin_type = BIN_CATEGORICAL
        ivals = dv.astype(np.int64)
        dropped = False
        if (ivals[dcnts > 0] < 0).any():
            log.warning("Met negative value in categorical features, will convert "
                        "it to NaN")
            keep = ivals >= 0
            ivals, dcnts = ivals[keep], dcnts[keep]
            dropped = True
        # distinct floats can collapse onto one int code: re-aggregate
        cats, inv = np.unique(ivals, return_inverse=True)
        counts = np.bincount(inv, weights=dcnts.astype(np.float64),
                             minlength=len(cats)).astype(np.int64)
        order = np.argsort(-counts, kind="stable")
        cats, counts = cats[order], counts[order]
        # cap at max_bin - 1 levels; where a row can be without a level's
        # bin (a rarer level, a negative code, NaN) the column keeps the
        # OTHER bin for it, the last one (module docstring)
        keep = min(len(cats), max_bin - 1)
        unbinned = keep < len(cats) or dropped or na_cnt > 0
        cats = cats[:keep]
        self.bin_2_categorical = [int(c) for c in cats]
        self._cat_2_bin = {int(c): i for i, c in enumerate(cats)}
        self.num_bin = max(1, len(cats)) + (1 if unbinned and keep else 0)
        # no missing-bin default routing: a row in the other bin goes
        # right because no left set holds that bin
        self.missing_type = MISSING_NONE
        self.default_bin = 0

    # --------------------------------------------------------------- mapping
    def is_trivial(self) -> bool:
        """True when the whole feature lands in one bin (reference dataset.cpp
        feature_pre_filter drops these)."""
        return self.num_bin <= 1

    @property
    def other_bin(self) -> int:
        """A categorical column's bin for rows whose value has no bin of
        its own (a level beyond the ``max_bin - 1`` kept, a negative
        code, NaN): the last bin, or -1 where every level got a bin."""
        if self.bin_type == BIN_CATEGORICAL \
                and 0 < len(self.bin_2_categorical) < self.num_bin:
            return self.num_bin - 1
        return -1

    @property
    def nan_bin(self) -> int:
        """Bin index holding missing values, or -1 when missing maps nowhere.
        A categorical column's is its OTHER bin (``other_bin``), which no
        left set holds; without one NaN folds into bin 0."""
        if self.bin_type == BIN_CATEGORICAL:
            return self.other_bin
        if self.missing_type == MISSING_NAN:
            return self.num_bin - 1
        if self.missing_type == MISSING_ZERO:
            return self.default_bin
        return -1

    def values_to_bins(self, values: np.ndarray) -> np.ndarray:
        """Vectorized ValueToBin (reference bin.h / bin.cpp); numerical
        columns route through the native C++ kernel when built."""
        values = np.asarray(values, dtype=np.float64)
        if self.bin_type != BIN_CATEGORICAL and len(values) >= 65536:
            try:
                from ..native import apply_bins_numerical
                nb = self.num_bin - 1 if self.missing_type == MISSING_NAN \
                    else -1
                return apply_bins_numerical(
                    values, np.asarray(self.bin_upper_bound),
                    self.missing_type, nb,
                    self.default_bin).astype(np.int32)
            except ImportError:
                pass
        if self.bin_type == BIN_CATEGORICAL:
            fill = max(self.other_bin, 0)
            return self._cat_values_to_bins(values, fill, fill)
        isnan = np.isnan(values)
        if self.missing_type == MISSING_ZERO:
            values = np.where(isnan, 0.0, values)
            isnan = np.zeros_like(isnan)
        out = np.searchsorted(self.bin_upper_bound, values, side="left")
        out = np.clip(out, 0, len(self.bin_upper_bound) - 1).astype(np.int32)
        if self.missing_type == MISSING_NAN:
            out = np.where(isnan, self.num_bin - 1, out).astype(np.int32)
        else:
            out[isnan] = self.default_bin
        return out

    def _cat_values_to_bins(self, values: np.ndarray, unseen_bin: int,
                            nan_bin_out: int) -> np.ndarray:
        """THE categorical raw->bin lookup, shared by training binning
        (``values_to_bins``: unseen/NaN take the other bin, bin 0 where
        the column has none) and the bitset
        predictor (``values_to_bins_pred``: dedicated sentinel bins).
        int64 truncation matches the host walk's ``int(v)`` coercion;
        negative codes never match a category and take the unseen fill."""
        values = np.asarray(values, dtype=np.float64)
        isnan = np.isnan(values)
        ivals = np.where(isnan, -1, values).astype(np.int64)
        table = self._cat_2_bin or {}
        # vectorized dict lookup via searchsorted over sorted cats
        cats = np.array(sorted(table), dtype=np.int64)
        out = np.full(len(values), unseen_bin, dtype=np.int32)
        if len(cats):
            bins_for = np.array([table[c] for c in cats], dtype=np.int32)
            pos = np.clip(np.searchsorted(cats, ivals), 0, len(cats) - 1)
            hit = cats[pos] == ivals
            out = np.where(hit, bins_for[pos], unseen_bin).astype(np.int32)
        out[isnan] = nan_bin_out
        return out

    def values_to_bins_pred(self, values: np.ndarray, unseen_bin: int,
                            nan_bin_out: int) -> np.ndarray:
        """``values_to_bins`` variant for the device BITSET predictor
        (models/predict.py predict_bitset_forest): categorical columns
        map categories unseen at training time to ``unseen_bin`` and NaN
        to ``nan_bin_out`` instead of folding both into bin 0 — the
        sentinels let bin-space traversal reproduce the raw-space walk's
        'not in set -> right' / cat_nan_left branches exactly
        (reference tree.cpp CategoricalDecision).  Numerical columns are
        unchanged (their bin space is decision-exact already)."""
        if self.bin_type != BIN_CATEGORICAL:
            return self.values_to_bins(values)
        return self._cat_values_to_bins(values, unseen_bin, nan_bin_out)

    def bin_to_value(self, bin_idx: int) -> float:
        """Representative split threshold for a bin boundary: the upper bound
        of ``bin_idx`` (used when converting bin thresholds to real-valued
        model thresholds, reference tree.cpp threshold_ semantics)."""
        if self.bin_type == BIN_CATEGORICAL:
            if 0 <= bin_idx < len(self.bin_2_categorical):
                return float(self.bin_2_categorical[bin_idx])
            return 0.0
        ub = self.bin_upper_bound
        idx = min(int(bin_idx), len(ub) - 1)
        # the last bin's bound stays +inf: a split there only sends missing
        # values right, every real value goes left
        return float(ub[idx])

    # --------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return {
            "num_bin": self.num_bin,
            "bin_type": self.bin_type,
            "missing_type": self.missing_type,
            "bin_upper_bound": [float(x) for x in self.bin_upper_bound],
            "bin_2_categorical": list(self.bin_2_categorical),
            "default_bin": self.default_bin,
            "min_val": self.min_val,
            "max_val": self.max_val,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "BinMapper":
        m = cls()
        m.num_bin = int(d["num_bin"])
        m.bin_type = int(d["bin_type"])
        m.missing_type = int(d["missing_type"])
        m.bin_upper_bound = np.array(d["bin_upper_bound"], dtype=np.float64)
        m.bin_2_categorical = [int(c) for c in d.get("bin_2_categorical", [])]
        m._cat_2_bin = {c: i for i, c in enumerate(m.bin_2_categorical)}
        m.default_bin = int(d.get("default_bin", 0))
        m.min_val = float(d.get("min_val", 0.0))
        m.max_val = float(d.get("max_val", 0.0))
        return m
