"""Binned Dataset + Metadata.

TPU-native re-design of the reference data layer (reference:
include/LightGBM/dataset.h:487 ``Dataset``, dataset.h:48 ``Metadata``,
src/io/dataset_loader.cpp ``DatasetLoader``).  The reference's column/row-wise
bin storages (dense_bin.hpp / sparse_bin.hpp / multi_val_dense_bin.hpp)
collapse into ONE packed device layout: a row-major ``uint8`` matrix
``[n_rows, n_used_features]`` — the natural operand for a TPU histogram
kernel (rows stream through VMEM tiles, features sit on the lane dimension).
The col-wise/row-wise auto-choice (dataset.cpp:615) is therefore moot.

Trivial features (single bin) are dropped from the packed matrix but kept in
the mapper list so model I/O refers to original feature indices (reference
``feature_pre_filter``, used_feature_map_).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..config import Config, as_config
from ..obs.metrics import count_event
from ..utils import log
from ..utils.timer import global_timer, phase
from .binning import BIN_CATEGORICAL, BinMapper, columns_first
from .bundling import (BundlePlan, apply_bundles, bundle_ranges, plan_bundles,
                       plan_bundles_sparse)

MAX_UINT8_BINS = 256

#: On-disk binary dataset format version (save_binary/load_binary).
#: v1 = unversioned seed format (marker only); v2 adds the version field
#: and streaming-ingest provenance.  Readers accept <= their own version.
BINARY_FORMAT_VERSION = 2


#: columns the dense construct handles at a time (``_construct_mappers``,
#: ``_bin_matrix``)
_COL_BLOCK = 64


def _column_block(arr: np.ndarray, cols, rows=slice(None)) -> np.ndarray:
    """Columns ``cols`` of ``arr`` at ``rows`` (each a slice or sorted
    indices, not indices both), each column contiguous: C-contiguous
    ``[columns, rows]``, whichever way ``arr`` lies in memory."""
    if arr.T.flags.c_contiguous:        # the columns lie contiguous
        block = arr.T[cols]
        return block if isinstance(rows, slice) \
            else np.take(block, rows, axis=1)
    return columns_first(arr[rows, cols])


def device_bins_pow2(widest: int) -> int:
    """Device histogram bin-axis width for a widest-column bin count:
    rounded up to a power of two (lane-friendly), floor 4.  THE rounding
    rule: ``Dataset.device_n_bins`` and the streamed ingest
    (io/streaming.py) both take it from here."""
    return max(1 << max(1, (int(widest) - 1).bit_length()), 4)


def _as_2d_float(data: Any) -> np.ndarray:
    """Accept numpy / pandas / list-of-rows; return float64 [n, F] with NaN
    for missing (the reference accepts mat/CSR/CSC/pandas via c_api)."""
    if hasattr(data, "values") and hasattr(data, "columns"):  # pandas DataFrame
        arr = data.to_numpy(dtype=np.float64, na_value=np.nan)
    elif hasattr(data, "toarray"):  # scipy sparse
        arr = np.asarray(data.toarray(), dtype=np.float64)
    else:
        arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        log.fatal(f"data must be 2-dimensional, got shape {arr.shape}")
    return arr


class Metadata:
    """Labels / weights / query boundaries / init scores / positions
    (reference dataset.h:48-360)."""

    def __init__(self, num_data: int):
        self.num_data = int(num_data)
        self.label: np.ndarray = np.zeros(num_data, dtype=np.float32)
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # int32 [nq+1]
        self.init_score: Optional[np.ndarray] = None
        self.position: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            log.fatal(f"Length of label ({len(label)}) != num_data ({self.num_data})")
        self.label = label

    def set_weight(self, weight: Optional[Sequence[float]]) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(weight) != self.num_data:
            log.fatal(f"Length of weight ({len(weight)}) != num_data ({self.num_data})")
        if (weight < 0).any():
            log.fatal("Weights should be non-negative")
        self.weight = weight

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        """``group`` is per-query SIZES (python-package convention;
        reference Metadata::SetQuery, dataset.h).  Loaders that read per-row
        query-id columns convert to sizes first (io/parser.py)."""
        if group is None:
            self.query_boundaries = None
            return
        sizes = np.asarray(group).astype(np.int64)
        bounds = np.zeros(len(sizes) + 1, dtype=np.int32)
        np.cumsum(sizes, out=bounds[1:])
        if bounds[-1] != self.num_data:
            log.fatal(f"Sum of query counts ({bounds[-1]}) != num_data "
                      f"({self.num_data})")
        self.query_boundaries = bounds

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)

    def set_position(self, position: Optional[Sequence[int]]) -> None:
        self.position = None if position is None else \
            np.asarray(position, dtype=np.int32).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class Dataset:
    """Binned training data (reference dataset.h:487).

    ``bins``  uint8 [n_rows, n_used]   packed bin matrix (device operand)
    ``mappers``  one BinMapper per ORIGINAL feature
    ``used_feature_idx``  original index of each packed column
    """

    def __init__(self) -> None:
        self.bins: np.ndarray = np.zeros((0, 0), dtype=np.uint8)
        self.mappers: List[BinMapper] = []
        self.used_feature_idx: List[int] = []
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata: Metadata = Metadata(0)
        self.config: Config = Config()
        self._reference: Optional["Dataset"] = None
        # raw values of the packed (used) features, kept only when
        # linear_tree is on (reference Dataset raw_data_ for linear leaves)
        self.raw: Optional[np.ndarray] = None
        # EFB (reference FastFeatureBundling dataset.cpp:246): when set,
        # ``bins`` holds bundled physical columns [n, Fb]
        self.bundle_plan: Optional[BundlePlan] = None
        # set by io/streaming.py: how this dataset was constructed
        # (chunk size, sketch accuracy, which features were sketched) —
        # persisted through save_binary so audits can tell a streamed
        # build from an in-memory one
        self.ingest_provenance: Optional[Dict[str, Any]] = None
        # what the categorical columns hold (``categorical_counts``)
        self.cat_counts: Optional[Dict[str, int]] = None

    # ------------------------------------------------------------ properties
    @property
    def num_data(self) -> int:
        return self.bins.shape[0]

    @property
    def num_features(self) -> int:
        """Packed (used, virtual) feature count."""
        return len(self.used_feature_idx)

    @property
    def label(self) -> np.ndarray:
        return self.metadata.label

    def num_bins_array(self) -> np.ndarray:
        return np.array([self.mappers[i].num_bin for i in self.used_feature_idx],
                        dtype=np.int32)

    def nan_bin_array(self) -> np.ndarray:
        return np.array([self.mappers[i].nan_bin for i in self.used_feature_idx],
                        dtype=np.int32)

    def categorical_array(self) -> np.ndarray:
        return np.array([self.mappers[i].bin_type == BIN_CATEGORICAL
                         for i in self.used_feature_idx], dtype=bool)

    def max_num_bin(self) -> int:
        return int(max((self.mappers[i].num_bin for i in self.used_feature_idx),
                       default=1))

    def device_n_bins(self) -> int:
        """Bin-axis width of device histograms / cat bitsets: max_num_bin
        (or the widest EFB bundle column) rounded up to a power of two
        (lane-friendly), floor 4.  Single source of truth — trees and their
        cat_bitset widths must agree with it."""
        widest = self.max_num_bin()
        if self.bundle_plan is not None:
            for members in self.bundle_plan.bundles:
                total = 1 + sum(self.mappers[self.used_feature_idx[f]].num_bin
                                - 1 for f in members)
                widest = max(widest, total)
        return device_bins_pow2(widest)

    def packed_mirror(self) -> np.ndarray:
        """Packed-word mirror of the bin matrix: i32 [n, ceil(F/4)], 4
        uint8 bins per word (little-endian bitcast of the row-major
        matrix — the layout ``ops/histogram.bins_to_words`` produces on
        device).

        Round-6 packed-bin histogram mode: the kernel's one-hot build
        compares 4 features per 32-bit lane (ops/hist_pallas.py
        ``histogram_leaves_packed_pallas``), so the dataset keeps this
        mirror alongside ``bins`` and the booster ships it ONCE instead
        of re-deriving the word view inside every traced tree.  Built
        lazily and cached; invalidated implicitly by never mutating
        ``bins`` after construction (the Dataset contract)."""
        cached = getattr(self, "_packed_mirror", None)
        if cached is not None and cached.shape[0] == self.bins.shape[0]:
            return cached
        n, num_f = self.bins.shape
        pad = (-num_f) % 4
        b = self.bins if not pad else \
            np.concatenate([self.bins,
                            np.zeros((n, pad), np.uint8)], axis=1)
        self._packed_mirror = np.ascontiguousarray(b).view(np.int32) \
            .reshape(n, (num_f + pad) // 4)
        return self._packed_mirror

    def device_bundle_arrays(self):
        """EFB tables trimmed to ``device_n_bins`` width, or None
        (learner/grower.py DeviceBundle operands)."""
        p = self.bundle_plan
        if p is None:
            return None
        B = self.device_n_bins()
        return (p.feat_col, p.src_idx[:, :B], p.valid[:, :B],
                p.default_bin, p.inv_table[:, :B])

    def device_bundle_ranges(self):
        """The bundle plan as ranges at ``device_n_bins`` width
        (io/bundling.py ``bundle_ranges``), or None: without a plan, and
        where a member is categorical or has a missing bin, which no
        range of its column states (learner/grower.py DeviceBundle)."""
        if self.bundle_plan is None or self.categorical_array().any() \
                or (self.nan_bin_array() >= 0).any():
            return None
        return bundle_ranges(self.bundle_plan, self.num_bins_array(),
                             self.device_n_bins())

    # ---------------------------------------------------------- construction
    @classmethod
    def from_data(cls, data: Any, label: Optional[Sequence[float]] = None,
                  config: Union[Config, Dict[str, Any], None] = None,
                  weight: Optional[Sequence[float]] = None,
                  group: Optional[Sequence[int]] = None,
                  init_score: Optional[Sequence[float]] = None,
                  feature_names: Optional[List[str]] = None,
                  categorical_feature: Optional[Sequence[Union[int, str]]] = None,
                  reference: Optional["Dataset"] = None) -> "Dataset":
        """Build a binned dataset (reference DatasetLoader::ConstructFromSampleData
        path through c_api LGBM_DatasetCreateFromMat, c_api.h:409)."""
        cfg = as_config(config)
        if hasattr(data, "tocsc") and hasattr(data, "nnz"):  # scipy sparse
            with phase("construct", global_timer):
                return cls._from_sparse(data, label, cfg, weight, group,
                                        init_score, feature_names,
                                        categorical_feature, reference)
        with phase("construct", global_timer):
            return cls._from_dense(data, label, cfg, weight, group,
                                   init_score, feature_names,
                                   categorical_feature, reference)

    @classmethod
    def _from_dense(cls, data, label, cfg, weight, group, init_score,
                    feature_names, categorical_feature, reference
                    ) -> "Dataset":
        arr = _as_2d_float(data)
        n, f = arr.shape
        ds = cls()
        ds.config = cfg
        ds.num_total_features = f
        if feature_names is None and hasattr(data, "columns"):
            feature_names = [str(c) for c in data.columns]
        ds.feature_names = feature_names or [f"Column_{i}" for i in range(f)]

        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_group(group)
        ds.metadata.set_init_score(init_score)

        if reference is not None:
            # valid set: reuse the training mappers (reference CreateValid,
            # dataset.h:703 — bin boundaries must align with train) and the
            # training EFB plan (bundle layouts must match)
            ds.mappers = reference.mappers
            ds.used_feature_idx = list(reference.used_feature_idx)
            ds.num_total_features = reference.num_total_features
            ds.feature_names = reference.feature_names
            ds._reference = reference
            with phase("dense_bin_matrix", global_timer,
                       seconds="construct_bin_matrix_s"):
                ds._bin_all(arr)
            if reference.bundle_plan is not None:
                ds.bundle_plan = reference.bundle_plan
                ds.bins = apply_bundles(ds.bins, ds.bundle_plan)
            if bool(cfg.linear_tree):
                ds.raw = arr[:, ds.used_feature_idx].astype(np.float32)
            return ds

        cat_idx = _resolve_categorical(categorical_feature, ds.feature_names)
        with phase("dense_bin_mappers", global_timer,
                   seconds="construct_bin_mappers_s"):
            ds._construct_mappers(arr, cfg, cat_idx)
        with phase("dense_bin_matrix", global_timer,
                   seconds="construct_bin_matrix_s"):
            ds._bin_all(arr)
        ds._count_categorical()
        if bool(cfg.enable_bundle) and cfg.tree_learner not in (
                "feature", "feature_parallel"):
            # cap bundle width at the pre-EFB histogram width so EFB can
            # only shrink the histogram tensor, never widen its bin axis
            plan = plan_bundles(ds.bins, ds.num_bins_array(),
                                max_total_bins=ds.device_n_bins())
            if plan is not None:
                saved = ds.bins.shape[1] - plan.num_bundles
                log.info(f"EFB bundled {ds.bins.shape[1]} features into "
                         f"{plan.num_bundles} columns (saved {saved})")
                ds.bundle_plan = plan
                ds.bins = apply_bundles(ds.bins, plan)
        if bool(cfg.linear_tree):
            ds.raw = arr[:, ds.used_feature_idx].astype(np.float32)
        return ds

    def create_valid(self, data: Any, label: Optional[Sequence[float]] = None,
                     **kwargs: Any) -> "Dataset":
        return Dataset.from_data(data, label=label, config=self.config,
                                 reference=self, **kwargs)

    # ------------------------------------------------------------- sparse
    @classmethod
    def _from_sparse(cls, data, label, cfg, weight, group, init_score,
                     feature_names, categorical_feature, reference
                     ) -> "Dataset":
        """Sparse (scipy CSR/CSC) ingestion WITHOUT densification.

        The TPU memory story for Allstate-class wide sparse data (reference
        sparse_bin.hpp delta-encoded columns + EFB): per-feature bin mappers
        come from the CSC columns' nonzero values (implicit rows counted as
        zeros via ``total_sample_cnt``), EFB bundles mutually-exclusive
        columns, and the ONLY row-major materialization is the final
        bundled uint8 [n, n_bundles] matrix — never a dense [n, F] float64.
        """
        csc = data.tocsc(copy=True)  # copy: sum_duplicates mutates in place
        csc.sum_duplicates()
        n, f = csc.shape
        if bool(cfg.linear_tree):
            log.fatal("linear_tree=true requires dense input "
                      "(sparse ingestion keeps no raw matrix)")
        if categorical_feature not in (None, "auto") and \
                len(list(categorical_feature)):
            log.fatal("categorical_feature with sparse input is not "
                      "supported; pass a dense matrix or a DataFrame")
        ds = cls()
        ds.config = cfg
        ds.num_total_features = f
        ds.feature_names = feature_names or [f"Column_{i}" for i in range(f)]
        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_group(group)
        ds.metadata.set_init_score(init_score)

        if reference is not None:
            # the builder decodes implicit entries through each mapper's
            # bin-of-0.0 (values_to_bins, categorical included) and
            # replicates apply_bundles' first-writer order, so a
            # dense-trained reference — categorical mappers, nonzero
            # default bins, dense-built bundle plans — binds without
            # densification (the r3 fallback here is gone)
            plan = reference.bundle_plan
            ds.mappers = reference.mappers
            ds.used_feature_idx = list(reference.used_feature_idx)
            ds.num_total_features = reference.num_total_features
            ds.feature_names = reference.feature_names
            ds._reference = reference
            ds.bundle_plan = plan
            with phase("bundle_matrix", global_timer):
                ds.bins = _sparse_bundled_matrix(
                    csc, ds.mappers, ds.used_feature_idx, ds.bundle_plan, n)
            return ds

        # --- bin mappers from column nonzeros (bin.cpp:311 FindBin with
        # zero elision: total_sample_cnt - len(values) counts as zeros)
        max_bin = min(int(cfg.max_bin), MAX_UINT8_BINS)
        cap = int(cfg.bin_construct_sample_cnt)
        rng = np.random.default_rng(cfg.data_random_seed)
        mbf = list(cfg.max_bin_by_feature or [])
        forced = _load_forced_bins(cfg, f)
        mappers = []
        with phase("sparse_bin_mappers", global_timer):
            for j in range(f):
                vals = csc.data[csc.indptr[j]:csc.indptr[j + 1]]
                if len(vals) > cap:
                    vals = vals[rng.choice(len(vals), cap, replace=False)]
                    total = int(round(n * cap / (csc.indptr[j + 1]
                                                 - csc.indptr[j])))
                else:
                    total = n
                fmax = mbf[j] if j < len(mbf) and mbf[j] > 1 else max_bin
                mappers.append(BinMapper.find_bin(
                    vals, total_sample_cnt=max(total, len(vals)),
                    max_bin=int(fmax),
                    min_data_in_bin=int(cfg.min_data_in_bin),
                    use_missing=bool(cfg.use_missing),
                    zero_as_missing=bool(cfg.zero_as_missing),
                    forced_bounds=forced.get(j)))
        ds.mappers = mappers
        ds.used_feature_idx = [j for j in range(f)
                               if not mappers[j].is_trivial()]
        dropped = f - len(ds.used_feature_idx)
        if dropped:
            log.info(f"Dropped {dropped} trivial (single-bin) feature(s)")
        if not ds.used_feature_idx:
            log.fatal("Cannot construct Dataset: all features are trivial")

        # --- EFB plan from the columns' own row lists (no dense matrix, no
        # sample: conflicts are counted over every row, so no row of the
        # training data holds two members of a column)
        plan = None
        if bool(cfg.enable_bundle) and cfg.tree_learner not in (
                "feature", "feature_parallel"):
            used = np.asarray(ds.used_feature_idx)
            starts, ends = csc.indptr[used], csc.indptr[used + 1]
            zero_bins = np.array([mappers[j].default_bin
                                  for j in ds.used_feature_idx], np.int32)
            # unlike the dense path (which never widens the bin axis), wide
            # sparse data WANTS full-width bundles: merging 30 nine-bin
            # one-hot-ish columns into one 256-bin column shrinks the
            # histogram tensor AND the kernel's column count; keep the plan
            # only when the total histogram cell count actually shrinks
            n_bins_pre = ds.device_n_bins()
            with phase("bundle_plan", global_timer):
                plan = plan_bundles_sparse(
                    lambda f: csc.indices[starts[f]:ends[f]], ends - starts,
                    ds.num_bins_array(), zero_bins, n)
            if plan is not None:
                ds.bundle_plan = plan
                cells_with = plan.num_bundles * ds.device_n_bins()
                cells_without = len(ds.used_feature_idx) * n_bins_pre
                ds.bundle_plan = None
                # column count drives the kernel/partition/memory costs, so
                # a big column reduction is worth a same-or-moderately-wider
                # histogram tensor (the bin axis is lane-padded anyway)
                shrinks_cols = plan.num_bundles <= \
                    0.75 * len(ds.used_feature_idx)
                if not (cells_with < cells_without
                        or (shrinks_cols and cells_with
                            <= 2 * cells_without)):
                    plan = None
            if plan is not None:
                saved = len(ds.used_feature_idx) - plan.num_bundles
                log.info(f"EFB bundled {len(ds.used_feature_idx)} sparse "
                         f"features into {plan.num_bundles} columns "
                         f"(saved {saved})")
        ds.bundle_plan = plan
        if plan is not None:
            count_event("efb_bundles", plan.num_bundles)
            count_event("efb_features", len(ds.used_feature_idx))
        with phase("bundle_matrix", global_timer):
            ds.bins = _sparse_bundled_matrix(csc, mappers,
                                             ds.used_feature_idx, plan, n)
        return ds

    def _construct_mappers(self, arr: np.ndarray, cfg: Config,
                           cat_idx: Sequence[int]) -> None:
        n, f = arr.shape
        max_bin = int(cfg.max_bin)
        if max_bin > MAX_UINT8_BINS:
            log.warning(f"max_bin={max_bin} > {MAX_UINT8_BINS} not yet supported "
                        f"on the uint8 path; clamping")
            max_bin = MAX_UINT8_BINS
        # sample rows for bin finding (reference bin_construct_sample_cnt,
        # dataset_loader.cpp sampling)
        sample_cnt = min(n, int(cfg.bin_construct_sample_cnt))
        if sample_cnt < n:
            rng = np.random.default_rng(cfg.data_random_seed)
            sample_rows = np.sort(rng.choice(n, size=sample_cnt,
                                             replace=False))
        else:
            sample_rows = slice(None)
        mbf = list(cfg.max_bin_by_feature or [])
        forced = _load_forced_bins(cfg, f)
        self.mappers = []
        cat_set = set(cat_idx)
        for j0 in range(0, f, _COL_BLOCK):
            # a block of columns at a time, each column contiguous: one
            # column of a row-major matrix of thousands is a cache miss
            # a value
            block = _column_block(arr, slice(j0, j0 + _COL_BLOCK), sample_rows)
            for j in range(j0, j0 + len(block)):
                fmax = mbf[j] if j < len(mbf) and mbf[j] > 1 else max_bin
                with _cat_span(j in cat_set):
                    m = BinMapper.find_bin(
                        block[j - j0], total_sample_cnt=block.shape[1],
                        max_bin=int(fmax),
                        min_data_in_bin=int(cfg.min_data_in_bin),
                        use_missing=bool(cfg.use_missing),
                        zero_as_missing=bool(cfg.zero_as_missing),
                        is_categorical=(j in cat_set),
                        forced_bounds=forced.get(j))
                self.mappers.append(m)
        self.used_feature_idx = [j for j in range(f)
                                 if not self.mappers[j].is_trivial()]
        dropped = f - len(self.used_feature_idx)
        if dropped:
            log.info(f"Dropped {dropped} trivial (single-bin) feature(s)")
        if not self.used_feature_idx:
            log.fatal("Cannot construct Dataset: all features are trivial "
                      "(single bin). Check your data or binning parameters.")

    def _bin_all(self, arr: np.ndarray) -> None:
        self.bins = self._bin_matrix(arr, in_construct=True)

    def _count_categorical(self) -> None:
        """The job's categorical counters (obs/metrics.py), once a
        training set: its categorical columns, those of them the split
        search scans by sorted subsets (``cat_subset_columns``), the bins
        that hold a level, and the rows in a column's other bin (read
        from the bins while a column of them is a feature: before a
        bundle plan is applied, 0 after)."""
        cats = [(p, self.mappers[j])
                for p, j in enumerate(self.used_feature_idx)
                if self.mappers[j].bin_type == BIN_CATEGORICAL]
        per_feature = self.bundle_plan is None and self.bins.size
        self.cat_counts = counts = {
            "cat_features": len(cats),
            "cat_subset_features": len(self.cat_subset_columns()),
            "cat_levels_kept": sum(len(m.bin_2_categorical)
                                   for _, m in cats),
            "cat_other_rows": sum(
                int(np.count_nonzero(self.bins[:, p] == m.other_bin))
                for p, m in cats if per_feature and m.other_bin >= 0)}
        if cats:
            count_event("cat_features", counts["cat_features"])
            count_event("cat_subset_features", counts["cat_subset_features"])
            count_event("cat_levels_kept", counts["cat_levels_kept"])
            count_event("cat_other_rows", counts["cat_other_rows"])

    def categorical_counts(self) -> Dict[str, int]:
        """``cat_counts``, counted on first use for a set the dense
        construct did not build."""
        if self.cat_counts is None:
            self._count_categorical()
        return self.cat_counts

    def cat_subset_columns(self) -> Tuple[int, ...]:
        """Packed indices of the categorical columns whose levels (the
        other bin left out) outnumber ``max_cat_to_onehot``: those take
        the sorted-subset variant of the split search, the others the
        one-hot variant (ops/split.py)."""
        onehot = int(self.config.max_cat_to_onehot)
        return tuple(
            p for p, j in enumerate(self.used_feature_idx)
            if self.mappers[j].bin_type == BIN_CATEGORICAL
            and len(self.mappers[j].bin_2_categorical) > onehot)

    def _bin_matrix(self, arr: np.ndarray,
                    in_construct: bool = False) -> np.ndarray:
        """Apply this dataset's per-feature mappers to a raw matrix —
        the one binning implementation shared by construction
        (``_bin_all``) and external-matrix prediction
        (``bin_external``).  ``in_construct``: the categorical columns'
        share is timed into the construct's span ``cat_bin_mappers``."""
        n = arr.shape[0]
        used = self.used_feature_idx
        bins = np.zeros((n, len(used)), dtype=np.uint8)
        if arr.shape[1] != self.num_total_features:
            log.fatal(f"The number of features in data ({arr.shape[1]}) does not "
                      f"match Dataset ({self.num_total_features})")
        # by blocks of columns, as ``_construct_mappers`` reads them
        for c0 in range(0, len(used), _COL_BLOCK):
            cols = used[c0:c0 + _COL_BLOCK]
            whole = cols[-1] - cols[0] + 1 == len(cols)
            block = _column_block(
                arr, slice(cols[0], cols[-1] + 1) if whole else cols)
            out = np.empty((len(cols), n), dtype=np.uint8)
            for k, j in enumerate(cols):
                m = self.mappers[j]
                with _cat_span(in_construct
                               and m.bin_type == BIN_CATEGORICAL):
                    out[k] = m.values_to_bins(block[k])
            columns_first(out, into=bins[:, c0:c0 + len(cols)])
        return bins

    def bin_external(self, arr: np.ndarray) -> np.ndarray:
        """Bin an EXTERNAL raw matrix with this dataset's mappers (and
        its EFB bundle layout) — the transformation a validation set
        goes through at construction, exposed for on-device batched
        prediction (boosting/gbdt.py ``_device_predict_raw``): a split
        on ``threshold`` is exactly ``bin <= threshold_bin`` under these
        mappers, so bin-space traversal reproduces raw-space decisions
        (NUMERIC features; categorical raw-space semantics for unseen
        categories differ, which is why the caller excludes categorical
        models)."""
        bins = self._bin_matrix(arr)
        if self.bundle_plan is not None:
            bins = apply_bundles(bins, self.bundle_plan)
        return np.ascontiguousarray(bins)

    def bin_external_pred(self, arr: np.ndarray) -> np.ndarray:
        """i32 LOGICAL (un-bundled) bins for the device BITSET predictor
        (models/predict.py ``predict_bitset_forest``): numeric columns
        bin exactly like ``bin_external``; CATEGORICAL columns map
        unseen categories to the PER-FEATURE sentinel bin ``num_bin``
        and NaN to ``num_bin + 1`` so the bitset walk reproduces the
        host raw-space semantics (unseen/NaN never inherit the
        most-frequent category's side) while the categorical one-hot
        stays as narrow as the feature itself.  Un-bundled on purpose —
        prediction needs no EFB layout, so bundled models route through
        the same path."""
        n = arr.shape[0]
        used = self.used_feature_idx
        if arr.shape[1] != self.num_total_features:
            log.fatal(f"The number of features in data ({arr.shape[1]}) "
                      f"does not match Dataset ({self.num_total_features})")
        bins = np.zeros((n, len(used)), dtype=np.int32)
        for col, j in enumerate(used):
            m = self.mappers[j]
            bins[:, col] = m.values_to_bins_pred(
                arr[:, j], m.num_bin, m.num_bin + 1)
        return np.ascontiguousarray(bins)

    # --------------------------------------------------------------- utility
    def bin_threshold_to_value(self, packed_feature: int, bin_thr: int) -> float:
        """Convert a learner bin threshold to the real-valued model threshold."""
        return self.mappers[self.used_feature_idx[packed_feature]].bin_to_value(bin_thr)

    def subset(self, indices) -> "Dataset":
        """Row subset SHARING mappers and the EFB plan — no re-binning
        (reference Dataset::CopySubrow dataset.h:661 / GetSubset; used by
        cv folds).  ``indices``: i64 row indices into this dataset."""
        idx = np.asarray(indices, np.int64)
        ds = Dataset()
        ds.mappers = self.mappers
        ds.used_feature_idx = list(self.used_feature_idx)
        ds.num_total_features = self.num_total_features
        ds.feature_names = self.feature_names
        ds.config = self.config
        ds.bundle_plan = self.bundle_plan
        ds.bins = self.bins[idx]
        md = Metadata(len(idx))
        md.set_label(self.metadata.label[idx])
        if self.metadata.weight is not None:
            md.set_weight(self.metadata.weight[idx])
        if self.metadata.init_score is not None:
            isc = self.metadata.init_score
            if isc.size == self.num_data:
                md.set_init_score(isc[idx])
            else:  # column-major multiclass flatten
                k = isc.size // self.num_data
                md.set_init_score(
                    isc.reshape(self.num_data, k, order="F")[idx]
                    .reshape(-1, order="F"))
        if self.metadata.position is not None:
            md.set_position(self.metadata.position[idx])
        # query boundaries don't survive arbitrary subsets; callers that
        # fold over whole queries re-set group sizes afterwards
        ds.metadata = md
        if self.raw is not None:
            ds.raw = self.raw[idx]
        return ds

    # ------------------------------------------------------- binary format
    def save_binary(self, path: str) -> None:
        """Persist the BINNED dataset so the expensive binning/EFB pass is
        checkpointable (reference Dataset::SaveBinaryFile /
        LGBM_DatasetSaveBinary c_api.h:516).  Format: npz with a marker
        entry, the packed bin matrix, JSON-serialized mappers and the
        bundle plan."""
        import json
        mappers_json = json.dumps([m.to_dict() for m in self.mappers])
        md = self.metadata
        extra: Dict[str, Any] = {}
        if md.weight is not None:
            extra["weight"] = md.weight
        if md.query_boundaries is not None:
            extra["query_boundaries"] = md.query_boundaries
        if md.init_score is not None:
            extra["init_score"] = md.init_score
        if md.position is not None:
            extra["position"] = md.position
        if self.raw is not None:
            extra["raw"] = self.raw
        if self.bundle_plan is not None:
            p = self.bundle_plan
            extra["bundle_json"] = json.dumps(p.bundles)
            extra["bundle_feat_col"] = p.feat_col
            extra["bundle_src_idx"] = p.src_idx
            extra["bundle_valid"] = p.valid
            extra["bundle_default_bin"] = p.default_bin
            extra["bundle_inv_table"] = p.inv_table
        if self.ingest_provenance is not None:
            extra["provenance_json"] = json.dumps(self.ingest_provenance)
        with open(path, "wb") as fh:  # keep the exact name (np appends .npz)
            np.savez_compressed(
                fh, lgbtpu_dataset=np.int32(1),
                format_version=np.int64(BINARY_FORMAT_VERSION),
                bins=self.bins,
                label=md.label, mappers_json=mappers_json,
                used_feature_idx=np.asarray(self.used_feature_idx, np.int64),
                num_total_features=np.int64(self.num_total_features),
                feature_names=np.asarray(self.feature_names, dtype=object),
                **extra)

    @classmethod
    def load_binary(cls, path: str, config: Optional[Config] = None
                    ) -> "Dataset":
        """Load a dataset written by :meth:`save_binary`."""
        import json
        from .binning import BinMapper
        z = np.load(path, allow_pickle=True)
        if "lgbtpu_dataset" not in z:
            log.fatal(f"{path} is not a lightgbm_tpu binary dataset")
        # v1 (seed) files carry only the marker; treat them as version 1
        version = int(z["format_version"]) if "format_version" in z else 1
        if version > BINARY_FORMAT_VERSION:
            log.fatal(
                f"Binary dataset {path!r} has format version {version}, but "
                f"this build reads up to version {BINARY_FORMAT_VERSION}; "
                "re-save it with a matching lightgbm_tpu version")
        ds = cls()
        ds.config = config or Config()
        ds.bins = z["bins"]
        ds.used_feature_idx = [int(i) for i in z["used_feature_idx"]]
        ds.num_total_features = int(z["num_total_features"])
        ds.feature_names = [str(s) for s in z["feature_names"]]
        ds.mappers = [BinMapper.from_dict(d)
                      for d in json.loads(str(z["mappers_json"]))]
        ds.metadata = Metadata(ds.bins.shape[0])
        ds.metadata.set_label(z["label"])
        if "weight" in z:
            ds.metadata.set_weight(z["weight"])
        if "query_boundaries" in z:
            ds.metadata.query_boundaries = z["query_boundaries"]
        if "init_score" in z:
            ds.metadata.set_init_score(z["init_score"])
        if "position" in z:
            ds.metadata.set_position(z["position"])
        if "raw" in z:
            ds.raw = z["raw"]
        if "provenance_json" in z:
            ds.ingest_provenance = json.loads(str(z["provenance_json"]))
        if "bundle_json" in z:
            from .bundling import BundlePlan
            bundles = json.loads(str(z["bundle_json"]))
            ds.bundle_plan = BundlePlan(
                bundles=bundles,
                feat_col=z["bundle_feat_col"],
                src_idx=z["bundle_src_idx"], valid=z["bundle_valid"],
                default_bin=z["bundle_default_bin"],
                inv_table=z["bundle_inv_table"],
                num_bundles=len(bundles))
        return ds


def _resolve_categorical(categorical_feature: Optional[Sequence[Union[int, str]]],
                         feature_names: List[str]) -> List[int]:
    if not categorical_feature or categorical_feature == "auto":
        return []
    out = []
    for c in categorical_feature:
        if isinstance(c, str) and not c.isdigit():
            if c in feature_names:
                out.append(feature_names.index(c))
            else:
                log.warning(f"Unknown categorical feature name: {c}")
        else:
            out.append(int(c))
    return sorted(set(out))


def _sparse_bundled_matrix(csc, mappers, used_idx, plan, n: int) -> np.ndarray:
    """Bundled uint8 [n, n_bundles] straight from CSC columns.

    Implicit (absent) entries are value 0.0, so each column starts at its
    feature's bin-of-zero — ``values_to_bins(0.0)``, which handles both
    numeric mappers (reference GetDefaultBin) and categorical mappers
    (the bin of category 0) — and only nonzero entries are binned and
    scattered.  With a bundle plan, member encoding and first-writer
    conflict resolution match ``apply_bundles`` on the equivalent dense
    matrix exactly, INCLUDING dense-built reference plans where a
    member's zero bin is a stored (non-default) bin: that member claims
    its implicit rows in member order too.  Rows in which a member's
    value lost to an earlier one are counted (``efb_conflict_rows``).

    Built column-major — a CSC column's rows land in one contiguous
    ``[n]`` line, where the row-major matrix takes a cache line per entry
    (420M of them at 13M x 4,228) — and transposed once at the end.
    """
    _z = np.zeros(1, np.float64)

    def zero_bin(m) -> int:
        return int(m.values_to_bins(_z)[0])

    def entries(j):
        """Column j's stored rows and their bins; a column whose stored
        values are all one value (a one-hot level) bins that value once."""
        lo, hi = csc.indptr[j], csc.indptr[j + 1]
        vals = csc.data[lo:hi]
        if hi - lo > 1 and vals[0] == vals[-1] and \
                (vals == vals[0]).all():
            b = np.broadcast_to(mappers[j].values_to_bins(vals[:1]),
                                (hi - lo,))
        else:
            b = mappers[j].values_to_bins(vals)
        return csc.indices[lo:hi], b

    columns = [[f] for f in range(len(used_idx))] if plan is None \
        else plan.bundles
    out = np.zeros((len(columns), n), np.uint8)
    lost = 0
    for col, members in enumerate(columns):
        line = out[col]
        if len(members) == 1:
            j = used_idx[members[0]]
            zb = zero_bin(mappers[j])
            if zb:
                line[:] = zb
            rows, b = entries(j)
            line[rows] = b.astype(np.uint8)
            continue
        for fv in members:
            j = used_idx[fv]
            rows, b = entries(j)
            stored = plan.valid[fv][b]
            free = line[rows] == 0
            write = stored & free
            lost += int((stored & ~free).sum())
            line[rows[write]] = plan.src_idx[fv][b[write]].astype(np.uint8)
            # a dense-built plan can store the zero bin (its bundle
            # default is the most-frequent bin, not necessarily the zero
            # bin): the member's implicit rows carry it, first-writer
            zb = zero_bin(mappers[j])
            if 0 <= zb < len(plan.valid[fv]) and plan.valid[fv][zb]:
                imp = np.ones(n, bool)
                imp[rows] = False
                imp &= line == 0
                line[imp] = np.uint8(plan.src_idx[fv][zb])
    if lost:
        count_event("efb_conflict_rows", lost)
    return np.ascontiguousarray(out.T)


def _cat_span(is_categorical: bool):
    """The span ``cat_bin_mappers`` around one categorical column's share
    of the dense construct (its level counts in ``_construct_mappers``,
    its bins in ``_bin_matrix``); nothing for a numeric column."""
    if not is_categorical:
        return contextlib.nullcontext()
    return phase("cat_bin_mappers", global_timer, seconds="cat_bin_mappers_s")


def _load_forced_bins(cfg: Config, num_features: int) -> dict:
    """Read ``forcedbins_filename`` (reference dataset_loader.cpp forced-bins
    JSON: ``[{"feature": i, "bin_upper_bound": [...]}, ...]``) into a
    {feature_index: sorted bounds} dict; empty when unset."""
    path = str(cfg.forcedbins_filename or "")
    if not path:
        return {}
    import json
    try:
        with open(path) as fh:
            entries = json.load(fh)
    except (OSError, ValueError) as e:
        log.warning(f"could not read forcedbins_filename={path!r}: {e}")
        return {}
    out = {}
    try:
        for e in entries:
            j = int(e.get("feature", -1))
            bounds = e.get("bin_upper_bound", [])
            if 0 <= j < num_features and bounds:
                out[j] = sorted(float(b) for b in bounds)
            elif j >= num_features:
                log.warning(f"forced bins: feature {j} out of range "
                            f"({num_features} features)")
    except (AttributeError, TypeError, ValueError) as e:
        log.warning(f"malformed forced-bins file {path!r} "
                    f"(expected [{{'feature': i, 'bin_upper_bound': "
                    f"[...]}}, ...]): {e}")
        return {}
    return out
