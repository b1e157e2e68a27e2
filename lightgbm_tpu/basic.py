"""User-facing ``Dataset`` and ``Booster``.

TPU-native re-design of the reference python-package core (reference:
python-package/lightgbm/basic.py — ``Dataset`` :1764 lazy construction with
reference alignment, ``Booster`` :3586).  The reference goes through ctypes
into the C API (src/c_api.cpp); here the "C API layer" is the in-process
framework itself, so these classes orchestrate binning/training directly.
Semantics preserved: lazy Dataset construction, valid sets binned against
their training reference, ``free_raw_data``, Booster train/eval/predict/
save/load surface.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence as TSeq, Union

import numpy as np

from .boosting import create_boosting
from .config import Config, as_config, normalize_params
from .io.dataset import Dataset as _InnerDataset
from .io.parser import load_text_file
from .metrics import create_metrics
from .models.model_io import (model_to_dict, model_to_string,
                              objective_to_string, parse_model_string)
from .models.tree import Tree
from .objectives import create_objective
from .utils import log


class Sequence:
    """Generic data access interface for batched/streaming construction
    (reference basic.py:915 ``Sequence`` ABC: user subclasses implement
    ``__getitem__`` — row or slice — and ``__len__``; the loader reads
    ``batch_size`` rows at a time so the raw source never needs a single
    contiguous materialization)."""

    batch_size = 4096

    def __getitem__(self, idx):  # pragma: no cover - interface
        raise NotImplementedError("Sub-classes of lightgbm_tpu.Sequence "
                                  "must implement __getitem__")

    def __len__(self) -> int:  # pragma: no cover - interface
        raise NotImplementedError("Sub-classes of lightgbm_tpu.Sequence "
                                  "must implement __len__")


def _sequence_to_array(seqs) -> np.ndarray:
    parts = []
    for s in seqs:
        n = len(s)
        for lo in range(0, n, int(getattr(s, "batch_size", 4096) or 4096)):
            hi = min(n, lo + int(getattr(s, "batch_size", 4096) or 4096))
            batch = np.asarray(s[slice(lo, hi)], dtype=np.float64)
            parts.append(batch.reshape(hi - lo, -1))
    return np.concatenate(parts, axis=0) if parts else np.zeros((0, 0))


def _convert_pandas_categorical(df, stored: Optional[list] = None):
    """Convert categorical-dtype columns to float codes (NaN = unseen /
    missing).  Returns (converted df, category lists in DataFrame column
    order, categorical column names).  ``stored`` aligns conversion to the
    TRAINING category lists — the reference's ``pandas_categorical`` model
    field, zipped positionally with the frame's categorical columns."""
    import pandas as pd
    cat_cols = [c for c in df.columns
                if isinstance(df[c].dtype, pd.CategoricalDtype)]
    if not cat_cols:
        return df, None, []
    if stored is not None and len(stored) != len(cat_cols):
        log.fatal(f"train data had {len(stored)} categorical column(s), "
                  f"this data has {len(cat_cols)}")
    df = df.copy()
    out = []
    for i, c in enumerate(cat_cols):
        cats = list(stored[i]) if stored is not None \
            else list(df[c].cat.categories)
        codes = pd.Categorical(df[c],
                               categories=cats).codes.astype(np.float64)
        df[c] = np.where(codes < 0, np.nan, codes)
        out.append(cats)
    return df, out, [str(c) for c in cat_cols]


def _coerce_data(data: Any, categorical_feature, category_maps=None):
    """Normalize input data to (float64 ndarray, feature_names or None,
    categorical_feature, pandas_categorical or None).

    Handles: numpy, list-of-rows, scipy CSR/CSC (densified — bins are dense
    uint8 on device anyway), pandas DataFrame (category dtypes -> codes with
    NaN = missing; 'auto' categorical resolves to those columns, reference
    basic.py _data_from_pandas), pyarrow Table, Sequence / list of Sequence.
    ``category_maps``: training category lists for valid-set alignment."""
    pandas_categorical = None
    feature_names = None
    if isinstance(data, Sequence):
        data = _sequence_to_array([data])
    elif isinstance(data, list) and data and \
            all(isinstance(s, Sequence) for s in data):
        data = _sequence_to_array(data)
    if type(data).__module__.split(".")[0] == "datatable" and \
            hasattr(data, "to_numpy"):
        # datatable Frame (reference basic.py _data_from_datatable): the
        # Frame's own to_numpy gives [n, F] with NaN for NA; column names
        # carry over.  Gated on the module name so the check costs
        # nothing when datatable isn't installed (it isn't in this
        # image; the path is exercised by a duck-typed stub in tests).
        feature_names = [str(c) for c in data.names] \
            if hasattr(data, "names") else None
        arr = data.to_numpy()
        if np.ma.isMaskedArray(arr):
            # real datatable returns a MASKED array for non-float
            # columns with NAs; np.asarray would silently expose the
            # fill values — masked cells must become NaN (missing)
            arr = np.ma.filled(arr.astype(np.float64), np.nan)
        arr = np.asarray(arr, dtype=np.float64)
        if arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        return arr, feature_names, categorical_feature, None
    if hasattr(data, "column_names") and hasattr(data, "to_pandas"):
        # pyarrow Table: numeric-only tables convert column-by-column from
        # the arrow buffers into ONE [n, F] float64 matrix (no pandas
        # block-manager intermediate doubling peak memory — the datasets
        # Arrow exists for are exactly the ones that can't afford it;
        # reference: include/LightGBM/arrow.h zero-copy ingestion).
        # Dictionary (categorical) columns keep the pandas path, which owns
        # the category-code round-trip logic.
        import pyarrow as pa
        if not any(pa.types.is_dictionary(f.type) for f in data.schema):
            names = [str(c) for c in data.column_names]
            n = data.num_rows
            arr = np.empty((n, len(names)), np.float64)
            for ci, col in enumerate(data.columns):
                arr[:, ci] = col.cast(pa.float64()).to_numpy(
                    zero_copy_only=False)
            return arr, names, categorical_feature, None
        data = data.to_pandas()
    if hasattr(data, "columns") and hasattr(data, "dtypes"):  # DataFrame
        feature_names = [str(c) for c in data.columns]
        data, pandas_categorical, cat_names = _convert_pandas_categorical(
            data, stored=category_maps)
        if cat_names and categorical_feature in ("auto", None):
            categorical_feature = cat_names
        arr = data.to_numpy(dtype=np.float64, na_value=np.nan)
        return arr, feature_names, categorical_feature, pandas_categorical
    if hasattr(data, "toarray") and hasattr(data, "nnz"):  # scipy sparse
        # passed through UN-densified: io/dataset.py _from_sparse bins the
        # CSC columns directly (the dense f64 matrix for Allstate-class
        # wide sparse data would be tens of GB)
        return data, feature_names, categorical_feature, pandas_categorical
    return (np.asarray(data, dtype=np.float64), feature_names,
            categorical_feature, pandas_categorical)


def _is_binary_dataset(path) -> bool:
    """True when ``path`` is a lightgbm_tpu binary dataset (npz with our
    marker — the analogue of the reference's binary-file magic check)."""
    try:
        with np.load(str(path), allow_pickle=False) as z:
            return "lgbtpu_dataset" in z
    except (OSError, ValueError):
        return False


def _margin_reached(out: np.ndarray, margin: float) -> np.ndarray:
    """Per-row early-termination test (reference
    prediction_early_stop.cpp — binary: 2*|raw|, multiclass: top-2 gap)."""
    if out.shape[1] == 1:
        return 2.0 * np.abs(out[:, 0]) >= margin
    part = np.partition(out, -2, axis=1)
    return (part[:, -1] - part[:, -2]) >= margin


def _objective_string_transform(out: np.ndarray, obj_str: str) -> np.ndarray:
    """Raw scores [n, k] -> output space, from a model-text objective string
    like ``"binary sigmoid:1"`` (reference ConvertOutput dispatch for
    text-loaded models, objective_function.h)."""
    obj_tokens = obj_str.split(" ")
    obj = obj_tokens[0]
    if obj == "binary":
        sig = 1.0
        for tok in obj_tokens[1:]:
            if tok.startswith("sigmoid:"):
                sig = float(tok.split(":")[1])
        return 1.0 / (1.0 + np.exp(-sig * out))
    if obj == "multiclass":
        ex = np.exp(out - out.max(axis=1, keepdims=True))
        return ex / ex.sum(axis=1, keepdims=True)
    if obj in ("multiclassova", "cross_entropy"):
        return 1.0 / (1.0 + np.exp(-out))
    if obj in ("poisson", "gamma", "tweedie"):
        return np.exp(out)
    if obj == "cross_entropy_lambda":
        return np.log1p(np.exp(out))
    if obj == "regression" and "sqrt" in obj_tokens[1:]:
        return np.sign(out) * out * out
    return out


class Dataset:
    """Lazily-constructed binned dataset (reference basic.py:1764)."""

    def __init__(self, data: Any, label: Optional[TSeq[float]] = None,
                 reference: Optional["Dataset"] = None,
                 weight: Optional[TSeq[float]] = None,
                 group: Optional[TSeq[int]] = None,
                 init_score: Optional[TSeq[float]] = None,
                 feature_name: Union[str, List[str], None] = "auto",
                 categorical_feature: Union[str, List, None] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, position=None):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self.position = position
        self.pandas_categorical: Optional[list] = None
        self._inner: Optional[_InnerDataset] = None
        # continuation: a predictor whose raw predictions become this
        # dataset's init_score (reference basic.py:2059
        # _set_init_score_by_predictor)
        self._predictor: Optional["Booster"] = None

    # ------------------------------------------------------------ plumbing
    def construct(self) -> "Dataset":
        if self._inner is not None:
            return self
        params = dict(self.params)
        ref_inner = None
        if self.reference is not None:
            self.reference.construct()
            ref_inner = self.reference._inner
            params = {**self.reference.params, **params}
        cfg = Config(params)
        data = self.data
        if isinstance(data, (str, os.PathLike)) and _is_binary_dataset(data):
            # binned binary dataset (reference LGBM_DatasetCreateFromFile on
            # a save_binary file): skips parsing AND binning entirely;
            # constructor-supplied metadata overrides what the file carries
            self._inner = _InnerDataset.load_binary(str(data), cfg)
            md = self._inner.metadata
            if self.label is not None:
                md.set_label(self.label)
            if self.weight is not None:
                md.set_weight(self.weight)
            if self.group is not None:
                md.set_group(self.group)
            if self.init_score is not None:
                md.set_init_score(self.init_score)
            if self.position is not None:
                md.set_position(self.position)
            if self._predictor is not None:
                log.fatal("init_model continuation requires raw data; "
                          "binary datasets store only binned values")
            if self.free_raw_data:
                self.data = None
            return self
        if isinstance(data, (str, os.PathLike)):
            arr, label, meta = load_text_file(str(data), cfg)
            if self.label is None:
                self.label = label
            for k, v in meta.items():
                if getattr(self, k, None) is None:
                    setattr(self, k, v)
            data = arr
        else:
            ref_cats = self.reference.pandas_categorical \
                if self.reference is not None else None
            data, fn_auto, catf, pcats = _coerce_data(
                data, self.categorical_feature, category_maps=ref_cats)
            if self.feature_name in ("auto", None) and fn_auto:
                self.feature_name = fn_auto
            self.categorical_feature = catf
            self.pandas_categorical = pcats
        fn = None if self.feature_name in ("auto", None) else list(self.feature_name)
        cat = None if self.categorical_feature in ("auto", None) else \
            list(self.categorical_feature)
        if cat is None:
            # categorical_feature may also arrive through params (the
            # reference honors both the Dataset kwarg and the parameter
            # route, config.h categorical_feature aliases)
            pcat = (self.params or {}).get("categorical_feature")
            for alias in ("cat_feature", "categorical_column",
                          "cat_column", "categorical_features"):
                if pcat in (None, ""):
                    pcat = (self.params or {}).get(alias)
            if pcat not in (None, "", "auto"):
                if isinstance(pcat, str):
                    pcat = [int(x) for x in pcat.split(",") if x != ""]
                cat = list(pcat)
        predictor = self._predictor
        skip_pred_init = getattr(self, "_skip_predictor_init_score", False)
        if predictor is None and self.reference is not None:
            predictor = self.reference._predictor
            skip_pred_init = skip_pred_init or getattr(
                self.reference, "_skip_predictor_init_score", False)
        if predictor is not None and self.init_score is None \
                and not skip_pred_init:
            # ALL of the predictor's trees: they are merged wholesale into
            # the new booster (gbdt.h MergeFrom), so residuals must be
            # computed against the full model, not best_iteration
            raw = predictor.predict(data, raw_score=True, num_iteration=-1)
            # column-major flatten for multi-output (reference regroup,
            # basic.py:2089)
            self.init_score = np.asarray(raw, np.float64).reshape(-1, order="F")
        self._inner = _InnerDataset.from_data(
            data, label=self.label, config=cfg, weight=self.weight,
            group=self.group, init_score=self.init_score, feature_names=fn,
            categorical_feature=cat, reference=ref_inner)
        if self._inner.metadata.position is None and self.position is not None:
            self._inner.metadata.set_position(self.position)
        if self.free_raw_data:
            self.data = None
        return self

    def create_valid(self, data, label=None, **kwargs) -> "Dataset":
        return Dataset(data, label=label, reference=self, **kwargs)

    @classmethod
    def from_inner(cls, inner: _InnerDataset,
                   params: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Wrap an already-constructed inner dataset (subset/binary-load
        paths — the reference's handle-around-existing-Dataset pattern)."""
        d = cls(data=None, params=params)
        d._inner = inner
        d.label = inner.metadata.label
        return d

    def subset(self, used_indices, params: Optional[Dict[str, Any]] = None
               ) -> "Dataset":
        """Row subset sharing bin mappers (reference Dataset.subset ->
        LGBM_DatasetGetSubset)."""
        self.construct()
        return Dataset.from_inner(self._inner.subset(used_indices),
                                  params or dict(self.params))

    def save_binary(self, filename: str) -> "Dataset":
        """Write the BINNED dataset to disk (reference
        Dataset.save_binary -> LGBM_DatasetSaveBinary c_api.h:516); loading
        it back skips parsing and binning."""
        self.construct()
        self._inner.save_binary(str(filename))
        return self

    def _set_resume_predictor(self, predictor: "Booster") -> None:
        """Continuation predictor whose score contribution is restored
        EXTERNALLY (robustness/checkpoint.py resume): its trees are
        merged into the new booster, but no init-score predict pass runs
        — the resume path overwrites (or rebuilds) the f32 score caches
        itself.  Unlike :meth:`_apply_predictor` this works on a
        constructed Dataset whose raw data was freed (the CLI path)."""
        self._predictor = predictor
        self._skip_predictor_init_score = True

    def _apply_predictor(self, predictor: Optional["Booster"]) -> None:
        """Set the continuation predictor (reference basic.py:2576
        ``_set_predictor``).  For an already-constructed dataset the init
        score is injected immediately — requires the raw data."""
        self._predictor = predictor
        # a leftover resume marker must not leak into a later plain
        # init_model continuation (it would silently skip the init-score
        # predict pass)
        self._skip_predictor_init_score = False
        if predictor is None or self._inner is None:
            return
        if self.data is None:
            log.fatal("Cannot use init_model with a constructed Dataset "
                      "whose raw data was freed; create the Dataset with "
                      "free_raw_data=False")
        raw = predictor.predict(self.data, raw_score=True, num_iteration=-1)
        self._inner.metadata.set_init_score(
            np.asarray(raw, np.float64).reshape(-1, order="F"))

    # ------------------------------------------------------------ accessors
    @property
    def inner(self) -> _InnerDataset:
        self.construct()
        return self._inner  # type: ignore[return-value]

    def num_data(self) -> int:
        return self.inner.num_data

    def num_feature(self) -> int:
        return self.inner.num_total_features

    def get_label(self) -> np.ndarray:
        return self.inner.metadata.label

    def get_weight(self) -> Optional[np.ndarray]:
        return self.inner.metadata.weight

    def get_group(self) -> Optional[np.ndarray]:
        qb = self.inner.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self) -> Optional[np.ndarray]:
        return self.inner.metadata.init_score

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None:
            self._inner.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weight(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(init_score)
        return self

    def get_data(self):
        """Raw data this Dataset was built from (reference
        Dataset.get_data; raises after free_raw_data-style release)."""
        if self.data is None:
            log.fatal("Cannot get data: the raw data was freed or this "
                      "Dataset was created from a binary/subset source")
        return self.data

    def get_params(self) -> Dict[str, Any]:
        """reference Dataset.get_params."""
        return dict(self.params)

    def get_feature_name(self) -> List[str]:
        """reference Dataset.get_feature_name."""
        return list(self.feature_names)

    def set_feature_name(self, feature_name: List[str]) -> "Dataset":
        """reference Dataset.set_feature_name (alias of
        set_feature_names)."""
        return self.set_feature_names(list(feature_name))

    def get_field(self, field_name: str) -> Optional[np.ndarray]:
        """reference Dataset.get_field: label/weight/init_score as float
        arrays, 'group' as cumulative query BOUNDARIES (the reference's
        storage form), 'position' as int."""
        self.construct()
        md = self._inner.metadata
        if field_name == "label":
            return None if md.label is None else np.asarray(md.label)
        if field_name == "weight":
            return None if md.weight is None else np.asarray(md.weight)
        if field_name == "init_score":
            return None if md.init_score is None else \
                np.asarray(md.init_score)
        if field_name == "group":
            qb = md.query_boundaries
            return None if qb is None else np.asarray(qb, np.int32)
        if field_name == "position":
            pos = getattr(md, "position", None)
            return None if pos is None else np.asarray(pos, np.int32)
        log.fatal(f"Unknown field name: {field_name}")

    def set_field(self, field_name: str, data) -> "Dataset":
        """reference Dataset.set_field."""
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        if field_name == "group":
            return self.set_group(np.asarray(data))
        if field_name == "position":
            return self.set_position(data)
        log.fatal(f"Unknown field name: {field_name}")

    def get_position(self) -> Optional[np.ndarray]:
        """reference Dataset.get_position (position-debiased ranking)."""
        return self.get_field("position")

    def set_position(self, position) -> "Dataset":
        """reference Dataset.set_position."""
        pos = np.asarray(position, np.int32)
        self.position = pos
        if self._inner is not None:
            self._inner.metadata.set_position(pos)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """reference Dataset.set_categorical_feature: effective before
        construction; afterwards the binning is fixed."""
        if self._inner is not None and \
                list(categorical_feature or []) != \
                list(self.categorical_feature or []):
            log.warning("set_categorical_feature ignored: the Dataset is "
                        "already constructed with its own binning")
            return self
        self.categorical_feature = categorical_feature
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """reference Dataset.set_reference: align bins to another
        dataset's mappers (before construction)."""
        if self._inner is not None and reference is not self.reference:
            log.warning("set_reference ignored: the Dataset is already "
                        "constructed")
            return self
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """reference Dataset.get_ref_chain: the set of datasets reachable
        through .reference links."""
        head: Optional["Dataset"] = self
        chain = set()
        while head is not None and len(chain) < ref_limit:
            if head in chain:
                break
            chain.add(head)
            head = head.reference
        return chain

    def feature_num_bin(self, feature: Union[int, str]) -> int:
        """reference Dataset.feature_num_bin: bin count of one feature."""
        self.construct()
        if isinstance(feature, str):
            feature = self.feature_names.index(feature)
        return int(self._inner.mappers[int(feature)].num_bin)

    @property
    def feature_names(self) -> List[str]:
        return self.inner.feature_names

    def set_feature_names(self, names: List[str]) -> "Dataset":
        """LGBM_DatasetSetFeatureNames (c_api.h:551)."""
        names = [str(n) for n in names]
        inner = self.inner
        if len(names) != inner.num_total_features:
            raise ValueError(
                f"{len(names)} names for {inner.num_total_features} features")
        inner.feature_names = names
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Column-concatenate another CONSTRUCTED dataset's features
        (reference LGBM_DatasetAddFeaturesFrom c_api.h:631 /
        Dataset::AddFeaturesFrom)."""
        a, b = self.inner, other.inner
        if a.num_data != b.num_data:
            raise ValueError("datasets hold different row counts")
        if a.bundle_plan is not None or b.bundle_plan is not None:
            raise ValueError(
                "add_features_from does not compose with EFB bundles; "
                "construct both datasets with enable_bundle=false")
        na = a.num_total_features
        a.bins = np.concatenate([a.bins, b.bins], axis=1)
        a.used_feature_idx = list(a.used_feature_idx) + \
            [na + i for i in b.used_feature_idx]
        a.mappers = list(a.mappers) + list(b.mappers)
        a.feature_names = list(a.feature_names) + list(b.feature_names)
        a.num_total_features = na + b.num_total_features
        return self

    def serialize_reference(self) -> bytes:
        """Binning reference (mappers + schema, no rows) as bytes
        (reference LGBM_DatasetSerializeReferenceToBinary)."""
        import json as _json
        inner = self.inner
        doc = {
            "lgbtpu_reference": 1,
            "mappers": [m.to_dict() for m in inner.mappers],
            "used_feature_idx": list(map(int, inner.used_feature_idx)),
            "num_total_features": int(inner.num_total_features),
            "feature_names": list(inner.feature_names),
            "params": {k: v for k, v in (self.params or {}).items()
                       if isinstance(v, (str, int, float, bool))},
        }
        return _json.dumps(doc).encode()

    @classmethod
    def deserialize_reference(cls, raw: bytes) -> "Dataset":
        """Rebuild a row-less reference Dataset whose ``create_valid``
        bins new rows on the serialized mapper grid (reference
        LGBM_DatasetCreateFromSerializedReference c_api.h:142)."""
        import json as _json
        from .io.binning import BinMapper
        from .io.dataset import Dataset as _InnerDataset, Metadata
        doc = _json.loads(raw.decode())
        if not doc.get("lgbtpu_reference"):
            raise ValueError("not a serialized dataset reference")
        inner = _InnerDataset()
        inner.mappers = [BinMapper.from_dict(d) for d in doc["mappers"]]
        inner.used_feature_idx = doc["used_feature_idx"]
        inner.num_total_features = doc["num_total_features"]
        inner.feature_names = doc["feature_names"]
        inner.bins = np.zeros((0, len(inner.used_feature_idx)), np.uint8)
        inner.metadata = Metadata(0)
        ds = cls.from_inner(inner, params=doc.get("params") or {})
        return ds


class Booster:
    """Trained/trainable model handle (reference basic.py:3586)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = normalize_params(params)
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._gbdt = None
        self._loaded: Optional[Dict[str, Any]] = None
        self.train_set = train_set
        self.pandas_categorical: Optional[list] = None
        if model_file is not None:
            # a missing/unreadable model file is an operator-facing error:
            # name the path in a LightGBMError instead of leaking the raw
            # OSError traceback
            try:
                with open(model_file) as f:
                    model_str = f.read()
            except OSError as e:
                raise log.LightGBMError(
                    f"cannot read model file {str(model_file)!r}: "
                    f"{type(e).__name__}: {e}") from e
        if model_str is not None:
            src = (f"model file {str(model_file)!r}"
                   if model_file is not None else "model string")
            try:
                self._loaded = parse_model_string(model_str)
            except log.LightGBMError as e:
                raise log.LightGBMError(f"failed to parse {src}: {e}") \
                    from None
            except Exception as e:
                # truncated/garbled tree blocks surface as KeyError /
                # ValueError deep in Tree.from_text; wrap them with the
                # path so the operator knows WHICH artifact is bad
                raise log.LightGBMError(
                    f"failed to parse {src}: "
                    f"{type(e).__name__}: {e}") from e
            self.pandas_categorical = self._loaded.get("pandas_categorical")
            return
        if train_set is None:
            log.fatal("Booster requires train_set or a model to load")
        train_set.params = {**train_set.params, **{
            k: v for k, v in self.params.items()}}
        train_set.construct()
        self.pandas_categorical = train_set.pandas_categorical
        cfg = Config(self.params)
        self._cfg = cfg
        self._gbdt = create_boosting(cfg, train_set.inner)
        # continuation: merge the init model's trees so the booster is
        # self-contained (reference basic.py:3675 LGBM_BoosterMerge →
        # gbdt.h:70 MergeFrom)
        if train_set._predictor is not None:
            self._gbdt.merge_from(train_set._predictor._get_trees())

    # ------------------------------------------------------------ training
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        if not hasattr(self, "_valid_lookup"):
            self._valid_lookup = {}
        self._valid_lookup[data] = len(self._gbdt.valid_sets)
        self._gbdt.add_valid(data.inner, name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting round (reference Booster.update →
        LGBM_BoosterUpdateOneIter c_api.h:765; custom fobj → :793)."""
        if fobj is None:
            return self._gbdt.train_one_iter()
        if self._gbdt.objective is not None:
            log.fatal("Cannot use fobj with a built-in objective; set "
                      "objective=none")
        grad, hess = fobj(self._current_train_preds(), self.train_set)
        return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))

    def _current_train_preds(self) -> np.ndarray:
        return self._gbdt._host_scores(self._gbdt.scores)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return self._gbdt.num_trees() if self._gbdt else \
            len(self._loaded["trees"])

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration if self._gbdt else \
            self._loaded["num_tree_per_iteration"]

    # --------------------------------------------------------- telemetry
    def telemetry(self) -> Dict[str, Any]:
        """Telemetry snapshot for this booster (obs/): counters/gauges
        accumulated while training, the per-booster phase-timing table,
        the process's compile table (obs/compile_events.py ``table()``:
        which programs were traced, lowered, compiled or loaded, under
        which span) and a current host/device memory sample.  Loaded
        (predict-only) boosters report the last two only."""
        if self._gbdt is not None:
            return self._gbdt.telemetry()
        from .obs import compile_events, memory as obs_memory
        return {"counters": {}, "gauges": {}, "phases": {},
                "compile_table": compile_events.table(),
                "memory": obs_memory.memory_snapshot()}

    def prometheus_text(self) -> str:
        """Training-side Prometheus text exposition (obs/prom.py):
        telemetry counters/gauges plus watchtower rollup gauges and SLO
        state when a watchtower is attached — same format as
        ``PredictionServer.prometheus_text`` so training and serving
        share one scrape pipeline."""
        if self._gbdt is not None:
            return self._gbdt.prometheus_text()
        from .obs import prom
        return prom.training_text({}, {})

    # ---------------------------------------------------------- evaluation
    def eval_train(self):
        out = self._gbdt.eval_train()
        name = getattr(self, "_train_data_name", "training")
        if name != "training":
            out = [(name,) + r[1:] for r in out]
        return out

    def eval_valid(self):
        return self._gbdt.eval_valid()

    # ---------------------------------------------------------- prediction
    def predict(self, data: Any, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False, pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                **kwargs) -> np.ndarray:
        if hasattr(data, "toarray") and hasattr(data, "nnz"):
            # scipy input densifies in BYTE-bounded row blocks (~512 MB
            # dense each) so prediction never allocates the full [n, F]
            # float64 matrix — the sparse ingestion memory story holds at
            # predict time too.  Wide matrices get proportionally fewer
            # rows per block.
            block = max(256, min(65536,
                                 (512 << 20) // (8 * max(data.shape[1], 1))))
            if data.shape[0] > block:
                csr = data.tocsr()
                blocks = [self.predict(
                    csr[r0:r0 + block],
                    start_iteration=start_iteration,
                    num_iteration=num_iteration,
                    raw_score=raw_score, pred_leaf=pred_leaf,
                    pred_contrib=pred_contrib,
                    pred_early_stop=pred_early_stop,
                    pred_early_stop_freq=pred_early_stop_freq,
                    pred_early_stop_margin=pred_early_stop_margin,
                    **kwargs)
                    for r0 in range(0, data.shape[0], block)]
                return np.concatenate(blocks, axis=0)
        X = self._to_matrix(data)
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if pred_contrib:
            return self._predict_contrib(X, start_iteration, num_iteration)
        early = (pred_early_stop, pred_early_stop_freq,
                 pred_early_stop_margin) if pred_early_stop else None
        if self._gbdt is not None:
            return self._gbdt.predict(X, raw_score=raw_score,
                                      start_iteration=start_iteration,
                                      num_iteration=num_iteration,
                                      pred_leaf=pred_leaf, early=early)
        return self._predict_loaded(X, start_iteration, num_iteration,
                                    raw_score, pred_leaf, early)

    def _to_matrix(self, data: Any) -> np.ndarray:
        if hasattr(data, "column_names") and hasattr(data, "to_pandas"):
            data = data.to_pandas()  # pyarrow Table
        if hasattr(data, "columns") and hasattr(data, "dtypes"):
            # pandas: categorical columns convert through the TRAINING
            # category lists (reference pandas_categorical round-trip)
            data, _, _ = _convert_pandas_categorical(
                data, stored=self.pandas_categorical)
            return data.to_numpy(dtype=np.float64, na_value=np.nan)
        if hasattr(data, "toarray"):
            return np.asarray(data.toarray(), np.float64)
        return np.asarray(data, np.float64)

    def num_feature(self) -> int:
        """Number of features the model was trained on (reference
        Booster.num_feature / LGBM_BoosterGetNumFeature c_api.h:876)."""
        return len(self.feature_name())

    def _predict_loaded(self, X, start_iteration, num_iteration, raw_score,
                        pred_leaf, early=None) -> np.ndarray:
        trees = self._loaded["trees"]
        k = self._loaded["num_tree_per_iteration"]
        total_iters = len(trees) // k if k else 0
        end = total_iters if num_iteration is None or num_iteration <= 0 else \
            min(total_iters, start_iteration + num_iteration)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        if pred_leaf:
            leaves = [trees[it * k + c].predict_leaf_index(X)
                      for it in range(start_iteration, end) for c in range(k)]
            return np.stack(leaves, axis=1)
        out = np.zeros((X.shape[0], k))
        active = np.ones(X.shape[0], bool) if early is not None else None
        for it in range(start_iteration, end):
            for c in range(k):
                if early is not None:
                    out[active, c] += trees[it * k + c].predict(X[active])
                else:
                    out[:, c] += trees[it * k + c].predict(X)
            if early is not None and (it + 1) % early[1] == 0:
                active &= ~_margin_reached(out, early[2])
                if not active.any():
                    break
        if not raw_score:
            out = _objective_string_transform(out, self._loaded["objective"])
        return out[:, 0] if k == 1 else out

    def _predict_contrib(self, X, start_iteration, num_iteration):
        """SHAP contributions (reference PredictContrib,
        gbdt_prediction.cpp:44; models/shap.py TreeSHAP)."""
        from .models.shap import predict_contrib
        trees = self._get_trees()
        k = self.num_model_per_iteration()
        nf = (self._gbdt.train_set.num_total_features if self._gbdt
              else self._loaded["max_feature_idx"] + 1)
        end = -1 if num_iteration is None or num_iteration <= 0 else \
            start_iteration + num_iteration
        return predict_contrib(trees, X, nf, k, start_iteration, end)

    def _get_trees(self) -> List[Tree]:
        return self._gbdt.models if self._gbdt is not None \
            else self._loaded["trees"]

    def refit(self, data: Any, label, decay_rate: Optional[float] = None,
              weight=None, group=None, **kwargs) -> "Booster":
        """Re-fit leaf values of the existing tree structures on new data
        (reference GBDT::RefitTree gbdt.cpp:258, LGBM_BoosterRefit
        c_api.h:776, FitByExistingTree serial_tree_learner.cpp:249-276).
        Returns a new Booster; structures are unchanged, leaf outputs are
        ``decay * old + (1 - decay) * new``."""
        from .io.dataset import Metadata
        from .models.model_io import objective_string_to_params

        params = dict(self.params)
        if self._gbdt is None and self._loaded is not None:
            # file/string-loaded booster: recover the objective from the
            # model header, not from (empty) construction params
            params = {**objective_string_to_params(self._loaded["objective"]),
                      **params}
        cfg = Config(params)
        if decay_rate is None:
            decay_rate = float(cfg.refit_decay_rate)
        X = self._to_matrix(data)
        n = X.shape[0]
        y = np.asarray(label, np.float64)
        md = Metadata(n)
        md.set_label(y)
        if weight is not None:
            md.set_weight(weight)
        if group is not None:
            md.set_group(group)
        objective = create_objective(cfg)
        if objective is None:
            log.fatal("refit requires a built-in objective")
        objective.init(md, n)

        new_booster = Booster(model_str=self.model_to_string(num_iteration=-1))
        trees = new_booster._loaded["trees"]
        k = max(1, new_booster._loaded["num_tree_per_iteration"])
        l1, l2 = float(cfg.lambda_l1), float(cfg.lambda_l2)
        scores = np.zeros((n, k))
        import jax.numpy as jnp
        for it in range(len(trees) // k):
            g, h = objective.get_gradients(
                jnp.asarray(scores[:, 0] if k == 1 else scores, jnp.float32))
            g = np.asarray(g, np.float64).reshape(n, k, order="F") \
                if g.ndim == 1 else np.asarray(g, np.float64)
            h = np.asarray(h, np.float64).reshape(n, k, order="F") \
                if h.ndim == 1 else np.asarray(h, np.float64)
            for c in range(k):
                t = trees[it * k + c]
                leaf = t.predict_leaf_index(X)
                sg = np.bincount(leaf, weights=g[:, c],
                                 minlength=t.num_leaves)
                sh = np.bincount(leaf, weights=h[:, c],
                                 minlength=t.num_leaves)
                # CalculateSplittedLeafOutput with L1 thresholding
                sg_reg = np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0)
                new_out = -sg_reg / (sh + l2 + 1e-15) * t.shrinkage
                t.leaf_value = decay_rate * t.leaf_value + \
                    (1.0 - decay_rate) * new_out
                scores[:, c] += t.leaf_value[leaf]
        return new_booster

    def refit_from_leaf_preds(self, leaf_preds: np.ndarray,
                              decay_rate: Optional[float] = None
                              ) -> "Booster":
        """Re-fit leaf values IN PLACE from a [n, num_trees] leaf-index
        matrix on the TRAINING set (reference LGBM_BoosterRefit c_api.h:776
        / GBDT::RefitTree gbdt.cpp:258; the Python wrapper predicts leaves
        then calls this)."""
        if self._gbdt is None:
            log.fatal("refit_from_leaf_preds needs a booster with training "
                      "state (use refit(data, label) on loaded models)")
        g = self._gbdt
        cfg = g.config
        if decay_rate is None:
            decay_rate = float(cfg.refit_decay_rate)
        l1, l2 = float(cfg.lambda_l1), float(cfg.lambda_l2)
        trees = g.models
        k = max(1, g.num_tree_per_iteration)
        n = leaf_preds.shape[0]
        if leaf_preds.shape[1] != len(trees):
            log.fatal(f"leaf matrix has {leaf_preds.shape[1]} columns for "
                      f"{len(trees)} trees")
        import jax.numpy as jnp
        scores = np.zeros((n, k))
        for it in range(len(trees) // k):
            gj, hj = g.objective.get_gradients(
                jnp.asarray(scores[:, 0] if k == 1 else scores, jnp.float32))
            gr = np.asarray(gj, np.float64).reshape(n, k, order="F") \
                if np.asarray(gj).ndim == 1 else np.asarray(gj, np.float64)
            hs = np.asarray(hj, np.float64).reshape(n, k, order="F") \
                if np.asarray(hj).ndim == 1 else np.asarray(hj, np.float64)
            for c in range(k):
                t = trees[it * k + c]
                leaf = leaf_preds[:, it * k + c]
                sg = np.bincount(leaf, weights=gr[:, c],
                                 minlength=t.num_leaves)
                sh = np.bincount(leaf, weights=hs[:, c],
                                 minlength=t.num_leaves)
                sg_reg = np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0)
                new_out = -sg_reg / (sh + l2 + 1e-15) * t.shrinkage
                t.leaf_value = decay_rate * t.leaf_value + \
                    (1.0 - decay_rate) * new_out
                scores[:, c] += t.leaf_value[leaf]
        g.invalidate_score_cache()
        return self

    def merge_models(self, other: "Booster") -> "Booster":
        """Append the other model's trees (reference LGBM_BoosterMerge
        c_api.h:680)."""
        import copy
        trees = other._get_trees()
        if self._gbdt is not None:
            self._gbdt.append_models(trees)
        else:
            # deep copy: later leaf edits on this booster must not reach
            # through to the source model (append_models copies too)
            self._loaded["trees"].extend(copy.deepcopy(trees))
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """LGBM_BoosterResetParameter (c_api.h:853): swap learning-control
        parameters on the live booster."""
        self.params = {**self.params, **normalize_params(params)}
        if self._gbdt is not None:
            self._gbdt.reset_config(Config(self.params))
        return self

    def reset_training_data(self, train_set: Dataset) -> "Booster":
        """LGBM_BoosterResetTrainingData (c_api.h:843)."""
        train_set.construct()
        if self._gbdt is None:
            log.fatal("reset_training_data needs a training booster")
        self._gbdt.reset_training_data(train_set.inner)
        self.train_set = train_set
        return self

    def shuffle_models(self, start: int = 0, end: int = -1) -> "Booster":
        """LGBM_BoosterShuffleModels (c_api.h:698): random-permute whole
        iterations in [start, end)."""
        k = max(1, self.num_model_per_iteration())
        trees = self._get_trees()
        n_iter = len(trees) // k
        end = n_iter if end < 0 else min(end, n_iter)
        start = max(0, start)
        if end - start > 1:
            rng = np.random.default_rng(int(self.params.get("seed") or 1))
            perm = rng.permutation(end - start) + start
            groups = [trees[i * k:(i + 1) * k] for i in range(n_iter)]
            shuffled = groups[:start] + [groups[p] for p in perm] \
                + groups[end:]
            flat = [t for grp in shuffled for t in grp]
            if self._gbdt is not None:
                self._gbdt.models = flat
                self._gbdt.invalidate_score_cache()
            else:
                self._loaded["trees"] = flat
        return self

    # ------------------------------------------------------------- im/export
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        if self._gbdt is None:
            # re-serialize loaded model
            d = self._loaded
            return model_to_string(
                d["trees"], num_class=d["num_class"],
                num_tree_per_iteration=d["num_tree_per_iteration"],
                max_feature_idx=d["max_feature_idx"],
                objective_str=d["objective"], feature_names=d["feature_names"],
                feature_infos=d["feature_infos"], params={},
                pandas_categorical=self.pandas_categorical)
        g = self._gbdt
        ds = g.train_set
        k = g.num_tree_per_iteration
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        total_iters = len(g.models) // k
        end = total_iters if num_iteration is None or num_iteration <= 0 else \
            min(total_iters, start_iteration + num_iteration)
        trees = [g.models[it * k + c] for it in range(start_iteration, end)
                 for c in range(k)]
        feature_infos = []
        for j in range(ds.num_total_features):
            m = ds.mappers[j]
            if m.is_trivial():
                feature_infos.append("none")
            elif m.bin_type == 1:
                feature_infos.append(
                    ":".join(str(c) for c in m.bin_2_categorical) or "none")
            else:
                feature_infos.append(f"[{m.min_val:g}:{m.max_val:g}]")
        obj_str = objective_to_string(
            g.objective.NAME if g.objective else "none", g.config)
        return model_to_string(
            trees, num_class=g.num_class, num_tree_per_iteration=k,
            max_feature_idx=ds.num_total_features - 1, objective_str=obj_str,
            feature_names=ds.feature_names, feature_infos=feature_infos,
            params=g.config._explicit,
            pandas_categorical=self.pandas_categorical)

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0, **kwargs) -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict[str, Any]:
        """Model as a python dict (reference Booster.dump_model returns the
        parsed JSON of LGBM_BoosterDumpModel, basic.py)."""
        if self._gbdt is not None:
            g = self._gbdt
            k = g.num_tree_per_iteration
            return model_to_dict(
                g.models, num_class=g.num_class, num_tree_per_iteration=k,
                max_feature_idx=g.train_set.num_total_features - 1,
                objective_str=objective_to_string(
                    g.objective.NAME if g.objective else "none", g.config),
                feature_names=g.train_set.feature_names)
        d = self._loaded
        return model_to_dict(
            d["trees"], num_class=d["num_class"],
            num_tree_per_iteration=d["num_tree_per_iteration"],
            max_feature_idx=d["max_feature_idx"],
            objective_str=d["objective"], feature_names=d["feature_names"])

    def trees_to_dataframe(self):
        """Flatten the model into a pandas DataFrame, one row per node
        (reference Booster.trees_to_dataframe, basic.py): columns
        tree_index, node_depth, node_index, left_child, right_child,
        parent_index, split_feature, split_gain, threshold, decision_type,
        missing_direction, missing_type, value, weight, count."""
        import pandas as pd
        from .models.tree import _decode_decision_type
        rows = []
        names = self.feature_name()

        def visit(t, ti, node, depth, parent):
            """Emit one node's row; returns its tag (iterative caller)."""
            if node < 0:
                leaf = -node - 1
                tag = f"{ti}-L{leaf}"
                rows.append(dict(
                    tree_index=ti, node_depth=depth, node_index=tag,
                    left_child=None, right_child=None, parent_index=parent,
                    split_feature=None, split_gain=None, threshold=None,
                    decision_type=None, missing_direction=None,
                    missing_type=None, value=float(t.leaf_value[leaf]),
                    weight=float(t.leaf_weight[leaf]),
                    count=int(t.leaf_count[leaf])))
                return tag, None
            tag = f"{ti}-S{node}"
            is_cat, default_left, missing_type = _decode_decision_type(
                int(t.decision_type[node]))
            if is_cat:
                # reference reports the '||'-joined category set, not the
                # internal cat-list index (reference basic.py
                # trees_to_dataframe)
                csi = int(t.cat_split_index[node])
                thr_out = "||".join(str(c) for c in t.cat_threshold[csi])
            else:
                thr_out = float(t.threshold[node])
            row = dict(
                tree_index=ti, node_depth=depth, node_index=tag,
                parent_index=parent,
                split_feature=names[int(t.split_feature[node])],
                split_gain=float(t.split_gain[node]),
                threshold=thr_out,
                decision_type="==" if is_cat else "<=",
                missing_direction="left" if default_left else "right",
                missing_type=["None", "Zero", "NaN"][missing_type],
                value=float(t.internal_value[node]),
                weight=float(t.internal_weight[node])
                if len(t.internal_weight) > node else 0.0,
                count=int(t.internal_count[node]))
            rows.append(row)
            return tag, row

        for ti, t in enumerate(self._get_trees()):
            # explicit stack: leaf-wise trees can be num_leaves deep, which
            # would blow Python's recursion limit
            stack = [(0 if t.num_leaves > 1 else -1, 1, None, None, None)]
            while stack:
                node, depth, parent, prow, side = stack.pop()
                tag, row = visit(t, ti, node, depth, parent)
                if prow is not None:
                    prow[side] = tag
                if row is not None:
                    stack.append((int(t.right_child[node]), depth + 1, tag,
                                  row, "right_child"))
                    stack.append((int(t.left_child[node]), depth + 1, tag,
                                  row, "left_child"))
        cols = ["tree_index", "node_depth", "node_index", "left_child",
                "right_child", "parent_index", "split_feature", "split_gain",
                "threshold", "decision_type", "missing_direction",
                "missing_type", "value", "weight", "count"]
        return pd.DataFrame(rows).reindex(columns=cols)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        trees = (self._gbdt.models if self._gbdt else self._loaded["trees"])
        nf = (self._gbdt.train_set.num_total_features if self._gbdt
              else self._loaded["max_feature_idx"] + 1)
        imp = np.zeros(nf)
        for t in trees:
            for i in range(t.num_leaves - 1):
                if importance_type == "split":
                    imp[t.split_feature[i]] += 1
                else:
                    imp[t.split_feature[i]] += max(float(t.split_gain[i]), 0.0)
        return imp

    def feature_name(self) -> List[str]:
        if self._gbdt is not None:
            return self._gbdt.train_set.feature_names
        return self._loaded["feature_names"]

    # ------------------------------------------------- parity accessors
    def model_from_string(self, model_str: str) -> "Booster":
        """Load a model INTO this booster (reference
        Booster.model_from_string): replaces the model state; the
        booster's own params are kept (the reference does not touch
        them) and training-only state is cleared."""
        other = Booster(model_str=model_str)
        self._gbdt = None
        self._loaded = other._loaded
        self.pandas_categorical = other.pandas_categorical
        self.best_iteration = -1
        self.train_set = None
        self._valid_lookup = {}
        self._train_data_name = "training"
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """reference Booster.set_train_data_name: the label used for the
        training set in eval output (see eval_train)."""
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """reference Booster.free_dataset: drop the Python references to
        the raw training/validation data (the binned device state the
        booster trains on is retained)."""
        self.train_set = None
        return self

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """reference Booster.set_network -> LGBM_NetworkInit: records the
        machine list and brings up the distributed runtime
        (parallel/launcher.py; device collectives are XLA's)."""
        from .capi_impl import network_init
        if isinstance(machines, (list, set)):
            machines = ",".join(str(m) for m in machines)
        network_init(str(machines), int(local_listen_port),
                     int(listen_time_out), int(num_machines))
        self._network = True
        return self

    def free_network(self) -> "Booster":
        """reference Booster.free_network."""
        from .capi_impl import network_free
        network_free()
        self._network = False
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """reference Booster.get_leaf_output / LGBM_BoosterGetLeafValue."""
        return float(self._get_trees()[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """reference Booster.set_leaf_output / LGBM_BoosterSetLeafValue;
        cached training scores are rebuilt like the reference's
        ScoreUpdater re-drive."""
        t = self._get_trees()[tree_id]
        t.leaf_value[leaf_id] = float(value)
        if t.is_linear:
            t.leaf_const[leaf_id] = float(value)
            t.leaf_coeff[leaf_id] = []
            t.leaf_features[leaf_id] = []
        if self._gbdt is not None:
            self._gbdt.invalidate_score_cache()
        return self

    def upper_bound(self) -> float:
        """reference Booster.upper_bound: sum over trees of the maximum
        leaf output (GBDT::GetUpperBoundValue)."""
        return float(sum(float(np.max(t.leaf_value)) if t.num_leaves else 0.0
                         for t in self._get_trees()))

    def lower_bound(self) -> float:
        """reference Booster.lower_bound (GBDT::GetLowerBoundValue)."""
        return float(sum(float(np.min(t.leaf_value)) if t.num_leaves else 0.0
                         for t in self._get_trees()))

    def _check_valid_alignment(self, data: Dataset) -> None:
        """The reference refuses validation data with different bin
        mappers (Dataset::CheckAlign); a dataset binned independently
        would evaluate trees against foreign bin indices."""
        if self.train_set is not None and \
                self.train_set in data.get_ref_chain():
            return
        data.construct()
        tm = self._gbdt.train_set.mappers
        vm = data.inner.mappers

        def same(a, b):
            # categorical mappers carry their mapping in bin_2_categorical
            # (bin_upper_bound stays the default), so compare both forms
            return (a.bin_type == b.bin_type
                    and a.num_bin == b.num_bin
                    and np.array_equal(np.asarray(a.bin_upper_bound),
                                       np.asarray(b.bin_upper_bound))
                    and list(getattr(a, "bin_2_categorical", []) or []) ==
                    list(getattr(b, "bin_2_categorical", []) or []))

        if len(tm) != len(vm) or any(not same(a, b)
                                     for a, b in zip(tm, vm)):
            log.fatal("cannot evaluate data with different bin mappers; "
                      "build it with create_valid / reference=")

    def eval(self, data: Dataset, name: str, feval=None) -> List[tuple]:
        """Evaluate the current model on ``data`` (reference
        Booster.eval): registered train/valid sets reuse their cached
        scores; any other ALIGNED Dataset is registered like the
        reference does (and stays registered)."""
        if self._gbdt is not None and data is self.train_set:
            out = [(name,) + r[1:] for r in self.eval_train()]
            scores_for_feval = self._gbdt.scores
        elif self._gbdt is not None:
            if data not in getattr(self, "_valid_lookup", {}):
                # the reference's eval registers unseen data as a valid
                # set; rebuilding ONLY the new entry's scores folds the
                # existing trees in without replaying every other cache
                self._check_valid_alignment(data)
                self.add_valid(data, name)
                self._gbdt.invalidate_score_cache(
                    only_valid_index=self._valid_lookup[data])
            vi = self._valid_lookup[data]
            out = [(name,) + r[1:] for r in self._gbdt._eval_metric_list(
                self._gbdt.valid_names[vi], self._gbdt.valid_metrics[vi],
                self._gbdt.valid_scores[vi])]
            scores_for_feval = self._gbdt.valid_scores[vi]
        else:
            # loaded booster: score through prediction (needs the raw
            # data, i.e. free_raw_data=False on `data`), with metrics and
            # output conversion from the MODEL's stored params/objective
            from .config import Config
            from .metrics import create_metrics
            from .objectives import create_objective
            data.construct()
            # model files store the objective with inline args
            # ("binary sigmoid:1", "lambdarank lambdarank_truncation..."):
            # split into the name plus parameter tokens
            obj_toks = str(self._loaded.get("objective", "none")).split()
            obj_extra = {t.split(":", 1)[0]: t.split(":", 1)[1]
                         for t in obj_toks[1:] if ":" in t}
            cfg = Config({**(self._loaded.get("params") or {}), **obj_extra,
                          "objective": obj_toks[0] if obj_toks else "none",
                          **self.params})
            ms = create_metrics(cfg)
            md = data.inner.metadata
            for m in ms:
                m.init(md, data.inner.num_data)
            obj = None
            try:
                obj = create_objective(cfg)
                if obj is not None:
                    obj.init(md, data.inner.num_data)
            except Exception:
                obj = None
            raw = np.asarray(self.predict(data.get_data(), raw_score=True),
                             np.float64)
            k = self.num_model_per_iteration()
            score = raw if k == 1 else raw.reshape(-1, k, order="F")
            isc = md.init_score
            if isc is not None:
                # per-row init scores broadcast over classes; full-size
                # ones reshape column-major (same as GBDT.add_valid)
                score = score + (np.asarray(isc).reshape(score.shape,
                                                         order="F")
                                 if np.size(isc) == score.size
                                 else np.asarray(isc).reshape(-1, 1))
            out = []
            for m in ms:
                for mname, val in m.eval(score, obj):
                    out.append((name, mname, val, m.bigger_is_better))
            scores_for_feval = score
        if feval is not None:
            fevals = feval if isinstance(feval, (list, tuple)) else [feval]
            sc = np.asarray(scores_for_feval, np.float64)
            sc = sc[:, 0] if sc.ndim == 2 and sc.shape[1] == 1 else sc
            for f in fevals:
                res = f(sc, data)
                out.append((name,) + tuple(res))
        return out

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """reference Booster.get_split_value_histogram: histogram over
        the model's split thresholds of one feature (numerical splits;
        the reference excludes categorical too)."""
        fnames = self.feature_name()
        fidx = fnames.index(feature) if isinstance(feature, str) \
            else int(feature)
        values = []
        for t in self._get_trees():
            for i in range(max(t.num_leaves - 1, 0)):
                if int(t.split_feature[i]) == fidx and \
                        not (int(t.decision_type[i]) & 1):
                    values.append(float(t.threshold[i]))
        values = np.asarray(values, np.float64)
        if bins is None:
            bins = max(len(np.unique(values)), 1)
        hist, edges = np.histogram(values, bins=bins)
        if not xgboost_style:
            return hist, edges
        nz = hist > 0
        return np.column_stack([edges[1:][nz], hist[nz]])
