"""Python-facing Dataset and Booster.

TPU-native counterpart of the reference python package's basic.py
(/root/reference/python-package/lightgbm/basic.py:656 Dataset, :1578 Booster). The
reference bridges to C++ through ctypes; here the "engine" is the in-process
JAX/XLA core (models/gbdt.py), so these classes own parameter handling, lazy
construction, reference-binning for validation data, and the train/eval/predict/
save surface with the same names and semantics.
"""
from __future__ import annotations

import copy
import json
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .dataset import BinnedDataset, construct_dataset
from .metric import Metric, create_metric, default_metric_for_objective
from .models import gbdt as gbdt_mod
from .models.model_text import dump_model_to_json, load_model_from_string, save_model_to_string
from .objective import create_objective, objective_from_model_string
from .obs import trace as trace_mod
from .resil.atomic import atomic_write_text
from .utils import log
from .utils.vfile import vopen
from .utils.log import LightGBMError


def _data_from_pandas(data, feature_name="auto", categorical_feature="auto",
                      pandas_categorical=None):
    """DataFrame -> (float64 matrix, names, categorical cols, category lists).

    Reference semantics (python-package/lightgbm/basic.py:255-344), own shape:
    'category'-dtype columns are replaced by their integer codes (NaN for
    missing); the per-column category order is captured at train time and
    re-applied at predict time so codes stay aligned. Returns None when
    ``data`` is not a DataFrame.
    """
    if not (hasattr(data, "dtypes") and hasattr(data, "columns")):
        return None
    df = data
    names = (
        [str(c) for c in df.columns] if feature_name == "auto" else list(feature_name)
    )
    cat_cols = [c for c in df.columns if str(df[c].dtype) == "category"]
    if categorical_feature == "auto":
        categorical = [str(c) for c in cat_cols]
    else:
        categorical = list(categorical_feature)
    if pandas_categorical is None:  # training
        pandas_categorical = [list(df[c].cat.categories) for c in cat_cols]
    elif len(cat_cols) != len(pandas_categorical):  # prediction
        raise LightGBMError(
            "train and predict data have a different number of categorical columns"
        )
    out = np.empty(df.shape, np.float64)
    for j, c in enumerate(df.columns):
        col = df[c]
        if str(col.dtype) == "category":
            cats = pandas_categorical[cat_cols.index(c)]
            codes = col.cat.set_categories(cats).cat.codes.to_numpy().astype(np.float64)
            codes[codes < 0] = np.nan  # unseen category / NaN -> missing
            out[:, j] = codes
        else:
            try:
                out[:, j] = col.to_numpy(dtype=np.float64, na_value=np.nan)
            except (TypeError, ValueError):
                log.fatal(
                    "DataFrame.dtypes must be int, float, bool or category; "
                    "column %r is %s" % (str(c), col.dtype)
                )
    return out, names, categorical, pandas_categorical


def _to_2d_float(data, allow_sparse: bool = False) -> np.ndarray:
    if hasattr(data, "values"):  # pandas
        data = data.values
    if hasattr(data, "toarray"):  # scipy sparse
        if allow_sparse:
            # construct_dataset bins sparse inputs column-wise without
            # densifying (and may EFB-bundle them, efb.py)
            return data
        data = data.toarray()
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr.astype(np.float64, copy=False)


class Dataset:
    """Lazy binned dataset (basic.py:656 semantics: construct on first use)."""

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name: Union[str, List[str]] = "auto",
        categorical_feature: Union[str, List] = "auto",
        params: Optional[Dict] = None,
        free_raw_data: bool = False,
    ) -> None:
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        self.pandas_categorical = None  # per-column category order (DataFrames)

    # -- construction ----------------------------------------------------

    def _apply_metadata_overrides(self, md) -> None:
        """Honor user-supplied label/weight/init_score/group over file-borne
        metadata (Metadata::Init semantics, dataset.h:40-248)."""
        if self.label is not None:
            md.label = np.asarray(self.label, np.float32).reshape(-1)
        if self.weight is not None:
            md.weight = np.asarray(self.weight, np.float32).reshape(-1)
        if self.init_score is not None:
            md.init_score = np.asarray(self.init_score, np.float64)
            md._validate()  # size check (Metadata::SetInitScore)
        if self.group is not None:
            from .dataset import Metadata

            md.query_boundaries = Metadata(
                md.num_data, group=np.asarray(self.group)
            ).query_boundaries
        md._validate()

    def construct(self, config: Optional[Config] = None) -> "Dataset":
        if self._binned is not None:
            return self
        trace_mod.watch_compiles()
        with trace_mod.span("dataset.construct", cat="setup"):
            return self._construct(config)

    def _construct(self, config: Optional[Config]) -> "Dataset":
        if config is None:
            config = Config.from_params(self.params)
        if isinstance(self.data, str):
            # file path: binary fast path (LoadFromBinFile) or text load
            from .dataset import is_binary_dataset_file, load_binary_dataset

            if is_binary_dataset_file(self.data):
                self._binned = load_binary_dataset(self.data)
                self._apply_metadata_overrides(self._binned.metadata)
                if self.reference is not None:
                    # a binary file carries its own BinMappers; if they differ
                    # from the reference's, eval-from-bins would silently score
                    # against the wrong bin boundaries (the text path instead
                    # re-bins with the reference's mappers)
                    self.reference.construct(config)
                    ref = self.reference._binned
                    ours = [m.to_dict() for m in self._binned.mappers]
                    theirs = [m.to_dict() for m in ref.mappers]
                    if ours != theirs:
                        log.fatal(
                            "Binary dataset file %r was binned with different "
                            "BinMappers than its reference dataset; re-save it "
                            "with reference= set, or pass the raw data instead"
                            % (self.data,)
                        )
                self._config = config
                return self
            if config.two_round and self.reference is None:
                # low-memory streaming load: the full float matrix never
                # materializes (dataset_loader.cpp two_round branch)
                from .dist_loader import apply_sidecars, load_two_round

                names = (
                    list(self.feature_name)
                    if isinstance(self.feature_name, (list, tuple))
                    else None
                )
                cats = (
                    self.categorical_feature
                    if self.categorical_feature not in (None, "auto")
                    else None
                )
                binned, row_idx = load_two_round(
                    self.data, config,
                    feature_names=names, categorical_feature=cats,
                )
                apply_sidecars(binned, self.data, row_idx)
                self._apply_metadata_overrides(binned.metadata)
                if self._predictor is not None:
                    # continued training: stream-predict init scores so the
                    # raw matrix still never materializes whole
                    binned.metadata.init_score = self._predictor_file_scores(
                        self.data, config, binned.num_total_features
                    )
                self._binned = binned
                self._config = config
                return self
            from .io import load_sidecar, load_text_file

            X, y, names = load_text_file(
                self.data, has_header=config.header, label_column=config.label_column
            )
            if self.label is None and y is not None:
                self.label = y
            if self.weight is None:
                self.weight = load_sidecar(self.data, "weight")
            if self.group is None:
                g = load_sidecar(self.data, "query")
                self.group = None if g is None else g.astype(np.int64)
            if self.init_score is None:
                self.init_score = load_sidecar(self.data, "init")
            if names and self.feature_name == "auto":
                self.feature_name = names
            self.data = X
        feature_names = None
        cats = None
        if self.reference is not None and self.pandas_categorical is None:
            # validation data re-uses the training set's category order
            self.reference.construct(config)
            self.pandas_categorical = self.reference.pandas_categorical
        from_pandas = _data_from_pandas(
            self.data, self.feature_name, self.categorical_feature,
            self.pandas_categorical,
        )
        if from_pandas is not None:
            data, feature_names, cats, self.pandas_categorical = from_pandas
        else:
            with trace_mod.span("dataset.to_float", cat="setup"):
                data = _to_2d_float(self.data, allow_sparse=True)
            if isinstance(self.feature_name, (list, tuple)):
                feature_names = list(self.feature_name)
            if isinstance(self.categorical_feature, (list, tuple)):
                cats = list(self.categorical_feature)
            elif self.categorical_feature not in (None, "auto"):
                # comma-joined / "name:col" string spec (_parse_categorical
                # resolves names against the file header's feature_names)
                cats = self.categorical_feature
        ref_binned = None
        if self.reference is not None:
            self.reference.construct(config)
            ref_binned = self.reference._binned
        init_score = self.init_score
        if self._predictor is not None:
            # continued training: init score = predictor's raw output on this data
            init_score = self._predictor_raw_scores(data)
        self._binned = construct_dataset(
            data,
            config,
            label=np.asarray(self.label, np.float64) if self.label is not None else None,
            weight=np.asarray(self.weight, np.float64) if self.weight is not None else None,
            group=np.asarray(self.group) if self.group is not None else None,
            init_score=init_score,
            feature_names=feature_names,
            categorical_feature=cats,
            reference=ref_binned,
        )
        self._config = config
        if self.free_raw_data:
            self.data = None
        return self

    def _predictor_file_scores(
        self, path: str, config, num_features: int
    ) -> np.ndarray:
        """Init scores from the predictor, streamed chunk-wise over the file
        (the two-round analogue of _predictor_raw_scores: bounded memory)."""
        from .dist_loader import iter_text_chunks

        parts = []
        for X, _, _ in iter_text_chunks(
            path,
            has_header=config.header,
            label_column=config.label_column,
            num_features=num_features,
        ):
            if X.shape[1] < num_features:
                X = np.pad(X, ((0, 0), (0, num_features - X.shape[1])))
            # per-row accumulation is row-independent, so the chunked f32
            # replay concatenates to exactly the whole-matrix replay
            ws = self._predictor.warmstart_scores(X)
            if ws is not None:
                parts.append(
                    (ws if ws.shape[0] > 1 else ws[0]).astype(np.float64)
                )
            else:
                raw = self._predictor.predict_raw(X)
                parts.append(raw.T if raw.ndim == 2 else raw)
        scores = np.concatenate(parts, axis=-1)
        if scores.ndim == 2:
            return scores.reshape(-1)  # class-major flatten
        return scores

    def _predictor_raw_scores(self, data: np.ndarray) -> np.ndarray:
        if hasattr(data, "toarray"):  # continued training on sparse input
            data = data.toarray()
        ws = self._predictor.warmstart_scores(data)
        if ws is not None:
            # per-tree f32 replay (models/gbdt.py warmstart_scores): these
            # f64 values are EXACT f32s, so the trainer's f32 init-score
            # cast recovers the parent run's score carry bit for bit — the
            # warm-start bedrock continued training rests on
            K = ws.shape[0]
            return (ws.reshape(-1) if K > 1 else ws[0]).astype(np.float64)
        raw = self._predictor.predict_raw(data)
        if raw.ndim == 2:
            return raw.T.reshape(-1)  # class-major flatten
        return raw

    def set_predictor(self, booster: Optional["Booster"]) -> None:
        self._predictor = booster._gbdt if booster is not None else None
        if self._predictor is not None and self._binned is not None:
            # dataset was constructed before the predictor was attached
            # (continued-training path): compute init scores now
            if self.data is None:
                log.fatal(
                    "Cannot set an init-score predictor on an already-constructed "
                    "Dataset whose raw data was freed"
                )
            init = self._predictor_raw_scores(_to_2d_float(self.data))
            self._binned.metadata.init_score = np.asarray(init, np.float64)

    # -- setters (basic.py Dataset API) -----------------------------------

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._binned is not None:
            self._binned.metadata.label = np.asarray(label, np.float32).reshape(-1)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._binned is not None and weight is not None:
            self._binned.metadata.weight = np.asarray(weight, np.float32).reshape(-1)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._binned is not None and init_score is not None:
            md = self._binned.metadata
            md.init_score = np.asarray(init_score, np.float64)
            md._validate()  # size check (Metadata::SetInitScore)
        return self

    def get_label(self):
        if self._binned is not None:
            return self._binned.metadata.label
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def set_field(self, field_name: str, data) -> "Dataset":
        """Generic field setter (basic.py:1114 Dataset.set_field /
        LGBM_DatasetSetField name dispatch)."""
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        if field_name in ("group", "query"):
            return self.set_group(data)
        raise LightGBMError("Unknown field name: %s" % field_name)

    def get_field(self, field_name: str):
        """Generic field getter (basic.py:1162 Dataset.get_field)."""
        if field_name == "label":
            return self.get_label()
        if field_name == "weight":
            return self.get_weight()
        if field_name == "init_score":
            return self.get_init_score()
        if field_name in ("group", "query"):
            return self.get_group()
        raise LightGBMError("Unknown field name: %s" % field_name)

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Re-declare categorical columns (basic.py:1201); a no-op when
        unchanged. After construction the binned matrix fixed each column's
        bin type — with raw data retained the dataset re-bins on next
        construct (the reference's set_categorical_feature path), without it
        the change is impossible."""
        if self.categorical_feature == categorical_feature:
            return self
        if self._binned is not None:
            if self.data is None or isinstance(self.data, str):
                raise LightGBMError(
                    "Cannot set categorical feature after freed raw data, set "
                    "free_raw_data=False when construct Dataset to avoid this."
                )
            # raw rows retained: drop the binned form and re-bin lazily
            self._binned = None
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        """Set feature names (basic.py:1273); validates before mutating."""
        if self._binned is not None and isinstance(feature_name, (list, tuple)):
            if len(feature_name) != self._binned.num_total_features:
                raise LightGBMError(
                    "Length of feature_name(%d) and num_feature(%d) don't match"
                    % (len(feature_name), self._binned.num_total_features)
                )
            self._binned.feature_names = list(feature_name)
        self.feature_name = feature_name
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Re-point this dataset at another training set's binning
        (basic.py:1247). After construction, retained raw data re-bins with
        the new reference's mappers on next use; without raw data the change
        is impossible."""
        if self.reference is reference:
            return self
        if self._binned is not None:
            if self.data is None or isinstance(self.data, str):
                raise LightGBMError(
                    "Cannot set reference after freed raw data, set "
                    "free_raw_data=False when construct Dataset to avoid this."
                )
            self._binned = None
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100) -> set:
        """The set of Datasets reachable through .reference links
        (basic.py:1507)."""
        head = self
        ref_chain: set = set()
        while len(ref_chain) < ref_limit:
            if isinstance(head, Dataset):
                ref_chain.add(head)
                if head.reference is not None:
                    head = head.reference
                else:
                    break
            else:
                break
        return ref_chain

    def get_data(self):
        """Raw data as passed in (post-subset slicing, basic.py:1437)."""
        if self.reference is not None and self.used_indices is not None:
            ref_data = self.reference.get_data()
            if ref_data is None or isinstance(ref_data, str):
                # a path string means the reference was never constructed (or
                # is a binary dataset file, which keeps no raw rows). Don't
                # construct here: a read accessor must not pin the reference's
                # binning with its own params, nor pay a full load just to
                # find there are no rows. Construct the reference first if
                # its loaded rows are wanted.
                return None
            idx = np.asarray(self.used_indices)
            if hasattr(ref_data, "iloc"):  # pandas: positional ROW selection
                return ref_data.iloc[idx]
            return ref_data[idx]
        return self.data

    def get_feature_penalty(self):
        """Per-feature penalty array, or None when unset (basic.py:1401)."""
        cfg = getattr(self, "_config", None) or Config.from_params(self.params)
        if cfg.feature_contri:
            return np.asarray(cfg.feature_contri, np.float64)
        return None

    def get_monotone_constraints(self):
        """Per-feature monotone constraint array, or None (basic.py:1413)."""
        if self._binned is not None and self._binned.monotone_constraints:
            return np.asarray(self._binned.monotone_constraints, np.int32)
        cfg = getattr(self, "_config", None) or Config.from_params(self.params)
        if cfg.monotone_constraints:
            return np.asarray(cfg.monotone_constraints, np.int32)
        return None

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Column-concatenate another constructed Dataset into this one
        (basic.py:1537 Dataset.add_features_from / Dataset::AddFeaturesFrom).

        Both datasets must be constructed, un-bundled (EFB off), and have the
        same row count; the other's binned columns, mappers, and names are
        appended in place. The other dataset keeps ownership of its raw data.
        """
        if self._binned is None or other._binned is None:
            raise LightGBMError("Both source and target Datasets must be constructed before adding features")
        a, b = self._binned, other._binned
        if a.num_data != b.num_data:
            raise LightGBMError(
                "Cannot add features from other Dataset with a different number of rows (%d vs %d)"
                % (b.num_data, a.num_data)
            )
        if a.is_bundled or b.is_bundled:
            raise LightGBMError(
                "Cannot add features to/from an EFB-bundled Dataset (disable "
                "enable_bundle to use add_features_from)"
            )
        if a.bins.dtype != b.bins.dtype:
            wide = np.promote_types(a.bins.dtype, b.bins.dtype)
            a.bins = a.bins.astype(wide)
            b_bins = b.bins.astype(wide)
        else:
            b_bins = b.bins
        off = a.num_total_features
        a.bins = np.concatenate([a.bins, b_bins], axis=0)
        a.mappers = list(a.mappers) + list(b.mappers)
        a.used_feature_idx = list(a.used_feature_idx) + [
            off + j for j in b.used_feature_idx
        ]
        a.num_total_features += b.num_total_features
        # de-collide names the way the reference's Merge does (suffix)
        seen = set(a.feature_names)
        merged = []
        for name in b.feature_names:
            new = name
            while new in seen:
                new = new + "_1"
            seen.add(new)
            merged.append(new)
        a.feature_names = list(a.feature_names) + merged
        if a.monotone_constraints or b.monotone_constraints:
            a.monotone_constraints = (
                list(a.monotone_constraints or [0] * off)
                + list(b.monotone_constraints or [0] * b.num_total_features)
            )
        return self

    def dump_text(self, filename: str) -> "Dataset":
        """Write the raw (unbinned) rows as text — debugging aid
        (basic.py:1557 Dataset.dump_text)."""
        if self.used_indices is None:
            # subsets carry data=None and slice rows via get_data(); plain
            # datasets construct first so file-backed data is loaded
            self.construct()
        data = self.get_data()
        if data is None or isinstance(data, str):
            # text-file datasets replace .data with the loaded matrix at
            # construct(); a remaining string means a binary dataset file,
            # which keeps no raw rows
            raise LightGBMError(
                "Cannot dump_text: the Dataset keeps no raw rows "
                "(freed, or loaded from a binary dataset file)"
            )
        arr = _to_2d_float(data)
        if hasattr(arr, "toarray"):
            arr = arr.toarray()
        with vopen(filename, "w") as fh:
            for row in np.asarray(arr, np.float64):
                fh.write(",".join("%.17g" % v for v in row) + "\n")
        return self

    def save_binary(self, filename: str) -> "Dataset":
        """Save the constructed (binned) dataset for fast reload
        (Dataset.save_binary, basic.py:1517; LGBM_DatasetSaveBinary)."""
        from .dataset import save_binary_dataset

        self.construct()
        save_binary_dataset(self._binned, filename)
        return self

    def num_data(self) -> int:
        if self._binned is not None:
            return self._binned.num_data
        if isinstance(self.data, str):
            self.construct()
            return self._binned.num_data
        return _to_2d_float(self.data, allow_sparse=True).shape[0]

    def num_feature(self) -> int:
        if self._binned is not None:
            return self._binned.num_total_features
        if isinstance(self.data, str):
            self.construct()
            return self._binned.num_total_features
        return _to_2d_float(self.data, allow_sparse=True).shape[1]

    def subset(self, used_indices, params=None) -> "Dataset":
        used_indices = np.asarray(used_indices)
        sub = Dataset(
            data=None,
            label=None,
            reference=self,
            params=params or self.params,
        )
        sub.used_indices = used_indices
        return sub

    def create_valid(self, data, label=None, weight=None, group=None, init_score=None, params=None) -> "Dataset":
        return Dataset(
            data,
            label=label,
            reference=self,
            weight=weight,
            group=group,
            init_score=init_score,
            params=params or self.params,
        )

    def construct_subset(self, config: Config) -> BinnedDataset:
        """Materialize a row-subset BinnedDataset (Dataset::CopySubset path)."""
        assert self.reference is not None and self.used_indices is not None
        self.reference.construct(config)
        parent = self.reference._binned
        from .dataset import Metadata

        idx = self.used_indices
        init_sub = None
        if parent.metadata.init_score is not None:
            isc = np.asarray(parent.metadata.init_score).reshape(-1)
            if len(isc) == parent.num_data:
                init_sub = isc[idx]
            else:
                K = len(isc) // parent.num_data
                init_sub = isc.reshape(K, parent.num_data)[:, idx].reshape(-1)
        md = Metadata(
            len(idx),
            label=None if parent.metadata.label is None else parent.metadata.label[idx],
            weight=None if parent.metadata.weight is None else parent.metadata.weight[idx],
            group=None,
            init_score=init_sub,
        )
        # group subsetting: rebuild boundaries from parent's query assignment
        if parent.metadata.query_boundaries is not None:
            qb = parent.metadata.query_boundaries
            qid = np.searchsorted(qb, idx, side="right") - 1
            # indices must be query-contiguous for ranking subsets
            sizes = np.diff(np.concatenate([[0], np.nonzero(np.diff(qid))[0] + 1, [len(qid)]]))
            md.query_boundaries = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        binned = BinnedDataset(
            parent.bins[:, idx],
            parent.mappers,
            parent.used_feature_idx,
            parent.num_total_features,
            md,
            feature_names=parent.feature_names,
            monotone_constraints=parent.monotone_constraints,
            group_id=parent.group_id,
            bin_offset=parent.bin_offset,
            max_group_bins=parent._max_group_bins,
        )
        return binned

    def get_binned(self, config: Config) -> BinnedDataset:
        if self.used_indices is not None:
            return self.construct_subset(config)
        self.construct(config)
        return self._binned


class Booster:
    """Training/prediction handle (basic.py:1578 Booster semantics)."""

    def __init__(
        self,
        params: Optional[Dict] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
        silent: bool = False,
    ) -> None:
        params = dict(params) if params else {}
        self.params = params
        self.train_set = train_set
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._valid_names: List[str] = []
        self._valid_datasets: List[Dataset] = []
        self._valid_slots: List[int] = []  # GBDT valid-list index per dataset
        self.pandas_categorical = None
        self._attrs: Dict[str, str] = {}
        self._train_data_name = "training"
        self._network_initialized = False
        if train_set is not None:
            self.config = Config.from_params(params)
            binned = train_set.get_binned(self.config)
            objective = create_objective(self.config)
            metrics = self._make_metrics(self.config)
            boosting = self.config.boosting
            cls = _boosting_class(boosting)
            self._gbdt = cls(self.config, binned, objective, metrics)
            self._train_dataset = train_set
            self.pandas_categorical = train_set.pandas_categorical
        elif model_file is not None:
            with vopen(model_file) as fh:
                self._load(fh.read(), params)
        elif model_str is not None:
            self._load(model_str, params)
        else:
            raise LightGBMError("Booster needs train_set, model_file or model_str")

    def _load(self, text: str, params: Dict) -> None:
        self.config = Config.from_params(params) if params else Config()
        # trailing pandas_categorical:<json> line (same tail format as the
        # reference python package writes after the model text)
        marker = "\npandas_categorical:"
        pos = text.rfind(marker)
        if pos >= 0:
            import json as _json

            line_end = text.find("\n", pos + 1)
            payload = text[pos + len(marker): line_end if line_end > 0 else None]
            try:
                self.pandas_categorical = _json.loads(payload)
            except ValueError:
                raise LightGBMError(
                    "Model file has a corrupt pandas_categorical record: %r"
                    % payload[:80]
                )
            text = text[:pos] + (text[line_end:] if line_end > 0 else "")
        self._gbdt = load_model_from_string(text, gbdt_mod.GBDT, self.config)
        obj = objective_from_model_string(getattr(self._gbdt, "loaded_objective", None), self.config)
        self._gbdt.objective = obj
        self._train_dataset = None

    def _make_metrics(self, config: Config) -> List[Metric]:
        names = config.metric if config.metric else [default_metric_for_objective(config.objective)]
        out = []
        for n in names:
            if n in ("", "None", "na", "null", "custom"):
                continue
            m = create_metric(n, config)
            if m is not None:
                out.append(m)
        return out

    # -- training --------------------------------------------------------

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        binned = data.get_binned(self.config)
        metrics = self._make_metrics(self.config)
        def raw_provider():
            raw = data.get_data()
            if isinstance(raw, str) or raw is None:
                return None  # binary-file datasets keep no raw rows
            from_pandas = _data_from_pandas(
                raw, pandas_categorical=self.pandas_categorical or []
            )
            return from_pandas[0] if from_pandas is not None else _to_2d_float(raw)

        self._gbdt.add_valid(binned, metrics, name, raw_data=raw_provider)
        self._valid_names.append(name)
        self._valid_datasets.append(data)
        self._valid_slots.append(len(self._gbdt.valid_names) - 1)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if stopped (can't split).

        Stop reporting runs one call behind the reference (gbdt.cpp:402):
        to keep the training loop free of per-iteration device syncs, the
        no-split check is deferred — the splitless iteration itself returns
        False and the True arrives on the NEXT update() call (which trains
        nothing and rolls the placeholder back). Final model state is
        identical to the reference's; only callers branching on the return
        value see the one-call lag.
        """
        if fobj is None:
            return self._gbdt.train_one_iter()
        K = self._gbdt.num_tree_per_iteration
        score = self._gbdt._train_score_np()
        grad, hess = fobj(_score_for_custom(score, K), self._train_dataset)
        return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))

    def update_chunk(self, n: int, sync_stop: bool = False):
        """Up to ``n`` boosting iterations as ONE device-resident dispatch
        (GBDT.train_chunk — the jitted lax.scan boosting loop); returns
        (iterations_run, stopped). Falls back to a single update() when
        chunking cannot engage (device_chunk_fallback_reason), so callers
        may loop on it unconditionally — except custom-gradient training
        (objective "none"), which must call update(fobj) per iteration, as
        there is no gradient source here. ``sync_stop=True`` resolves the
        deferred no-split check before returning (set it when evaluation
        follows at this boundary)."""
        if n <= 1 or self._gbdt.device_chunk_fallback_reason() is not None:
            return 1, self.update()
        return self._gbdt.train_chunk(n, sync_stop=sync_stop)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self) -> int:
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return self._gbdt.num_trees()

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def sample_draws(self) -> List[Dict]:
        """The row draws of this booster's training (GOSS, bagging, rf), one
        dict a drawn iteration: ``iteration``, ``in_bag`` and ``amplified``
        ([N] bool) and ``multiplier`` (``GBDT.sample_draws``; a bounded record,
        oldest first). Empty where no rows were drawn."""
        return self._gbdt.sample_draws()

    def feature_draws(self) -> List[Dict]:
        """The column draws of this booster's training (``feature_fraction``
        < 1), one dict a drawn tree: ``tree``, ``iteration`` and ``columns``
        (int32[k]: the ``max(1, int(feature_fraction x F))`` drawn columns of
        the table, in rising order; ``GBDT.feature_draws``, a bounded record,
        oldest first). The tree was grown over these columns alone and names
        no other. Empty where no tree draws."""
        return self._gbdt.feature_draws()

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx + 1

    # -- evaluation ------------------------------------------------------

    def eval_train(self, feval=None) -> List:
        return self._eval_set(
            self._gbdt._train_score_np(), self._train_data_name,
            self._gbdt.training_metrics, feval, self._train_dataset,
        )

    def eval_valid(self, feval=None) -> List:
        # slot -> Dataset through the explicit map (the python-side lists can
        # be shorter than the GBDT's after free_dataset; see eval())
        slot_ds = dict(zip(self._valid_slots, self._valid_datasets))
        out = []
        for i, name in enumerate(self._gbdt.valid_names):
            out.extend(
                self._eval_set(
                    self._gbdt._valid_score_np(i), name,
                    self._gbdt.valid_metrics[i], feval, slot_ds.get(i),
                )
            )
        return out

    def _eval_set(self, score, name, metrics, feval, dataset) -> List:
        results = []
        for m in metrics:
            for mname, val, bigger in m.eval(score, self._gbdt.objective):
                results.append((name, mname, val, bigger))
        if feval is not None:
            preds = score if self._gbdt.objective is None else self._gbdt.objective.convert_output(score)
            ret = feval(preds, dataset)
            if ret is not None:
                if isinstance(ret, list):
                    for (mname, val, bigger) in ret:
                        results.append((name, mname, val, bigger))
                else:
                    mname, val, bigger = ret
                    results.append((name, mname, val, bigger))
        return results

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        """Evaluate on an arbitrary Dataset (basic.py Booster.eval): reuses
        the valid-set slot when ``data`` was added with add_valid, else adds
        it first like the reference does. ``_valid_slots`` maps each tracked
        Dataset to its slot in the GBDT's valid lists — the two sides can
        diverge after free_dataset()/model_from_string()."""
        if data is self._train_dataset:
            return self.eval_train(feval)
        for pos, ds in enumerate(self._valid_datasets):
            if ds is data:
                i = self._valid_slots[pos]
                return self._eval_set(
                    self._gbdt._valid_score_np(i), name,
                    self._gbdt.valid_metrics[i], feval, ds,
                )
        self.add_valid(data, name)
        i = self._valid_slots[-1]
        return self._eval_set(
            self._gbdt._valid_score_np(i), name, self._gbdt.valid_metrics[i],
            feval, data,
        )

    # -- attributes / bookkeeping (basic.py Booster.attr/set_attr) -------

    def attr(self, key: str):
        """Free-form string attribute, or None when unset."""
        return self._attrs.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set (string) or delete (None) free-form attributes."""
        for key, value in kwargs.items():
            if value is None:
                self._attrs.pop(key, None)
            elif isinstance(value, str):
                self._attrs[key] = value
            else:
                raise LightGBMError("Only string values are accepted")
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Rename the training set in eval output (default 'training')."""
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """Drop the python-side training/validation Dataset references
        (basic.py Booster.free_dataset), letting their raw matrices be
        collected. The trained model remains fully usable — predict, save,
        and even update() keep working, since the GBDT core holds its own
        device-resident binned data (the reference's C++ booster likewise
        keeps its Dataset)."""
        self._train_dataset = None
        self.train_set = None
        self._valid_datasets = []
        self._valid_slots = []
        self._valid_names = []
        return self

    def free_network(self) -> "Booster":
        """Reference parity no-op: collectives live inside the jitted
        programs (psum over the mesh), there is no standing network to tear
        down (network.h:89 Network::Dispose)."""
        self._network_initialized = False
        return self

    def set_network(self, machines=None, local_listen_port: int = 12400,
                    listen_time_out: int = 120, num_machines: int = 1) -> "Booster":
        """Reference parity shim (basic.py Booster.set_network): multi-host
        topology comes from the JAX distributed runtime (jax.distributed /
        the mesh), not from a machine list; recorded for introspection."""
        self._network_initialized = num_machines > 1
        return self

    def shuffle_models(self, start_iteration: int = 0, end_iteration: int = -1) -> "Booster":
        """Shuffle tree order in [start, end) (basic.py Booster.shuffle_models
        / GBDT::ShuffleModels — used to decorrelate for continued training)."""
        self._gbdt.shuffle_models(start_iteration, end_iteration)
        return self

    def model_from_string(self, model_str: str, verbose: bool = True) -> "Booster":
        """Replace this booster's model with one parsed from a model string."""
        self._load(model_str, self.params)
        # the fresh GBDT has no valid lists; drop stale python-side tracking
        self._train_dataset = None
        self.train_set = None
        self._valid_datasets = []
        self._valid_slots = []
        self._valid_names = []
        if verbose:
            log.info(
                "Finished loading model, total used %d iterations"
                % self._gbdt.current_iteration
            )
        return self

    def get_split_value_histogram(self, feature, bins=None) -> np.ndarray:
        """Histogram of split thresholds used for ``feature`` across the model
        (basic.py Booster.get_split_value_histogram).

        ``feature``: index or name. Returns (counts, bin_edges) like
        numpy.histogram; ``bins`` defaults to numpy's 'auto'.
        """
        if isinstance(feature, str):
            names = self.feature_name()
            if feature not in names:
                raise LightGBMError("Unknown feature name: %s" % feature)
            feature = names.index(feature)
        values = []
        for tree in self._gbdt.trees():
            for node in range(max(tree.num_leaves - 1, 0)):
                if int(tree.split_feature[node]) == feature and not tree._is_categorical(node):
                    values.append(float(tree.threshold[node]))
        if bins is None:
            bins = "auto"
        return np.histogram(np.asarray(values, np.float64), bins=bins)

    # -- prediction ------------------------------------------------------

    def predict(
        self,
        data,
        num_iteration: int = -1,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        **kwargs,
    ) -> np.ndarray:
        if isinstance(data, np.ndarray) and data.ndim == 1:
            # a bare feature vector is ambiguous (1 row? 1 feature?); the
            # reference python package rejects it with this message
            raise LightGBMError("Input numpy.ndarray must be 2 dimensional")
        from_pandas = _data_from_pandas(
            data, pandas_categorical=self.pandas_categorical or []
        )
        X = from_pandas[0] if from_pandas is not None else _to_2d_float(data)
        n_model = self.num_feature()
        if X.shape[1] != n_model:
            # Predictor::Predict's guard (the reference fatals with the same
            # sentence; silent broadcasting would score garbage)
            raise LightGBMError(
                "The number of features in data (%d) is not the same as it "
                "was in training data (%d)" % (X.shape[1], n_model)
            )
        if pred_leaf:
            return self._gbdt.predict_leaf_index(X, num_iteration)
        if pred_contrib:
            return self._gbdt.predict_contrib(X, num_iteration)
        early_stop = None
        pred_early_stop = kwargs.get("pred_early_stop", self.config.pred_early_stop)
        obj_name = self._gbdt.objective.name if self._gbdt.objective is not None else ""
        if pred_early_stop and obj_name in ("binary", "multiclass", "multiclassova", "cross_entropy"):
            from .prediction_early_stop import create_prediction_early_stop_instance

            es_type = "multiclass" if self._gbdt.num_tree_per_iteration > 1 else "binary"
            early_stop = create_prediction_early_stop_instance(
                es_type,
                int(kwargs.get("pred_early_stop_freq", self.config.pred_early_stop_freq)),
                float(kwargs.get("pred_early_stop_margin", self.config.pred_early_stop_margin)),
            )
        return self._gbdt.predict(X, num_iteration, raw_score=raw_score, early_stop=early_stop)

    # -- model IO --------------------------------------------------------

    def save_model(self, filename: str, num_iteration: int = -1, start_iteration: int = 0) -> "Booster":
        # atomic publish (resil/atomic.py): a crash mid-save leaves either
        # the previous complete model file or the new one, never a prefix
        atomic_write_text(
            filename, self.model_to_string(num_iteration, start_iteration)
        )
        import os as _os

        if _os.environ.get("LIGHTGBM_TPU_DRIFT_SIDECAR", "") not in ("", "0"):
            # drift reference sidecar (<filename>.drift.json): the training
            # set's bin occupancy mapped through the model lattice, for the
            # serve-time drift monitor (serve/drift.py; docs/Serving.md).
            # Env-gated + full-model only: a clipped save's lattice (or a
            # start_iteration-shifted one) would not match what the sidecar
            # fingerprints — serving would refuse it with a misleading
            # "different model" warning.
            if (num_iteration is not None and num_iteration > 0) or (
                start_iteration or 0
            ) > 0:
                log.warning(
                    "drift: sidecar skipped for %r (iteration-clipped "
                    "save; use save_drift_reference on the full model)"
                    % filename
                )
            else:
                self.save_drift_reference(filename)
        return self

    def save_drift_reference(self, model_filename: str) -> Optional[str]:
        """Write ``<model_filename>.drift.json`` — the training-distribution
        reference the serve-time drift monitor scores live traffic against
        (serve/drift.py). Needs the live training set (call before
        free_dataset); returns the sidecar path, or None when no reference
        could be built. ``save_model`` emits it automatically under
        ``LIGHTGBM_TPU_DRIFT_SIDECAR=1``."""
        from .serve.drift import write_sidecar

        return write_sidecar(model_filename, self)

    def model_to_string(self, num_iteration: int = -1, start_iteration: int = 0) -> str:
        s = save_model_to_string(self._gbdt, start_iteration, num_iteration)
        import json as _json

        try:
            tail = _json.dumps(self.pandas_categorical)
        except TypeError:
            # fail loudly, like the reference: a silently stringified category
            # (e.g. a Timestamp) would map every value to missing after reload
            raise LightGBMError(
                "pandas categorical columns must hold JSON-serializable "
                "categories (str/int/float/bool) to save the model"
            )
        return s + "\npandas_categorical:%s\n" % tail

    def dump_model(self, num_iteration: int = -1) -> dict:
        return dump_model_to_json(self._gbdt, num_iteration)

    def feature_importance(self, importance_type: str = "split", iteration: int = -1) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type, iteration)

    def feature_name(self) -> List[str]:
        ds = self._gbdt.train_set
        if ds is not None:
            return ds.feature_names
        return getattr(self._gbdt, "feature_names", [])

    def reset_parameter(self, params: Dict) -> "Booster":
        self.params.update(params)
        self._gbdt.reset_parameter(params)
        return self

    def refit(self, data, label, decay_rate: float = 0.9, **kwargs) -> "Booster":
        """Refit the existing Booster on new data (basic.py:2290-2332):
        keep every tree's structure, recompute leaf values from the new data's
        gradients, blended ``decay_rate*old + (1-decay_rate)*new``."""
        if self._gbdt.objective is None:
            raise LightGBMError("Cannot refit due to null objective function.")
        leaf_preds = self.predict(data, num_iteration=-1, pred_leaf=True, **kwargs)
        # carry the model's objective (with its params) and class count so a
        # loaded model refits under its own config — the reference aborts via
        # CHECK(num_tree_per_iteration == NumModelPerIteration) when these
        # drift (gbdt.cpp ResetTrainingData); here they are inherited instead.
        params = dict(self.params)
        obj_str = self._gbdt.objective.to_string()
        tokens = obj_str.split()
        params.setdefault("objective", tokens[0])
        for tok in tokens[1:]:
            if ":" in tok:
                k, v = tok.split(":", 1)
                params.setdefault(k, v)
            elif tok == "sqrt":
                params.setdefault("reg_sqrt", True)
        params.setdefault("num_class", self._gbdt.num_class)
        train_set = Dataset(data, label=label, params=params)
        new_booster = Booster(params, train_set)
        if (
            new_booster._gbdt.num_tree_per_iteration
            != self._gbdt.num_tree_per_iteration
        ):
            raise LightGBMError(
                "Cannot refit: the new objective trains %d models per iteration "
                "but the loaded model has %d"
                % (
                    new_booster._gbdt.num_tree_per_iteration,
                    self._gbdt.num_tree_per_iteration,
                )
            )
        new_booster._gbdt.merge_models_from(self._gbdt)
        new_booster._gbdt.refit(np.asarray(leaf_preds), decay_rate)
        return new_booster

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Output of one leaf (LGBM_BoosterGetLeafValue, c_api.h)."""
        return float(self._gbdt.trees()[tree_id].leaf_value[leaf_id])

    def to_packed(self, num_iteration: int = -1):
        """Compile this model into a :class:`~lightgbm_tpu.serve.PackedEnsemble`
        for device-resident batch inference (serve/packed.py): one vmapped
        dispatch per request batch instead of a host walk per tree. The exact
        path of the returned object reproduces ``predict`` bit for bit; see
        docs/Serving.md."""
        from .serve.packed import pack_booster

        return pack_booster(self, num_iteration=num_iteration)

    def __getstate__(self):
        return {"model_str": self.model_to_string(), "params": self.params}

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = -1
        self.best_score = {}
        self._valid_names = []
        self.train_set = None
        self._load(state["model_str"], state["params"])

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(model_str=self.model_to_string(), params=self.params)


def _score_for_custom(score: np.ndarray, K: int) -> np.ndarray:
    """Custom-fobj score layout: [N] or flattened class-major [K*N] (engine.py)."""
    if K == 1:
        return score
    return score.reshape(-1)


def _boosting_class(name: str):
    from .models.gbdt import GBDT

    if name == "gbdt":
        return GBDT
    if name == "dart":
        from .models.dart import DART

        return DART
    if name == "goss":
        from .models.goss import GOSS

        return GOSS
    if name == "rf":
        from .models.rf import RandomForest

        return RandomForest
    log.fatal("Unknown boosting type %s" % name)
