"""User-facing ``Dataset`` and ``Booster``.

Mirrors the reference python package's basic.py (python-package/lightgbm/
basic.py:930 ``Dataset``, basic.py:1276 ``Booster``) — same lazy-construction
semantics, same method surface — but with no FFI: the "C API layer" the
reference reaches through ctypes (src/c_api.cpp) is here the in-process
TPU framework itself (BinnedDataset + GBDT/DART on JAX).
"""

from __future__ import annotations

import copy
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config, key_alias_transform
from .io.dataset import (
    BinnedDataset, _merge_api_categoricals, _resolve_column_list)
from .io.metadata import Metadata
from .metrics import Metric, create_metrics
from .models.dart import create_boosting
from .models.gbdt import GBDT
from .objectives import create_objective
from .obs import telemetry


class LightGBMError(Exception):
    """Error raised by the framework (reference basic.py:45)."""


def _to_2d_float(data, dtype=np.float64) -> np.ndarray:
    arr = np.asarray(data, dtype=dtype)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        raise LightGBMError("data must be 2 dimensional")
    return arr


def _densify(data, dtype=np.float64) -> np.ndarray:
    """Accept numpy / pandas / scipy-sparse row data (basic.py:472-927).
    ``dtype=None`` keeps the data's own: ``Dataset.construct`` leaves the
    one conversion to the binner, which makes it under a span."""
    if hasattr(data, "toarray"):  # scipy CSR/CSC
        return _to_2d_float(data.toarray(), dtype)
    if hasattr(data, "values") and not isinstance(data, np.ndarray):  # pandas
        return _to_2d_float(data.values, dtype)
    return _to_2d_float(data, dtype)


class Dataset:
    """Dataset for training/validation.

    Like the reference ``Dataset`` (basic.py:930-1274): parameters
    (max_bin, categorical_feature, reference, ...) are collected eagerly
    but binning happens lazily on first use, so a validation set can be
    aligned to its training set's bin mappers.
    """

    def __init__(
        self,
        data,
        label=None,
        max_bin: int = 256,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name: Optional[List[str]] = None,
        categorical_feature: Optional[Sequence[int]] = None,
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = False,
    ):
        self.data = data
        self.label = label
        self.max_bin = int(max_bin)
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = list(categorical_feature or [])
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._inner: Optional[BinnedDataset] = None

    # ------------------------------------------------------------ construct
    def construct(self) -> BinnedDataset:
        """Build the binned dataset lazily (basic.py:1014-1036).  The
        first call is the span ``lgbm.setup.ingest`` (host wall time;
        a reference set is built before it, under its own)."""
        if self._inner is None:
            ref_inner = (self.reference.construct()
                         if self.reference is not None else None)
            with telemetry.span("lgbm.setup.ingest"):
                self._inner = self._ingest(ref_inner)
            if self.free_raw_data:
                self.data = None
        return self._inner

    def _ingest(self, ref_inner: Optional[BinnedDataset]) -> BinnedDataset:
        params = key_alias_transform(dict(self.params))
        params.setdefault("max_bin", self.max_bin)
        cfg = Config.from_dict(params)
        cats = self.categorical_feature
        if any(isinstance(c, str) for c in cats):
            # column-name entries resolve against feature_name
            # (reference basic.py categorical_feature by str)
            if not self.feature_name:
                raise LightGBMError(
                    "categorical_feature given by name requires feature_name"
                )
            try:
                cats = [
                    c if not isinstance(c, str) else self.feature_name.index(c)
                    for c in cats
                ]
            except ValueError as e:
                raise LightGBMError(
                    f"categorical_feature name not in feature_name: {e}"
                ) from None
        with telemetry.span("lgbm.setup.ingest.metadata"):
            meta = Metadata(
                label=None if self.label is None else np.asarray(self.label),
                weights=self.weight,
                init_score=self.init_score,
            )
            if self.group is not None:
                meta.set_field("group", np.asarray(self.group))

        if isinstance(self.data, str):
            inner = BinnedDataset.from_file(
                self.data, config=cfg, reference=ref_inner,
                categorical_features=cats or None,
            )
            if meta.label is not None:
                inner.metadata.set_field("label", meta.label)
            for field in ("weight", "init_score"):
                v = meta.get_field(field)
                if v is not None:
                    inner.metadata.set_field(field, v)
            if meta.query_boundaries is not None:
                inner.metadata.query_boundaries = meta.query_boundaries
                inner.metadata._finish()
            return inner
        if meta.label is None:
            raise LightGBMError("label should not be None for training data")
        # ``categorical_column`` in params declares columns of in-memory
        # data too, as the reference takes it: a matrix has no label
        # column, so its indices are the matrix's own
        names = (list(self.feature_name)
                 if isinstance(self.feature_name, (list, tuple)) else None)
        try:
            declared = _resolve_column_list(cfg.categorical_column, names)
        except ValueError as e:
            raise LightGBMError(
                f"categorical_column={cfg.categorical_column!r} needs "
                f"feature_name to hold its names: {e}") from None
        cats = _merge_api_categoricals(
            [], declared + list(cats),
            self.data.shape[1] if hasattr(self.data, "shape")
            else len(self.data[0]))
        if hasattr(self.data, "tocsr"):  # scipy sparse: O(nnz) ingest,
            # never densified to f64 (reference SparseBin path,
            # sparse_bin.hpp; round 1 called .toarray() here)
            csr = self.data.tocsr()
            indptr = np.asarray(csr.indptr, dtype=np.int64)
            indices = np.asarray(csr.indices, dtype=np.int64)
            values = np.asarray(csr.data, dtype=np.float64)
            if ref_inner is not None:
                return ref_inner.align_with_csr(indptr, indices, values, meta)
            return BinnedDataset.from_csr(
                indptr, indices, values, csr.shape[1], meta, config=cfg,
                categorical_features=cats,
                feature_names=self.feature_name,
            )
        X = _densify(self.data, dtype=None)
        if ref_inner is not None:
            return ref_inner.align_with(X, meta)
        return BinnedDataset.from_matrix(
            X,
            meta,
            config=cfg,
            categorical_features=cats,
            feature_names=self.feature_name,
        )

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """A validation set aligned to this dataset (basic.py:1074-1097)."""
        return Dataset(
            data, label=label, reference=self, weight=weight, group=group,
            init_score=init_score, params=params or self.params,
        )

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing this dataset's bin mappers (basic.py:1099)."""
        inner = self.construct().subset(np.asarray(used_indices))
        out = Dataset.__new__(Dataset)
        out.__dict__.update(
            data=None, label=None, max_bin=self.max_bin, reference=self,
            weight=None, group=None, init_score=None, feature_name=self.feature_name,
            categorical_feature=self.categorical_feature,
            params=dict(params or self.params), free_raw_data=True, _inner=inner,
        )
        return out

    def save_binary(self, filename: str) -> None:
        self.construct().save_binary(filename)

    # -------------------------------------------------------------- fields
    def set_field(self, field_name: str, data) -> None:
        if self._inner is not None:
            self._inner.metadata.set_field(field_name, data)
        if field_name == "label":
            self.label = data
        elif field_name == "weight":
            self.weight = data
        elif field_name in ("group", "query"):
            self.group = data
        elif field_name == "init_score":
            self.init_score = data

    def get_field(self, field_name: str):
        if self._inner is not None:
            return self._inner.metadata.get_field(field_name)
        return {
            "label": self.label, "weight": self.weight,
            "group": self.group, "query": self.group,
            "init_score": self.init_score,
        }.get(field_name)

    set_label = lambda self, label: self.set_field("label", label)
    set_weight = lambda self, weight: self.set_field("weight", weight)
    set_group = lambda self, group: self.set_field("group", group)
    set_init_score = lambda self, s: self.set_field("init_score", s)

    def get_group(self):
        """Per-query group sizes (reference basic.py get_group =
        get_field('group'))."""
        g = self.get_field("group")
        return None if g is None else np.asarray(g)

    def _reset_or_refuse(self, what: str) -> None:
        """Binning-input mutation after construction: rebin lazily when
        the raw data is still held (reference basic.py drops its inner
        dataset), refuse only once the raw data was freed."""
        if self._inner is None:
            return
        if self.data is not None:
            self._inner = None
        else:
            raise LightGBMError(
                f"cannot change {what} after construction once raw data "
                "was freed; create a new Dataset"
            )

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Declare categorical columns by index or name, or 'auto'
        (reference basic.py:1135-1147)."""
        if isinstance(categorical_feature, str):
            if categorical_feature != "auto":
                raise LightGBMError(
                    "categorical_feature must be a list of int/str or 'auto'"
                )
            cats = []
        else:
            cats = list(categorical_feature or [])
        if cats != self.categorical_feature:
            self._reset_or_refuse("categorical_feature")
        self.categorical_feature = cats
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        """Column names (reference basic.py set_feature_name)."""
        names = list(feature_name) if feature_name is not None else None
        if names is not None:
            expected = None
            if self._inner is not None:
                expected = self._inner.num_total_features
            elif hasattr(self.data, "shape") and len(
                getattr(self.data, "shape", ())
            ) == 2:
                expected = self.data.shape[1]
            if expected is not None and len(names) != expected:
                raise LightGBMError(
                    f"expected {expected} feature names, got {len(names)}"
                )
            if self._inner is not None:
                self._inner.feature_names = names
        self.feature_name = names
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Align this dataset's binning to another dataset's bin mappers
        (reference basic.py set_reference)."""
        if reference is not self.reference:
            self._reset_or_refuse("reference")
        self.reference = reference
        return self

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        return self.construct().num_data

    def num_feature(self) -> int:
        return self.construct().num_total_features


class Booster:
    """The boosting model (reference basic.py:1276-1819).

    Construct with either ``train_set`` (training mode), ``model_file``
    (prediction mode), or ``model_str``.
    """

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
    ):
        self.params = dict(params or {})
        self.best_iteration = -1
        self._train_dataset: Optional[Dataset] = None
        self.name_valid_sets: List[str] = []
        self.train_data_name = "training"
        self._attr: Dict[str, str] = {}
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise LightGBMError("Training data should be Dataset instance")
            cfg = Config.from_dict(self.params)
            inner_train = train_set.construct()  # under its own span
            with telemetry.span("lgbm.setup.booster"):
                objective = None
                if cfg.objective != "none":
                    with telemetry.span("lgbm.setup.booster.objective"):
                        objective = create_objective(
                            cfg, inner_train.metadata, inner_train.num_data)
                self._gbdt = create_boosting(cfg, inner_train, objective)
                self.config = cfg
                self._train_dataset = train_set
                if cfg.input_model:
                    init = Booster(model_file=cfg.input_model)
                    self._gbdt.merge_from(init._gbdt, prepend=True)
        elif model_file is not None:
            with open(model_file, "r") as fh:
                model_str = fh.read()
            self._init_from_string(model_str)
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise LightGBMError(
                "Booster needs at least one of train_set, model_file, model_str"
            )

    def _init_from_string(self, model_str: str) -> None:
        cfg = Config.from_dict(self.params)
        first = model_str.lstrip().splitlines()[0].strip()
        # model-file type sniffing (boosting.cpp:7-16)
        if first == "dart":
            from .models.dart import DART

            self._gbdt = DART(cfg)
        else:
            self._gbdt = GBDT(cfg)
        self._gbdt.load_model_from_string(model_str)
        self.config = cfg

    # ----------------------------------------------------------- attributes
    def attr(self, key: str) -> Optional[str]:
        """Get a string attribute (reference basic.py attr)."""
        return self._attr.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set string attributes; None deletes (reference basic.py
        set_attr)."""
        for key, value in kwargs.items():
            if value is None:
                self._attr.pop(key, None)
            else:
                if not isinstance(value, str):
                    # ValueError for reference exception compatibility
                    # (reference basic.py set_attr)
                    raise ValueError("Set attr only accepts strings")
                self._attr[key] = value
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Name used for the training set in eval output (reference
        basic.py set_train_data_name)."""
        self.train_data_name = name
        return self

    # ------------------------------------------------------------- training
    def add_valid(self, data: Dataset, name: str) -> None:
        """basic.py:1388 / LGBM_BoosterAddValidData."""
        if not isinstance(data, Dataset):
            raise LightGBMError("Validation data should be Dataset instance")
        self._gbdt.add_valid_dataset(data.construct(), name)
        self.name_valid_sets.append(name)

    def finish_lagged_stop(self) -> None:
        """Drain the lagged stop check after the last update() call
        (no-op unless LGBM_TPU_STOP_LAG is set) — see GBDT."""
        self._gbdt.finish_lagged_stop()

    def update(self, train_set: Optional[Dataset] = None, fobj: Optional[Callable] = None) -> bool:
        """One boosting iteration; returns True if no further training is
        possible (basic.py:1431-1501)."""
        if train_set is not None and train_set is not self._train_dataset:
            self._reset_train_data(train_set)
        if fobj is None:
            return self._gbdt.train_one_iter()
        grad, hess = fobj(self.__inner_predict_flat(0), self._train_dataset)
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        n = self._gbdt.num_data * self._gbdt.num_class
        if len(grad) != n or len(hess) != n:
            raise LightGBMError(
                f"Lengths of gradient({len(grad)}) and hessian({len(hess)}) "
                f"don't match training rows x classes ({n})"
            )
        return self._gbdt.train_one_iter(grad, hess)

    def _reset_train_data(self, train_set: Dataset) -> None:
        """LGBM_BoosterResetTrainingData semantics, shared by update()'s
        train_set branch and the C API shim."""
        inner = train_set.construct()
        obj = create_objective(self.config, inner.metadata, inner.num_data) \
            if self.config.objective != "none" else None
        self._gbdt.reset_training_data(inner, obj)
        self._train_dataset = train_set

    def rollback_one_iter(self) -> None:
        self._gbdt.rollback_one_iter()

    def reset_parameter(self, params: Dict[str, Any]) -> None:
        """Subset of parameters resettable mid-training (learning_rate et al;
        reference LGBM_BoosterResetParameter path)."""
        params = key_alias_transform(dict(params))
        for k, v in params.items():
            if hasattr(self.config, k):
                setattr(self.config, k, type(getattr(self.config, k))(v))
        if "learning_rate" in params:
            self._gbdt.learning_rate = float(params["learning_rate"])
        self.params.update(params)

    # ----------------------------------------------------------------- eval
    def __inner_predict_flat(self, data_idx: int) -> np.ndarray:
        s = self._gbdt.predict_at(data_idx)  # [K, n]
        return s.reshape(-1)  # class-major flatten, matching the reference

    def eval(self, data: Union[int, Dataset], name: str, feval=None):
        """Evaluate on train (0) / added valid sets; returns the reference's
        (data_name, eval_name, result, is_higher_better) tuples."""
        if isinstance(data, int):
            data_idx = data
        else:
            if data is self._train_dataset:
                data_idx = 0
            else:
                inner = data.construct()
                data_idx = 1 + next(
                    i for i, vs in enumerate(self._gbdt.valid_sets) if vs is inner
                )
        return self.__eval_at(data_idx, name, feval)

    def eval_train(self, feval=None):
        return self.__eval_at(0, self.train_data_name, feval)

    def eval_valid(self, feval=None):
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out.extend(self.__eval_at(i + 1, name, feval))
        return out

    def __eval_at(self, data_idx: int, name: str, feval=None):
        gb = self._gbdt
        metrics = gb.train_metrics if data_idx == 0 else gb.valid_metrics[data_idx - 1]
        scores = gb.predict_at(data_idx)
        s = scores if gb.num_class > 1 else scores[0]
        out = []
        for m in metrics:
            if hasattr(m, "eval_multi"):
                for k, v in zip(m.eval_at, m.eval_multi(s)):
                    out.append((name, f"{m.name}@{k}", v, m.bigger_is_better))
            else:
                out.append((name, m.name, m.eval(s), m.bigger_is_better))
        if feval is not None:
            ds = self._train_dataset if data_idx == 0 else _DatasetView(
                gb.valid_sets[data_idx - 1]
            )
            ret = feval(scores.reshape(-1), ds)
            if ret is not None:
                if isinstance(ret, list):
                    for n_, v_, b_ in ret:
                        out.append((name, n_, v_, b_))
                else:
                    n_, v_, b_ = ret
                    out.append((name, n_, v_, b_))
        return out

    # -------------------------------------------------------------- predict
    def predict(
        self,
        data,
        num_iteration: int = -1,
        raw_score: bool = False,
        pred_leaf: bool = False,
        data_has_header: bool = False,
        is_reshape: bool = True,
    ):
        """Prediction on raw (unbinned) features; ``data`` may be a matrix
        or a text file path (basic.py:259-448 semantics)."""
        if self.best_iteration > 0 and num_iteration <= 0:
            num_iteration = self.best_iteration
        if isinstance(data, str):
            from .io.parser import parse_file

            # STRICT on the prediction path regardless of any training
            # config: lenient parsing skips rows, and a skipped row
            # silently shifts every later prediction onto the wrong
            # input line — raising (the pre-hardening behavior) is the
            # only row-alignment-safe response here
            raw, _ = parse_file(data, has_header=data_has_header,
                                strict=True)
            label_idx = self._gbdt.label_idx
            if raw.shape[1] > self._gbdt.max_feature_idx + 1:
                data = np.delete(raw, label_idx, axis=1)
            else:
                data = raw
        if hasattr(data, "tocsr"):
            # sparse inputs: densify per row-chunk so peak memory is one
            # chunk, not the whole matrix (the reference predicts CSR
            # natively, c_api.cpp PredictForCSR; trees only read the
            # split features of each row anyway).  The chunk row count
            # scales with the width so the dense chunk stays ~256MB
            # whatever the feature count.
            n_rows, n_cols = data.shape
            chunk_rows = max(1, (32 << 20) // max(1, n_cols))  # 32M f64 elems
            if n_rows > chunk_rows:
                csr = data.tocsr()
                chunks = [
                    self.predict(
                        csr[i : i + chunk_rows].toarray(),
                        num_iteration=num_iteration, raw_score=raw_score,
                        pred_leaf=pred_leaf, is_reshape=is_reshape,
                    )
                    for i in range(0, n_rows, chunk_rows)
                ]
                return np.concatenate(chunks, axis=0)
        X = _densify(data)
        if pred_leaf:
            return self._gbdt.predict_leaf_index(X, num_iteration)
        if raw_score:
            return self._gbdt.predict_raw_score(X, num_iteration)
        return self._gbdt.predict(X, num_iteration)

    # ----------------------------------------------------------------- save
    def save_model(self, filename: str, num_iteration: int = -1) -> None:
        if num_iteration <= 0:
            num_iteration = self.best_iteration
        self._gbdt.save_model_to_file(filename, num_iteration)

    def model_to_string(self, num_iteration: int = -1) -> str:
        if num_iteration <= 0:
            num_iteration = self.best_iteration
        return self._gbdt.save_model_to_string(num_iteration)

    def dump_model(self, num_iteration: int = -1) -> Dict[str, Any]:
        """JSON-style dict dump (gbdt.cpp:438-477)."""
        if num_iteration <= 0:
            num_iteration = self.best_iteration
        return self._gbdt.dump_model(num_iteration)

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        imp = self._gbdt.feature_importance_array(importance_type)
        return imp

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names)

    @property
    def current_iteration(self) -> int:
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return self._gbdt.num_trees

    # --------------------------------------------------------------- pickle
    def __getstate__(self):
        """Pickle via model-string round trip (basic.py:1360)."""
        state = {
            "params": self.params,
            "best_iteration": self.best_iteration,
            "model_str": self._gbdt.save_model_to_string(-1),
            "attr": dict(self._attr),
            "train_data_name": self.train_data_name,
        }
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self._train_dataset = None
        self.name_valid_sets = []
        self.train_data_name = state.get("train_data_name", "training")
        self._attr = dict(state.get("attr", {}))
        self._init_from_string(state["model_str"])

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        out = Booster(model_str=self._gbdt.save_model_to_string(-1),
                      params=copy.deepcopy(self.params))
        out.best_iteration = self.best_iteration
        out._attr = dict(self._attr)
        out.train_data_name = self.train_data_name
        return out


class _DatasetView:
    """Minimal Dataset-like wrapper handed to custom fobj/feval for valid
    sets (exposes get_label/get_weight/get_field like the reference)."""

    def __init__(self, inner: BinnedDataset):
        self._inner = inner

    def get_label(self):
        return self._inner.metadata.label

    def get_weight(self):
        return self._inner.metadata.weights

    def get_field(self, name):
        return self._inner.metadata.get_field(name)

    def num_data(self):
        return self._inner.num_data
