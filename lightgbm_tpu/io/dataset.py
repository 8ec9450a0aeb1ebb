"""Binned dataset: the device-friendly column store.

TPU-native redesign of the reference ``Dataset``/``DatasetLoader``
(include/LightGBM/dataset.h:279-411, src/io/dataset_loader.cpp): instead of
per-feature Bin objects (dense u8/u16/u32 + sparse delta encodings), the
whole dataset is a single dense binned matrix ``X_bin: uint8[n, F]`` (u16
when any feature has >256 bins) laid out row-major in host memory and moved
to TPU HBM once.  Trivial (single-bin) features are dropped and tracked via
``used_feature_map`` exactly like the reference (dataset.h:286-307).

Loading pipeline (mirrors DatasetLoader::LoadFromFile, dataset_loader.cpp:162):
parse text -> resolve column roles -> sample rows (bin_construct_sample_cnt)
-> find per-feature BinMappers -> encode all rows to bins.  Valid sets are
encoded with the *train* set's mappers (LoadFromFileAlignWithOtherDataset,
dataset_loader.cpp:223-264).  A binary cache (npz) skips parse+binning
(SaveBinaryFile/LoadFromBinFile, dataset.cpp:131-168).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..log import Log
from ..obs import telemetry
from .binner import BinMapper, CATEGORICAL, NUMERICAL, find_bin_mappers
from .metadata import Metadata
from .parser import ParseError, parse_file

BINARY_MAGIC = "lightgbm_tpu_binned_dataset_v1"
# rows of one column that the numpy encoder bins in one task (_encode_bins)
ENCODE_BLOCK_ROWS = 1 << 21


def _finite_label_mask(label_col: np.ndarray, config: Config, path: str,
                       has_side_rows: bool = False) -> Optional[np.ndarray]:
    """Input hardening: rows with non-finite labels are a counted,
    logged skip (telemetry ``bad_rows``) — a single NaN label would
    otherwise poison every gradient of the run.  Returns the keep mask,
    or None when all labels are finite.  ``strict_data=true`` raises;
    so does the presence of row-aligned side files (weights/query/
    init_score), where silently renumbering rows would desynchronize
    them."""
    bad = ~np.isfinite(np.asarray(label_col, np.float64))
    n_bad = int(bad.sum())
    if n_bad == 0:
        return None
    msg = (f"{path}: {n_bad} row(s) with non-finite labels "
           f"(first at data row {int(np.argmax(bad))})")
    if config.strict_data:
        raise ParseError(msg + " (strict_data=true)")
    if has_side_rows:
        raise ParseError(
            msg + " — cannot skip rows: row-aligned side files "
            "(.weight/.query/.init) would desynchronize. Clean the data "
            "or regenerate the side files.")
    telemetry.count("bad_rows", n_bad)
    Log.warning(msg + "; skipping them (strict_data=false)")
    return ~bad


def _encode_bins(
    X: np.ndarray,
    used_map: np.ndarray,
    mappers: List[BinMapper],
    X_bin: np.ndarray,
) -> None:
    """Fill ``X_bin[:, inner] = mappers[inner].value_to_bin(X[:, orig])``
    for every used column — the Feature::PushData loop
    (dataset_loader.cpp:761, feature.h:79-85).  Numerical features go
    through the native OpenMP batch encoder when available."""
    from .. import native

    num_orig: List[int] = []
    num_inner: List[int] = []
    num_bounds: List[np.ndarray] = []
    rest: List[Tuple[int, int]] = []
    for orig, inner in enumerate(used_map):
        if inner < 0:
            continue
        m = mappers[inner]
        if m.bin_type == NUMERICAL:
            num_orig.append(orig)
            num_inner.append(int(inner))
            num_bounds.append(np.asarray(m.bin_upper_bound, np.float64))
        else:
            rest.append((orig, int(inner)))

    if num_orig:
        inner_arr = np.asarray(num_inner)
        direct = (
            X_bin.flags.c_contiguous
            and len(num_orig) == X_bin.shape[1]
            and np.array_equal(inner_arr, np.arange(X_bin.shape[1]))
        )
        out = X_bin if direct else np.empty(
            (X.shape[0], len(num_orig)), X_bin.dtype
        )
        if native.value_to_bin_numerical(
            np.ascontiguousarray(X, np.float64),
            np.asarray(num_orig, np.int64),
            num_bounds,
            out,
        ):
            if not direct:
                X_bin[:, inner_arr] = out
        else:  # pure-python fallback
            rest = list(zip(num_orig, num_inner)) + rest

    if not rest:
        return
    # categorical columns (and every column without the native encoder):
    # numpy, a block of rows of a column a task, on threads (its sorts
    # and searches let go of the GIL); every value is binned alone, so the
    # blocks' results are the whole column's
    n = X.shape[0]
    tasks = [(orig, inner, lo) for orig, inner in rest
             for lo in range(0, n, ENCODE_BLOCK_ROWS)]

    def encode(task):
        orig, inner, lo = task
        hi = min(lo + ENCODE_BLOCK_ROWS, n)
        X_bin[lo:hi, inner] = mappers[inner].value_to_bin(X[lo:hi, orig])

    with telemetry.span("lgbm.setup.ingest.encode.python"):
        if len(tasks) == 1:
            encode(tasks[0])
            return
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(min(len(tasks), os.cpu_count() or 1)) as pool:
            for _ in pool.map(encode, tasks):
                pass


def _sample_row_indices(n: int, config: Config) -> np.ndarray:
    """The shared-seed bin-construction sample draw (config.h:108 default
    50k rows).  ONE implementation on purpose: streaming, distributed,
    sparse, and in-memory loading must all draw the identical rows for
    their bin mappers (and therefore trees) to be bit-identical."""
    cnt = min(n, int(config.bin_construct_sample_cnt))
    rng = np.random.RandomState(config.data_random_seed)
    if cnt >= n:
        return np.arange(n)
    return np.sort(rng.choice(n, size=cnt, replace=False))


def _resolve_roles(config: Config, names: Optional[List[str]]):
    """Column-role resolution shared by the one-shot and streaming
    loaders (dataset_loader.cpp:23-160): returns (label_col, ignore set,
    categorical cols, weight_col, group_col) in raw column space, with
    weight/group added to the ignore set."""
    label_col = _resolve_column(config.label_column, names)
    if label_col is None:
        label_col = 0
    ignore = set(_resolve_column_list(config.ignore_column, names, label_col))
    cats = _resolve_column_list(config.categorical_column, names, label_col)
    weight_col = _resolve_column(config.weight_column, names, label_col)
    group_col = _resolve_column(config.group_column, names, label_col)
    if weight_col is not None:
        ignore.add(weight_col)
    if group_col is not None:
        ignore.add(group_col)
    return label_col, ignore, cats, weight_col, group_col



def _merge_api_categoricals(cat_inner, categorical_features, num_features):
    """Union API-level (FEATURE-space) categorical declarations into the
    config-derived list, validating range — a typo'd index must not be a
    silent no-op."""
    if not categorical_features:
        return cat_inner
    bad = [c for c in categorical_features if not 0 <= int(c) < num_features]
    if bad:
        raise ValueError(
            f"categorical_feature indices out of range: {bad} "
            f"(num_features={num_features})"
        )
    return sorted(set(cat_inner) | {int(c) for c in categorical_features})


def _resolve_column(spec: str, names: Optional[List[str]],
                    label_col: Optional[int] = None) -> Optional[int]:
    """Resolve 'name:foo' or integer-string column spec to a RAW column
    index (dataset_loader.cpp:23-160).

    Numeric side-column specs (weight/group/ignore/categorical) are
    FEATURE-space in the reference — its parser strips the label before
    assigning indices (parser.hpp:28-33, ``bias = -1``), and name lookups
    go through a label-removed name2idx (dataset_loader.cpp:62-67).  Pass
    ``label_col`` to convert such a spec to raw space; the label spec
    itself resolves raw (``label_col=None``)."""
    if spec is None or spec == "":
        return None
    if spec.startswith("name:"):
        if names is None:
            raise ValueError("column given by name but data has no header")
        return names.index(spec[5:])
    v = int(spec)
    if label_col is not None and v >= label_col:
        v += 1
    return v


def _resolve_column_list(spec: str, names: Optional[List[str]],
                         label_col: Optional[int] = None) -> List[int]:
    """List form of :func:`_resolve_column` (same feature-space
    semantics for numeric entries when ``label_col`` is given)."""
    spec = (spec or "").strip("[]() ")  # a list in ``params`` comes as its str
    if not spec:
        return []
    if spec.startswith("name:"):
        if names is None:
            raise ValueError("columns given by name but data has no header")
        return [names.index(s) for s in spec[5:].split(",")]
    out = [int(s) for s in spec.replace(",", " ").split()]
    if label_col is not None:
        out = [v if v < label_col else v + 1 for v in out]
    return out


class BinnedDataset:
    """Columns binned to integers + metadata; ready for device transfer."""

    def __init__(
        self,
        X_bin,
        bin_mappers: List[BinMapper],
        used_feature_map: np.ndarray,
        num_total_features: int,
        metadata: Metadata,
        feature_names: Optional[List[str]] = None,
    ):
        assert len(X_bin.shape) == 2 and X_bin.shape[1] == len(bin_mappers)
        # [n, F_used] uint8/uint16 ndarray, or a SparseBins CSR structure
        # (io/sparse.py) for high-sparsity data — the SparseBin analog
        # (src/io/sparse_bin.hpp), kept when density < 0.2 mirroring the
        # reference's sparse_rate >= 0.8 threshold (bin.cpp:291-302)
        self.X_bin = X_bin
        self.bin_mappers = bin_mappers  # per *used* feature
        # used_feature_map[orig_col] = inner feature idx or -1 (dataset.h:286)
        self.used_feature_map = used_feature_map
        self.num_total_features = int(num_total_features)
        self.metadata = metadata
        self.feature_names = feature_names or [
            f"Column_{i}" for i in range(num_total_features)
        ]

    def _count_ingest(self, sample_rows: int,
                      float64_bytes: int) -> "BinnedDataset":
        """The ``ingest.*`` counters, once a dataset, from the loader
        that binned it: the table's shape, the rows the bin finder
        sampled, the float64 values held at once, the bins kept."""
        telemetry.count_many({
            "ingest.rows": self.num_data,
            "ingest.columns": self.num_total_features,
            "ingest.used_columns": self.num_features,
            "ingest.sample_rows": sample_rows,
            "ingest.float64_bytes": float64_bytes,
            "ingest.bin_bytes": self.X_bin.nbytes,
        })
        return self

    # ---------------------------------------------------------------- props
    @property
    def is_sparse(self) -> bool:
        return not isinstance(self.X_bin, np.ndarray)

    def dense_bins(self) -> np.ndarray:
        """The dense [n, F_used] binned matrix — materialized on demand
        for sparse storage (binned u8 is 8-64x smaller than the raw f64
        the round-1 path densified, and trivial columns are already
        dropped, so this is the TPU-transfer layout, not a memory bomb)."""
        return self.X_bin.toarray() if self.is_sparse else self.X_bin

    def dense_bins_T_device(self, sharding=None):
        """The feature-major [F, n] binned matrix ON DEVICE, cached on
        the dataset so every booster sharing this dataset — cv() folds,
        train_many() models — shares ONE device copy instead of
        uploading num_models duplicates (the forest-batching HBM
        contract, docs/forest_batching.md).  ``sharding`` (a mesh
        learner's row layout) sends each shard from the host straight
        to its device; None is the default device."""
        cache = self.__dict__.setdefault("_bins_T_device", {})
        if sharding not in cache:
            import jax

            cache[sharding] = jax.device_put(
                np.ascontiguousarray(self.dense_bins().T), sharding)
        return cache[sharding]

    @property
    def num_data(self) -> int:
        return self.X_bin.shape[0]

    @property
    def num_features(self) -> int:
        return self.X_bin.shape[1]

    @property
    def num_bins_per_feature(self) -> np.ndarray:
        return np.array([m.num_bin for m in self.bin_mappers], dtype=np.int32)

    @property
    def max_num_bin(self) -> int:
        return int(self.num_bins_per_feature.max()) if self.num_features else 1

    @property
    def missing_bins(self) -> np.ndarray:
        """[F] int32: each feature's missing bin (its last), -1 where it
        has none (io/binner.py)."""
        return np.array([m.missing_bin for m in self.bin_mappers],
                        dtype=np.int32)

    @property
    def is_categorical(self) -> np.ndarray:
        return np.array(
            [m.bin_type == CATEGORICAL for m in self.bin_mappers], dtype=bool
        )

    def inner_to_real_feature(self, inner: int) -> int:
        """Inner feature index -> original column index."""
        return int(np.nonzero(self.used_feature_map == inner)[0][0])

    @property
    def real_feature_indices(self) -> np.ndarray:
        out = np.full(self.num_features, -1, dtype=np.int64)
        for orig, inner in enumerate(self.used_feature_map):
            if inner >= 0:
                out[inner] = orig
        return out

    # ------------------------------------------------------------ construct
    @staticmethod
    def from_matrix(
        X: np.ndarray,
        metadata: Metadata,
        config: Optional[Config] = None,
        categorical_features: Sequence[int] = (),
        feature_names: Optional[List[str]] = None,
        mappers_all: Optional[List[BinMapper]] = None,
    ) -> "BinnedDataset":
        """Bin a dense feature matrix.  ``mappers_all`` (one BinMapper per
        column, trivial ones dropped here) skips bin finding — used by the
        distributed loader where mappers must be rank-consistent."""
        config = config or Config()
        with telemetry.span("lgbm.setup.ingest.float64"):
            X = np.ascontiguousarray(X, dtype=np.float64)
        n, f_total = X.shape
        sample_rows = 0
        if mappers_all is None:
            with telemetry.span("lgbm.setup.ingest.find_bins"):
                sample_idx = _sample_row_indices(n, config)
                sample_rows = len(sample_idx)
                mappers_all = find_bin_mappers(
                    X[sample_idx],
                    total_sample_cnt=sample_rows,
                    max_bin=config.max_bin, use_missing=config.use_missing,
                    categorical_features=categorical_features,
                )
        if len(mappers_all) != f_total:
            raise ValueError(
                f"mappers_all covers {len(mappers_all)} columns, data has {f_total}"
            )
        used_map = np.full(f_total, -1, dtype=np.int64)
        used_mappers: List[BinMapper] = []
        for j, m in enumerate(mappers_all):
            if not m.is_trivial:
                used_map[j] = len(used_mappers)
                used_mappers.append(m)

        max_nb = max((m.num_bin for m in used_mappers), default=1)
        if max_nb > 65536:
            # the reference's u32 dense-bin specialization
            # (src/io/bin.cpp:304-322) is deliberately not carried: the
            # packed training record stores bins 2-per-i32 at u16 width
            # and no realistic config exceeds 65536 bins per feature —
            # fail loudly instead of silently wrapping the u16 cast
            raise ValueError(
                f"a feature produced {max_nb} bins; this build supports "
                f"max 65536 bins per feature (uint16 storage) — lower "
                f"max_bin or bin_construct_sample_cnt")
        dtype = np.uint8 if max_nb <= 256 else np.uint16
        with telemetry.span("lgbm.setup.ingest.encode"):
            X_bin = np.empty((n, len(used_mappers)), dtype=dtype)
            _encode_bins(X, used_map, used_mappers, X_bin)
            float64_bytes = X.nbytes
            # a float64 copy made above goes back to the system here, under
            # the span and not at the return: 0.08 s a GB on the chip's host
            del X
        return BinnedDataset(
            X_bin, used_mappers, used_map, f_total, metadata, feature_names
        )._count_ingest(sample_rows, float64_bytes)

    @staticmethod
    def from_csr(
        indptr: np.ndarray,
        indices: np.ndarray,
        values: np.ndarray,
        num_cols: int,
        metadata: Metadata,
        config: Optional[Config] = None,
        categorical_features: Sequence[int] = (),
        feature_names: Optional[List[str]] = None,
        mappers_all: Optional[List[BinMapper]] = None,
        keep_sparse: Optional[bool] = None,
    ) -> "BinnedDataset":
        """Bin a CSR matrix in O(nnz) memory — no dense f64 ever exists.

        Mirrors the reference's sparse push path (Feature::PushData on
        ``(col, value)`` pairs, feature.h:79-85 + sparse_bin.hpp): bin
        mappers are found from a sampled row subset with elided zeros
        counted (bin.cpp:48-85), then every stored entry is bin-encoded
        in place.  Storage stays CSR when density < 0.2 (``keep_sparse``
        overrides), else the dense u8 matrix is built.
        """
        from .sparse import encode_csr_bins, find_bin_mappers_csr

        config = config or Config()
        n = len(indptr) - 1
        sample_rows = 0
        if mappers_all is None:
            sample_idx = _sample_row_indices(n, config)
            sample_rows = len(sample_idx)
            mappers_all = find_bin_mappers_csr(
                indptr, indices, values, num_cols, sample_idx,
                max_bin=config.max_bin, use_missing=config.use_missing,
                categorical_features=categorical_features,
            )
        used_map = np.full(num_cols, -1, dtype=np.int64)
        used_mappers: List[BinMapper] = []
        for j, m in enumerate(mappers_all):
            if not m.is_trivial:
                used_map[j] = len(used_mappers)
                used_mappers.append(m)
        sb = encode_csr_bins(indptr, indices, values, used_map, used_mappers)
        f_used = max(len(used_mappers), 1)
        density = sb.nnz / float(max(n, 1) * f_used)
        if keep_sparse is None:
            # is_enable_sparse=false forces dense storage (config.h:104)
            keep_sparse = config.is_enable_sparse and density < 0.2
        X_bin = sb if keep_sparse else sb.toarray()
        return BinnedDataset(
            X_bin, used_mappers, used_map, num_cols, metadata, feature_names
        )._count_ingest(sample_rows, values.nbytes)

    def align_with(
        self, X: np.ndarray, metadata: Metadata
    ) -> "BinnedDataset":
        """Bin another raw matrix with THIS dataset's mappers (valid set
        alignment, dataset_loader.cpp:223-264)."""
        X = np.ascontiguousarray(X, dtype=np.float64)
        n, f_total = X.shape
        if f_total < self.num_total_features:
            pad = np.zeros((n, self.num_total_features - f_total), dtype=np.float64)
            X = np.hstack([X, pad])
        X_bin = np.empty((n, self.num_features), dtype=self.X_bin.dtype)
        _encode_bins(X, self.used_feature_map, self.bin_mappers, X_bin)
        return BinnedDataset(
            X_bin,
            self.bin_mappers,
            self.used_feature_map,
            self.num_total_features,
            metadata,
            self.feature_names,
        )

    def align_with_csr(
        self,
        indptr: np.ndarray,
        indices: np.ndarray,
        values: np.ndarray,
        metadata: Metadata,
        keep_sparse: Optional[bool] = None,
    ) -> "BinnedDataset":
        """Sparse counterpart of ``align_with``: bin CSR rows with THIS
        dataset's mappers in O(nnz)."""
        from .sparse import encode_csr_bins

        # entries in columns this dataset never saw map to no used feature
        in_range = indices < len(self.used_feature_map)
        if not in_range.all():
            n = len(indptr) - 1
            rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
            rows, indices, values = rows[in_range], indices[in_range], values[in_range]
            row_lens = np.bincount(rows, minlength=n)
            indptr = np.concatenate([[0], np.cumsum(row_lens, dtype=np.int64)])
        sb = encode_csr_bins(
            indptr, indices, values, self.used_feature_map, self.bin_mappers
        )
        if keep_sparse is None:
            keep_sparse = self.is_sparse
        return BinnedDataset(
            sb if keep_sparse else sb.toarray(),
            self.bin_mappers,
            self.used_feature_map,
            self.num_total_features,
            metadata,
            self.feature_names,
        )

    @staticmethod
    def from_file(
        path: str,
        config: Optional[Config] = None,
        reference: Optional["BinnedDataset"] = None,
        rank: Optional[int] = None,
        categorical_features: Optional[Sequence[int]] = None,
    ) -> "BinnedDataset":
        """Load + bin a text data file (or its binary cache).

        With ``config.num_machines > 1`` and ``is_pre_partition=false``,
        every rank reads the file and keeps only its shared-seed random
        row partition — query-granular for ranked data
        (dataset_loader.cpp:500-605).  ``rank`` defaults to
        ``jax.process_index()``."""
        config = config or Config()
        bin_path = path + ".bin"
        if (
            config.enable_load_from_binary_file
            and os.path.exists(bin_path)
            and reference is None
            and config.num_machines <= 1
            and not categorical_features
            # a cached binary records nothing about API-level categorical
            # declarations; honoring the declaration wins over the cache
        ):
            try:
                ds = BinnedDataset.load_binary(bin_path)
                if ds.is_sparse and not config.is_enable_sparse:
                    # the cache was written sparse; honor the flag anyway
                    ds.X_bin = ds.dense_bins()
                return ds
            except Exception:
                pass
        from .parser import detect_file_format

        fmt = detect_file_format(path, config.has_header)
        if fmt == "libsvm" and not config.weight_column and not config.group_column:
            return BinnedDataset._from_libsvm_sparse(
                path, config, reference=reference, rank=rank,
                categorical_features=categorical_features,
            )
        single_machine = config.num_machines <= 1 or config.is_pre_partition
        # auto-stream only for files too big to comfortably hold as f64
        # (the flag is the explicit opt-in; dense LibSVM with weight/
        # group columns keeps the one-shot parser)
        want_stream = config.use_two_round_loading or (
            os.path.getsize(path) > (4 << 30)
        )
        if want_stream and single_machine and fmt != "libsvm":
            try:
                return BinnedDataset._from_file_streaming(
                    path, config, fmt, reference=reference,
                    categorical_features=categorical_features,
                )
            except ParseError:
                raise  # already classified (strict mode / label guard)
            except ValueError as e:
                # malformed rows mid-stream: the chunked fast reader
                # cannot skip-and-continue (dropped rows would desync
                # the counted preallocation), so degrade to the one-shot
                # lenient path below — counted bad_rows skip semantics,
                # at the cost of whole-file memory for an already-
                # degraded input.  strict_data raises instead.
                if config.strict_data:
                    raise ParseError(
                        f"{path}: malformed rows in streaming load "
                        f"(strict_data=true): {type(e).__name__}: "
                        f"{str(e)[:200]}") from e
                Log.warning(
                    f"{path}: streaming parse failed "
                    f"({type(e).__name__}: {str(e)[:120]}); falling "
                    "back to one-shot lenient load (malformed rows "
                    "will be counted and skipped)")
        raw, names = parse_file(path, has_header=config.has_header, fmt=fmt,
                                strict=config.strict_data)
        side = Metadata.load_side_files(path)

        # ---- resolve column roles on the FULL file (dataset_loader.cpp:23-160)
        label_col, ignore, cats, weight_col, group_col = _resolve_roles(
            config, names
        )
        keep = _finite_label_mask(
            raw[:, label_col], config, path,
            has_side_rows=any(side.get(k) is not None for k in
                              ("weights", "query_boundaries", "init_score")))
        if keep is not None:
            raw = raw[keep]
        n = raw.shape[0]
        label = raw[:, label_col].astype(np.float32)
        weights = side.get("weights")
        if weight_col is not None:
            weights = raw[:, weight_col].astype(np.float32)
        qb = side.get("query_boundaries")
        if group_col is not None:
            gid = raw[:, group_col].astype(np.int64)
            # contiguous group ids -> boundaries
            change = np.nonzero(np.diff(gid))[0] + 1
            qb = np.concatenate([[0], change, [n]])

        feat_cols = [
            j for j in range(raw.shape[1]) if j != label_col and j not in ignore
        ]
        X = raw[:, feat_cols]
        fnames = (
            [names[j] for j in feat_cols]
            if names is not None
            else [f"Column_{j}" for j in range(len(feat_cols))]
        )
        cat_inner = _merge_api_categoricals(
            [feat_cols.index(c) for c in cats if c in feat_cols],
            categorical_features, len(feat_cols),
        )
        meta = Metadata(
            label=label,
            weights=weights,
            query_boundaries=qb,
            init_score=side.get("init_score"),
        )

        distributed = config.num_machines > 1 and not config.is_pre_partition
        mappers_all = None
        if distributed:
            from .distributed import (
                distributed_find_bin_mappers,
                partition_rows,
            )
            import jax

            if rank is None:
                rank = jax.process_index()
            # query-granular partition uses the FULL metadata's boundaries
            # (side file OR group_column, dataset_loader.cpp:560-605)
            keep = partition_rows(
                n, rank, config.num_machines,
                seed=config.data_random_seed,
                query_boundaries=meta.query_boundaries,
            )
            # Bin mappers must be rank-consistent.  Since is_pre_partition=
            # false means every rank parsed the FULL file, the shared-seed
            # sample over the full data gives identical mappers everywhere
            # with zero communication; with multiple attached processes the
            # feature-sharded finder + mapper allgather is used instead
            # (dataset_loader.cpp:692-755).
            sample_idx = _sample_row_indices(n, config)
            if jax.process_count() > 1:
                mappers_all = distributed_find_bin_mappers(
                    X[sample_idx], rank, config.num_machines,
                    max_bin=config.max_bin, use_missing=config.use_missing,
                    categorical_features=cat_inner,
                    total_sample_cnt=len(sample_idx),
                )
            else:
                mappers_all = find_bin_mappers(
                    X[sample_idx], total_sample_cnt=len(sample_idx),
                    max_bin=config.max_bin, use_missing=config.use_missing,
                    categorical_features=cat_inner,
                )
            X = X[keep]
            meta = meta.subset(keep)

        if reference is not None:
            return reference.align_with(X, meta)
        ds = BinnedDataset.from_matrix(
            X, meta, config, categorical_features=cat_inner,
            feature_names=fnames, mappers_all=mappers_all,
        )
        # the binary cache holds FULL-file contents only — a partitioned
        # rank subset must never poison the shared cache path
        if config.is_save_binary_file and not distributed:
            ds.save_binary(bin_path)
        return ds

    @staticmethod
    def _from_file_streaming(
        path: str,
        config: Config,
        fmt: str,
        reference: Optional["BinnedDataset"] = None,
        chunk_rows: int = 200_000,
        categorical_features: Optional[Sequence[int]] = None,
    ) -> "BinnedDataset":
        """Two-round loading (use_two_round_loading, dataset_loader.cpp:
        181-209): round one streams chunks to pull the bin-construction
        sample, round two streams again encoding each chunk straight into
        the preallocated binned matrix.  Peak RSS is the binned matrix
        plus one text chunk — never the whole file as float64.

        The sampled row indices reuse the in-memory path's shared-seed
        draw over the counted row total, so bin mappers (and therefore
        trees) are bit-identical to non-streaming loading.
        """
        from .parser import (
            _read_head,
            count_data_rows,
            parse_file_chunks,
        )

        names: Optional[List[str]] = None
        if config.has_header:
            head = _read_head(path, 1)
            sep = "," if fmt == "csv" else None
            names = [s.strip() for s in head[0].strip().split(sep)]
        side = Metadata.load_side_files(path)
        n = count_data_rows(path, config.has_header)

        label_col, ignore, cats, weight_col, group_col = _resolve_roles(
            config, names
        )

        feat_cols: Optional[List[int]] = None
        mappers_all = None
        if reference is None:
            # ---- round 1: stream chunks, keep only the sampled rows
            sample_idx = _sample_row_indices(n, config)
            offset = 0
            buf: List[np.ndarray] = []
            for chunk in parse_file_chunks(path, config.has_header, fmt, chunk_rows):
                if feat_cols is None:
                    feat_cols = [
                        j for j in range(chunk.shape[1])
                        if j != label_col and j not in ignore
                    ]
                lo = np.searchsorted(sample_idx, offset)
                hi = np.searchsorted(sample_idx, offset + len(chunk))
                if hi > lo:
                    buf.append(chunk[sample_idx[lo:hi] - offset][:, feat_cols])
                offset += len(chunk)
            sample_raw = np.vstack(buf)
            cat_inner = _merge_api_categoricals(
                [feat_cols.index(c) for c in cats if c in feat_cols],
                categorical_features, len(feat_cols),
            )
            mappers_all = find_bin_mappers(
                sample_raw,
                total_sample_cnt=len(sample_idx),
                max_bin=config.max_bin, use_missing=config.use_missing,
                categorical_features=cat_inner,
            )
            used_map = np.full(len(feat_cols), -1, dtype=np.int64)
            used_mappers: List[BinMapper] = []
            for j, m in enumerate(mappers_all):
                if not m.is_trivial:
                    used_map[j] = len(used_mappers)
                    used_mappers.append(m)
        else:
            used_map = reference.used_feature_map
            used_mappers = reference.bin_mappers

        # ---- round 2: stream again, encoding chunks into the binned matrix
        dtype = (
            np.uint8
            if max((m.num_bin for m in used_mappers), default=1) <= 256
            else np.uint16
        )
        X_bin = np.empty((n, len(used_mappers)), dtype=dtype)
        label = np.empty(n, np.float32)
        weights = np.empty(n, np.float32) if weight_col is not None else None
        gid = np.empty(n, np.int64) if group_col is not None else None
        offset = 0
        for chunk in parse_file_chunks(path, config.has_header, fmt, chunk_rows):
            if feat_cols is None:
                feat_cols = [
                    j for j in range(chunk.shape[1])
                    if j != label_col and j not in ignore
                ]
            m_rows = len(chunk)
            X = chunk[:, feat_cols]
            if reference is not None and X.shape[1] < len(used_map):
                X = np.hstack(
                    [X, np.zeros((m_rows, len(used_map) - X.shape[1]))]
                )
            _encode_bins(X, used_map, used_mappers, X_bin[offset:offset + m_rows])
            label[offset:offset + m_rows] = chunk[:, label_col]
            if weights is not None:
                weights[offset:offset + m_rows] = chunk[:, weight_col]
            if gid is not None:
                gid[offset:offset + m_rows] = chunk[:, group_col]
            offset += m_rows

        keep = _finite_label_mask(
            label, config, path,
            has_side_rows=any(side.get(k) is not None for k in
                              ("weights", "query_boundaries", "init_score")))
        if keep is not None:
            X_bin, label = X_bin[keep], label[keep]
            weights = weights[keep] if weights is not None else None
            gid = gid[keep] if gid is not None else None
            n = int(keep.sum())

        qb = side.get("query_boundaries")
        if gid is not None:
            change = np.nonzero(np.diff(gid))[0] + 1
            qb = np.concatenate([[0], change, [n]])
        meta = Metadata(
            label=label,
            weights=side.get("weights") if weights is None else weights,
            query_boundaries=qb,
            init_score=side.get("init_score"),
        )
        fnames = (
            [names[j] for j in feat_cols]
            if names is not None
            else None
        )
        if reference is not None:
            return BinnedDataset(
                X_bin,
                reference.bin_mappers,
                reference.used_feature_map,
                reference.num_total_features,
                meta,
                reference.feature_names,
            )
        ds = BinnedDataset(
            X_bin, used_mappers, used_map, len(feat_cols), meta, fnames
        )._count_ingest(len(sample_idx), sample_raw.nbytes)
        if config.is_save_binary_file:
            ds.save_binary(path + ".bin")
        return ds

    @staticmethod
    def _from_libsvm_sparse(
        path: str,
        config: Config,
        reference: Optional["BinnedDataset"] = None,
        rank: Optional[int] = None,
        categorical_features: Optional[Sequence[int]] = None,
    ) -> "BinnedDataset":
        """LibSVM ingest in O(nnz) memory — streamed CSR parse, sparse
        bin finding with elided zeros, in-place bin encoding.  Replaces
        the round-1 dense-f64 materialization (a news20-scale memory
        bomb; reference handles this via SparseBin, sparse_bin.hpp).

        Column-space note: ``ignore_column``/``categorical_column``
        numeric specs are FEATURE indices (the reference's parsers emit
        label-removed indices, parser.hpp:28-33; LibSVM token indices ARE
        feature indices), so they apply to the CSR columns directly.
        """
        from .sparse import _ranges_concat, parse_libsvm_csr

        label, indptr, indices, values, num_cols = parse_libsvm_csr(
            path, has_header=config.has_header
        )
        side = Metadata.load_side_files(path)
        keep = _finite_label_mask(
            label, config, path,
            has_side_rows=any(side.get(k) is not None for k in
                              ("weights", "query_boundaries", "init_score")))
        if keep is not None:
            nz_keep = np.repeat(keep, np.diff(indptr))
            indices, values = indices[nz_keep], values[nz_keep]
            label = label[keep]
            row_lens = np.diff(indptr)[keep]
            indptr = np.concatenate([[0], np.cumsum(row_lens,
                                                    dtype=np.int64)])
        n = len(label)

        ignore = set(_resolve_column_list(config.ignore_column, None))
        if ignore:
            keep = ~np.isin(indices, np.asarray(sorted(ignore)))
            rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
            rows, indices, values = rows[keep], indices[keep], values[keep]
            row_lens = np.bincount(rows, minlength=n)
            indptr = np.concatenate([[0], np.cumsum(row_lens, dtype=np.int64)])
        cats = _merge_api_categoricals(
            _resolve_column_list(config.categorical_column, None),
            categorical_features, num_cols,
        )
        meta = Metadata(
            label=label,
            weights=side.get("weights"),
            query_boundaries=side.get("query_boundaries"),
            init_score=side.get("init_score"),
        )

        distributed = config.num_machines > 1 and not config.is_pre_partition
        mappers_all = None
        if distributed:
            from .distributed import partition_rows
            from .sparse import find_bin_mappers_csr
            import jax

            if rank is None:
                rank = jax.process_index()
            keep_rows = partition_rows(
                n, rank, config.num_machines,
                seed=config.data_random_seed,
                query_boundaries=meta.query_boundaries,
            )
            # shared-seed sample over the FULL file gives every rank
            # identical mappers with zero communication (every rank
            # parsed the whole file when is_pre_partition=false)
            sample_idx = _sample_row_indices(n, config)
            mappers_all = find_bin_mappers_csr(
                indptr, indices, values, num_cols, sample_idx,
                max_bin=config.max_bin, use_missing=config.use_missing,
                categorical_features=cats,
            )
            keep_rows = np.asarray(keep_rows)
            starts = indptr[keep_rows]
            lens = indptr[keep_rows + 1] - starts
            take = _ranges_concat(starts, lens)
            indices, values = indices[take], values[take]
            indptr = np.concatenate([[0], np.cumsum(lens, dtype=np.int64)])
            meta = meta.subset(keep_rows)

        if reference is not None:
            return reference.align_with_csr(indptr, indices, values, meta)
        ds = BinnedDataset.from_csr(
            indptr, indices, values, num_cols, meta, config,
            categorical_features=cats, mappers_all=mappers_all,
        )
        if config.is_save_binary_file and not distributed:
            ds.save_binary(path + ".bin")
        return ds

    # ---------------------------------------------------------- binary cache
    def save_binary(self, path: str) -> None:
        import json

        tmp = path + ".tmp.npz"
        sparse_fields = {}
        if self.is_sparse:
            sparse_fields = dict(
                sp_indptr=self.X_bin.indptr,
                sp_col=self.X_bin.col,
                sp_bin=self.X_bin.bin,
                sp_default=self.X_bin.default_bins,
                sp_shape=np.asarray(self.X_bin.shape, dtype=np.int64),
            )
        np.savez_compressed(
            tmp,
            magic=BINARY_MAGIC,
            X_bin=np.empty((0, 0), np.uint8) if self.is_sparse else self.X_bin,
            **sparse_fields,
            used_feature_map=self.used_feature_map,
            num_total_features=self.num_total_features,
            mappers=json.dumps([m.to_dict() for m in self.bin_mappers]),
            feature_names=json.dumps(self.feature_names),
            label=self.metadata.label if self.metadata.label is not None else np.empty(0),
            weights=self.metadata.weights
            if self.metadata.weights is not None
            else np.empty(0),
            query_boundaries=self.metadata.query_boundaries
            if self.metadata.query_boundaries is not None
            else np.empty(0, dtype=np.int64),
            init_score=self.metadata.init_score
            if self.metadata.init_score is not None
            else np.empty(0),
        )
        # numpy appends .npz to names without it; move atomically onto the
        # requested name so a re-save never leaves a stale cache behind
        os.replace(tmp if os.path.exists(tmp) else tmp + ".npz", path)

    @staticmethod
    def load_binary(path: str) -> "BinnedDataset":
        import json

        with np.load(path, allow_pickle=False) as z:
            if str(z["magic"]) != BINARY_MAGIC:
                raise ValueError("not a lightgbm_tpu binary dataset file")
            mappers = [BinMapper.from_dict(d) for d in json.loads(str(z["mappers"]))]
            meta = Metadata(
                label=z["label"] if z["label"].size else None,
                weights=z["weights"] if z["weights"].size else None,
                query_boundaries=z["query_boundaries"]
                if z["query_boundaries"].size
                else None,
                init_score=z["init_score"] if z["init_score"].size else None,
            )
            if "sp_indptr" in z:
                from .sparse import SparseBins

                storage = SparseBins(
                    z["sp_indptr"], z["sp_col"], z["sp_bin"],
                    z["sp_default"], tuple(z["sp_shape"]),
                )
            else:
                storage = z["X_bin"]
            return BinnedDataset(
                storage,
                mappers,
                z["used_feature_map"],
                int(z["num_total_features"]),
                meta,
                json.loads(str(z["feature_names"])),
            )

    # -------------------------------------------------------------- numerics
    def subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row subset sharing bin mappers (Dataset::Subset, dataset.cpp:59)."""
        indices = np.asarray(indices)
        return BinnedDataset(
            self.X_bin.rows(indices) if self.is_sparse else self.X_bin[indices],
            self.bin_mappers,
            self.used_feature_map,
            self.num_total_features,
            self.metadata.subset(indices),
            self.feature_names,
        )

    def check_align(self, other: "BinnedDataset") -> bool:
        """Valid-data bin compatibility (Dataset::CheckAlign,
        dataset.h:290-306)."""
        if other.num_features != self.num_features:
            return False
        return all(
            a.num_bin == b.num_bin for a, b in zip(self.bin_mappers, other.bin_mappers)
        )

    def bin_thresholds_real(self) -> List[np.ndarray]:
        """Per-feature real-valued threshold for each bin (used when writing
        tree thresholds in raw-value space, tree.cpp:70)."""
        return [m.bin_upper_bound if m.bin_type == NUMERICAL else np.asarray(m.bin_to_category, dtype=np.float64) for m in self.bin_mappers]
