"""GBDT boosting driver.

TPU-native re-design of the reference GBDT (src/boosting/gbdt.{h,cpp}):
the binned matrix lives on device feature-major; each boosting iteration
computes objective gradients (jitted), optionally re-samples a bagging
mask, grows one tree per class with the serial (or parallel) learner,
applies shrinkage, and updates train/valid scores entirely on device —
train scores via the final leaf partition (no traversal, mirroring
score_updater.hpp:59-61), valid scores via vectorized traversal of the
bin-aligned valid matrix.

Model save/load uses the reference's text format byte-for-byte
(gbdt.cpp:479-592, tree.cpp:124-151) so models interoperate.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import recompile
from ..config import Config
from ..device import enable_compile_cache, on_tpu
from ..device import platform as device_platform
from ..io.dataset import BinnedDataset
from ..obs import memory as obs_memory
from ..obs import telemetry
from ..resilience import faults
from ..resilience.atomic import atomic_write
from ..obs.device_time import phase_scope
from ..learners.serial import (
    TreeLearnerParams, check_count_envelope, grow_tree)
from ..metrics import Metric, create_metrics
from ..objectives import ObjectiveFunction, create_objective
from .tree import (
    Tree,
    empty_tree,
    finalize_thresholds,
    finalize_thresholds_device,
    ensemble_leaves_raw,
    leaf_lookup,
    leaf_lookup_path,
    ensemble_sum_binned,
    ensemble_sum_raw,
    pack_threshold_bounds,
    predict_binned,
    predict_raw,
    stack_trees,
    predict_leaf_raw,
)

# Batch-prediction backend (read ONCE at import, like the kernel knobs):
# "auto" = the matmul path (ops/predict_matmul.py) on TPU, the
# vectorized walk elsewhere (the dense path-incidence matmuls would run
# at scalar speed on the CPU fallback); "1"/"0" force.
_PREDICT_MM = os.environ.get("LGBM_TPU_PREDICT_MATMUL", "auto")
# rows per matmul-predict dispatch: bounds the [rows, L]-shaped dense
# intermediates (~2.5KB/row/tree-step at L=255) well inside HBM
_ROW_CHUNK = int(os.environ.get("LGBM_TPU_PREDICT_ROW_CHUNK", str(1 << 20)))

# forest_batching="auto" row ceiling: the explicit batched grow loop
# (learners/forest.py) does O(n) work per split per lane while the
# sequential windows tier down, so its win inverts as n grows — the
# CPU-container sweep (docs/forest_batching.md) crosses between 2k rows
# (1.45x faster) and 4k (0.64x).  Chip re-evaluation rides
# forest_batching="on" or this env knob.
_FOREST_AUTO_MAX_ROWS = int(os.environ.get("LGBM_TPU_FOREST_MAX_ROWS",
                                           "2048"))


# path descriptions already logged at INFO (GBDT._log_paths_once)
_LOGGED_PATHS: set = set()


def _use_matmul_predict() -> bool:
    if _PREDICT_MM == "auto":
        return on_tpu()
    return _PREDICT_MM != "0"


def raw_score_output(out: np.ndarray, num_class: int) -> np.ndarray:
    """[K, n] raw scores -> the public raw-score shape ([n] or [n, K])."""
    return out[0] if num_class == 1 else out.T


def transform_scores(out: np.ndarray, num_class: int, sigmoid: float,
                     objective_name: str) -> np.ndarray:
    """GBDT::Predict's host-side f64 output transform (gbdt.cpp:
    631-645), factored out so the serving engine applies bitwise the
    SAME transform as the offline predictor (serving/engine.py)."""
    if sigmoid > 0 and num_class == 1 and objective_name == "binary":
        return 1.0 / (1.0 + np.exp(-2.0 * sigmoid * out[0]))
    if num_class > 1:
        z = out - out.max(axis=0, keepdims=True)
        e = np.exp(z)
        return (e / e.sum(axis=0, keepdims=True)).T
    return out[0]


@functools.partial(jax.jit, donate_argnums=(1,))
@phase_scope("leaf-update")
def _post_grow_step(tree, scores, k, leaf_id, rate, bounds_mat, real_feat):
    """Shrinkage + score update + device-side threshold finalization in
    one dispatch (gbdt.cpp:229-247's post-train steps)."""
    tree = tree.shrink(rate)
    scores = scores.at[k].add(leaf_lookup(tree.leaf_value, leaf_id))
    tree = finalize_thresholds_device(tree, bounds_mat, real_feat)
    return tree, scores


class GBDT:
    """Gradient Boosting Decision Trees (gbdt.h:17)."""

    name = "gbdt"

    def __init__(
        self,
        config: Config,
        train_set: Optional[BinnedDataset] = None,
        objective: Optional[ObjectiveFunction] = None,
    ):
        enable_compile_cache()  # lazy, TPU-gated, once
        recompile.install()  # the compile.* seconds by program, likewise
        self.config = config
        self.num_class = int(config.num_class)
        self.learning_rate = float(config.learning_rate)
        self.max_leaves = config.num_leaves_
        self.models: List[Tree] = []  # flat, iter-major: tree i*K+k
        self.iter_ = 0
        self.num_init_iteration = 0
        self.label_idx = 0
        self.max_feature_idx = -1
        self.feature_names: List[str] = []
        self.sigmoid = float(config.sigmoid)
        self.objective = objective
        self.train_set: Optional[BinnedDataset] = None
        self.valid_sets: List[BinnedDataset] = []
        self.valid_names: List[str] = []
        self.train_metrics: List[Metric] = []
        self.valid_metrics: List[List[Metric]] = []
        self.best_iteration = -1
        self._bag_rng = np.random.RandomState(config.bagging_seed)
        # lagged stop check (see train_one_iter); 0 = eager reference
        # semantics
        self._stop_lag = int(os.environ.get("LGBM_TPU_STOP_LAG", "0"))
        self._pending_stop: List[jax.Array] = []
        self._feat_rng = np.random.RandomState(config.feature_fraction_seed)
        # reference-parity double accumulation for histograms
        # (include/LightGBM/bin.h:21-22); see Config.hist_dtype.  f64 is
        # enabled per-trace via the jax.enable_x64 context in
        # train_one_iter, never by flipping the process-global flag.
        self._use_f64_hist = config.hist_dtype == "float64"
        # non-finite gradient/leaf guard (resilience/guards.py); None
        # under the default policy "off" — zero cost, zero behavior drift
        if getattr(config, "nonfinite_policy", "off") != "off":
            from ..resilience.guards import make_guard

            self._nf_guard = make_guard(config.nonfinite_policy)
        else:
            self._nf_guard = None
        self._model_version = 0
        self._missing_bin, self._missing_features = None, 0
        if train_set is not None:
            self.reset_training_data(train_set, objective)

    # ------------------------------------------------------------------ setup
    def reset_training_data(
        self, train_set: BinnedDataset, objective: Optional[ObjectiveFunction]
    ) -> None:
        """GBDT::ResetTrainingData (gbdt.cpp:49-122)."""
        self.train_set = train_set
        self.objective = objective
        n = train_set.num_data
        self.num_data = n
        self.max_feature_idx = train_set.num_total_features - 1
        self.feature_names = list(train_set.feature_names)
        if self.objective is not None and self.objective.name == "binary":
            self.sigmoid = self.objective.sigmoid

        self._num_bins = max(int(train_set.max_num_bin), 2)
        # mesh learners set these in _create_tree_learner: how the
        # [F, n] binned matrix and [n]-shaped row vectors lie over the
        # mesh.  None = everything on the default device (serial).
        self._bins_sharding = self._row_sharding = None
        self._learner_devices = 1
        # each feature's missing bin (io/binner.py), on the device where
        # any feature has one: the growers and the binned walks take it,
        # and None keeps every program of a table without
        mb = train_set.missing_bins
        self._missing_features = int((mb >= 0).sum())
        self._missing_bin = jnp.asarray(mb) if self._missing_features else None
        with telemetry.span("lgbm.setup.booster.learner"):
            self._learner_params = TreeLearnerParams.from_config(self.config)
            self._grower = self.select_grower()
            check_count_envelope(n, self.config.hist_dtype,
                                 self._count_shards())
            self._grow = self._create_tree_learner()
            # how _post_grow_step reads each row's leaf value, from the
            # leaf table's length alone
            telemetry.count(
                f"score.leaf_lookup.{leaf_lookup_path(self.max_leaves)}",
                self.max_leaves)
        # host transpose plus device_put, none waited for: host wall
        # time like every span; ``.shard`` where the rows go to the
        # shards of a mesh
        with telemetry.span("lgbm.setup.booster.upload"
                            if self._row_sharding is None
                            else "lgbm.setup.booster.shard"):
            self._nbpf = jnp.asarray(train_set.num_bins_per_feature)
            self._is_cat = jnp.asarray(train_set.is_categorical)
            self._real_feat = train_set.real_feature_indices
            self._bin_thresholds = train_set.bin_thresholds_real()
            self._bounds_mat, self._real_feat_dev = pack_threshold_bounds(
                self._bin_thresholds, self._real_feat)
            # device copy cached ON the dataset: cv folds / train_many
            # models constructed over the same BinnedDataset share one
            # upload.  Under a mesh learner the matrix goes to its shards
            # ONCE here, so the per-tree jit finds every operand already
            # in place.
            self._bins_T = train_set.dense_bins_T_device(self._bins_sharding)

            K = self.num_class
            init = train_set.metadata.init_score
            if init is not None:
                scores = np.asarray(init, np.float32).reshape(K, n)
            else:
                scores = np.zeros((K, n), np.float32)
            self._scores = self._by_row(scores)
            self._bag_mask = self._by_row(np.ones(n, np.float32))
            self._bag_cnt = n
            telemetry.count("setup.upload_bytes", sum(
                a.nbytes for a in (
                    self._bins_T, self._scores, self._bag_mask, self._nbpf,
                    self._is_cat, self._bounds_mat, self._real_feat_dev)))
        self._log_paths_once()
        # memory-census owner tags (obs/memory.py).  Getters resolve
        # the CURRENT attributes at census time, so the per-iteration
        # reassignment of _scores stays covered; the registry keeps
        # only a weakref to this booster, so dropping the booster
        # frees everything (the leak-detector contract).
        for tok in (getattr(self, "_mem_tokens", None) or ()):
            obs_memory.unregister_owner(tok)
        self._mem_tokens = (
            obs_memory.register_owner(
                "dataset", self,
                lambda b: (b._bins_T, b._nbpf, b._is_cat,
                           b._bounds_mat, b._real_feat_dev)),
            obs_memory.register_owner(
                "scores", self,
                lambda b: (b._scores, b._bag_mask,
                           getattr(b, "_valid_scores", []),
                           getattr(b, "_valid_bins", []))),
        )
        with telemetry.span("lgbm.setup.booster.metrics"):
            self.train_metrics = create_metrics(
                self.config, train_set.metadata, n
            )
        obs_memory.phase_boundary("binning")
        # rollback support: keep per-iteration train score deltas off-device?
        # cheaper: recompute on rollback from stored trees (rare path).

    @functools.cached_property
    def _chunking(self):
        """How the fused grower's kernels would walk this table's
        feature axis (learners/fused.py ``chunking``), once a booster:
        the selector's gate, the log line and the counters read it."""
        from ..learners import fused

        return fused.chunking(self.train_set.num_features, self._num_bins)

    def select_grower(self, row_mask: bool = False):
        """The ONE place that chooses between the two leaf-wise growers,
        from what it can observe.  Returns ``(which, why)``: ``"fused"``
        (learners/fused.py: float32 training on a TPU chip, or with
        ``tree_learner=data`` on the chips of one process, rows sharded
        over them: ``_mesh_devices``) or ``"canonical"``
        (learners/serial.py: everything else, the feature- and
        voting-parallel learners and more than one process among it)
        with the first condition that ruled the fused one out.  The raw
        ``[Fp, 4, Bp]`` histogram layout exists only inside the fused
        grower, so ``histogram_pool_size`` selects the canonical one.
        (learners/fused.py, and Pallas with it, is imported where it is
        first needed, as every kernel module is.)"""
        cfg = self.config
        F = self.train_set.num_features
        if not on_tpu():
            why = f"platform={device_platform()}"
        elif jax.process_count() > 1 or not (
                cfg.tree_learner in ("serial", "data")
                or len(jax.devices()) == 1):
            why = (f"tree_learner={cfg.tree_learner} over "
                   f"{jax.device_count()} devices"
                   + (f" of {jax.process_count()} processes"
                      if jax.process_count() > 1 else ""))
        elif not self._use_pallas_hist():
            why = f"hist_dtype={cfg.hist_dtype} hist_impl={cfg.hist_impl}"
        elif float(cfg.histogram_pool_size) > 0:
            why = f"histogram_pool_size={cfg.histogram_pool_size}"
        elif row_mask:
            why = "base row mask"
        else:
            plan = self._chunking
            if plan.fits:
                return "fused", ""
            why = (f"{F} features x {self._num_bins} bins: {plan.said}, "
                   "the accumulators of every chunk and the record's "
                   "blocks past the chip's VMEM")
        return "canonical", why

    def _mesh_devices(self) -> int:
        """The devices a mesh learner of this process spreads over: every
        local device, or ``num_machines`` of them where that is set."""
        nd = len(jax.devices())
        if self.config.num_machines > 1:
            nd = min(nd, self.config.num_machines)
        return nd

    def _count_shards(self) -> int:
        """How many shards the rows are dealt to where every histogram's
        count channel holds one shard's rows: the fused grower's chips
        under ``tree_learner=data`` (learners/fused.py sums the TREE's
        counts in int32), else 1."""
        if (self._grower[0] == "fused" and self.config.tree_learner == "data"
                and self._mesh_devices() > 1):
            return self._mesh_devices()
        return 1

    def _count_fused_plan(self) -> None:
        """What the fused grower's kernels walk, once a booster
        (obs/telemetry)."""
        from ..ops import record

        plan = self._chunking
        telemetry.count_many({
            "grow.feature_chunks": plan.feature_chunks,
            "grow.chunk_features": plan.chunk_features,
            "grow.hist_block_bytes": plan.hist_block_bytes,
            "grow.record_words": plan.record_words,
            "grow.split_tiles_per_step": record.split_tiles(
                plan.record_words),
            "grow.place_steps_per_tile": record.PLACE_STEPS_PER_TILE,
            "grow.place_launches_per_split":
                record.PLACE_LAUNCHES_PER_SPLIT,
            "grow.onehot_planes": plan.onehot_planes,
            "grow.categorical_features": int(
                self.train_set.is_categorical.sum()),
            "grow.missing_features": self._missing_features,
        })

    def _serial_leafwise_grower(self):
        """The grow callable of ``self._grower`` for serial leaf-wise
        growth."""
        if self._grower[0] == "fused":
            from ..learners import fused

            self._count_fused_plan()
            return functools.partial(
                fused.grow_tree,
                num_bins=self._num_bins,
                max_leaves=self.max_leaves,
                **self._missing_kw(),
            )
        return functools.partial(
            grow_tree,
            num_bins=self._num_bins,
            max_leaves=self.max_leaves,
            hist_fn=self._leafwise_hist_fn(),
            hist_pool=self._hist_pool_slots(),
            **self._missing_kw(),
        )

    def _missing_kw(self) -> dict:
        """The growers' ``missing_bin`` where the table has missing
        values, else nothing: a table without compiles the program it
        always did."""
        return {} if self._missing_bin is None else {
            "missing_bin": self._missing_bin}

    def _refuse_missing(self, learner: str) -> None:
        """A learner that cannot route a missing bin says so; it never
        reads the NaN as 0.0 behind the user's back."""
        if self._missing_bin is not None:
            raise ValueError(
                f"{self._missing_features} features have a missing bin "
                f"and {learner} cannot route missing values (only the "
                "serial learners and tree_learner=data on the fused "
                "grower can): train with use_missing=false, or with "
                "tree_learner=serial")

    def _create_tree_learner(self):
        """TreeLearner::CreateTreeLearner (tree_learner.cpp:8-20): map
        config.tree_learner to a grow callable.  All parallel variants run
        SPMD over the local device mesh — the reference's `num_machines`
        world (network.cpp:20-38) is the mesh's row axis."""
        tl = self.config.tree_learner
        if jax.process_count() > 1:
            # true multi-host world (Network::Init analog already ran,
            # parallel/multihost.py): rows are the per-process ingest
            # partition, collectives cross hosts over the global mesh.
            # This check precedes the serial branch — a "serial" learner
            # on per-process partitions would silently train on a
            # fraction of the data.
            from ..log import Log
            from ..parallel import data_mesh
            from ..parallel.multihost import make_multihost_data_parallel_grower

            if tl != "data":
                Log.warning(
                    f"tree_learner={tl} runs data-parallel across "
                    "processes (feature/voting sharding stays intra-host)"
                )
            self._refuse_missing("the multi-process data-parallel learner")
            from ..resilience.retry import collective_deadline_s

            self._learner_devices = jax.device_count()
            return make_multihost_data_parallel_grower(
                data_mesh(),  # all global devices
                num_bins=self._num_bins,
                max_leaves=self.max_leaves,
                sorted_hist=self._use_pallas_hist(),
                hist_pool=self._hist_pool_slots(),
                # the config's collective deadline guards the sentinel's
                # per-iteration allgather too (a preempted peer must
                # fail the world loudly, not hang it)
                collective_deadline=collective_deadline_s(self.config),
            )
        if tl == "serial" or len(jax.devices()) == 1:
            return self._serial_leafwise_grower()
        from ..parallel import (
            data_mesh,
            make_data_parallel_grower,
            make_feature_parallel_grower,
            make_voting_parallel_grower,
        )
        from ..parallel.mesh import ROW_AXIS

        nd = self._mesh_devices()
        mesh = data_mesh(num_devices=nd)
        if self._grower[0] != "fused":
            self._refuse_missing(f"tree_learner={tl} on the canonical grower")
        if tl == "feature":
            # every device holds all rows and searches a feature shard
            self._set_placement(mesh, None, 1)
            return make_feature_parallel_grower(
                mesh, num_bins=self._num_bins, max_leaves=self.max_leaves,
                sorted_hist=self._use_pallas_hist(),
                hist_pool=self._hist_pool_slots(),
            )
        self._set_placement(mesh, ROW_AXIS, nd)
        if self._grower[0] == "fused":
            from ..parallel.data_parallel import (
                make_fused_data_parallel_grower)

            self._count_fused_plan()
            telemetry.count_many({
                "dp.shards": nd,
                "dp.rows_per_shard": -(-self.train_set.num_data // nd),
                "dp.exchange_bytes_per_split": self._exchange_bytes(),
                "dp.collectives_per_split": 1,
            })
            return make_fused_data_parallel_grower(
                mesh, num_bins=self._num_bins, max_leaves=self.max_leaves,
                **self._missing_kw())
        if tl == "voting":
            return make_voting_parallel_grower(
                mesh,
                num_bins=self._num_bins,
                max_leaves=self.max_leaves,
                top_k=self.config.top_k,
                sorted_hist=self._use_pallas_hist(),
                hist_pool=self._hist_pool_slots(),
            )
        return make_data_parallel_grower(
            mesh,
            num_bins=self._num_bins,
            max_leaves=self.max_leaves,
            sorted_hist=self._use_pallas_hist(),
            hist_pool=self._hist_pool_slots(),
        )

    def _exchange_bytes(self) -> int:
        """Bytes of the one block the fused data-parallel grower sums over
        the chips a split: the smaller child's ``[NC * Fc, 4, Bp]``
        float32 histogram (learners/fused.py ``exchange``)."""
        plan = self._chunking
        return plan.feature_chunks * plan.hist_block_bytes

    def _set_placement(self, mesh, row_axis, row_shards: int) -> None:
        """Record how a mesh learner's operands lie over ``mesh``: rows
        split over ``row_axis`` (None = every device holds all rows).
        Rows that do not divide the shard count cannot be placed evenly;
        they stay on one device and the learner's own padding scatters
        them inside each tree's jit — said once, as a warning."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._learner_devices = mesh.size
        if row_axis is not None and self.train_set.num_data % row_shards:
            from ..log import Log

            Log.warning(
                f"num_data={self.train_set.num_data} is not a multiple of "
                f"the {row_shards} row shards: the binned matrix stays on "
                "one device and is re-scattered for every tree")
            return
        self._bins_sharding = NamedSharding(mesh, P(None, row_axis))
        self._row_sharding = NamedSharding(mesh, P(row_axis))

    def _replicated(self, a):
        """Valid-set operands: whole on every device of the learner's
        mesh, so the per-tree valid-score update moves nothing."""
        if self._row_sharding is None:
            return jnp.asarray(a)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            a, NamedSharding(self._row_sharding.mesh, P()))

    def _by_row(self, a):
        """A host or device array whose LAST axis is rows, put where the
        learner wants rows: on the default device, or over the mesh."""
        if self._row_sharding is None:
            return jnp.asarray(a)
        if np.ndim(a) == 1:
            return jax.device_put(a, self._row_sharding)
        return jax.device_put(a, self._bins_sharding)

    def _log_paths_once(self) -> None:
        """Say once per process, at INFO, which program this booster
        runs and where — so a log shows a CPU run (segment-sum
        histograms, interpreted kernels) for what it is, and a serial
        learner on a multi-chip host as the one-chip run it is."""
        from ..log import Log

        which, why = self._grower
        serial = self._learner_devices == 1
        if which == "fused":
            hist, search = "pallas raw-layout", "pallas (in the split step)"
            from ..ops import record

            K = record.split_tiles(self._chunking.record_words)
            part = (f"packed record, {self._chunking.said}, placement "
                    f"{record.PLACE_STEPS_PER_TILE} step a tile in "
                    f"{record.PLACE_LAUNCHES_PER_SPLIT} launch a split, "
                    f"split step {K} tile{'s' if K > 1 else ''} a grid step")
            if not serial:
                nd = self._learner_devices
                part += (f", rows over {nd} devices "
                         f"({-(-self.num_data // nd)} a shard), one all-reduce"
                         f" of {self._exchange_bytes()} B a split")
        else:
            hist = "pallas" if self._use_pallas_hist() else "segment-sum"
            search = ("pallas" if on_tpu() and not self._use_f64_hist
                      else "jnp")
            part = "row permutation" if serial else "packed record"
        msg = (f"platform={device_platform()} "
               f"devices={self._learner_devices} of {jax.device_count()} "
               f"tree_learner={self.config.tree_learner} "
               f"grower={which}{f' ({why})' if why else ''}: "
               f"histogram={hist}, search={search}, partition={part}, "
               f"score update by {leaf_lookup_path(self.max_leaves)} over "
               f"{self.max_leaves} leaves, "
               f"{int(self.train_set.is_categorical.sum())} of "
               f"{self.train_set.num_features} features categorical, "
               f"{self._missing_features} of {self.train_set.num_features} "
               "features with a missing bin"
               + (", pallas kernels interpreted" if not on_tpu()
                  and ("pallas" in hist or "record" in part) else ""))
        if msg not in _LOGGED_PATHS:
            _LOGGED_PATHS.add(msg)
            Log.info(msg)

    def _hist_pool_slots(self) -> int:
        """config.histogram_pool_size (MB) -> LRU slot count, the
        reference's sizing rule (serial_tree_learner.cpp:25-37): 0 means
        keep all num_leaves histograms resident.  Applies to every
        learner (serial and all mesh variants)."""
        mb = float(self.config.histogram_pool_size)
        if mb <= 0:
            return 0
        # a pool selects the canonical grower (select_grower), whose
        # slots are [F, B, 3]
        itemsize = 8 if self._use_f64_hist else 4
        per_leaf = (self.train_set.num_features * self._num_bins * 3
                    * itemsize)
        slots = int(mb * 1024 * 1024 / max(per_leaf, 1))
        return max(2, min(slots, self.max_leaves))

    def _use_matmul_hist(self) -> bool:
        impl = self.config.hist_impl
        return impl == "matmul" or (
            impl == "auto" and on_tpu()
        )

    def _use_pallas_hist(self) -> bool:
        """ONE eligibility rule for the f32 Pallas MXU histogram kernels:
        requested (or auto-on-TPU) and not overridden by the f64
        reference-parity accumulation mode."""
        return self._use_matmul_hist() and not self._use_f64_hist

    def _leafwise_hist_fn(self):
        """Histogram implementation for leaf-wise growth: the single-leaf
        MXU matmul kernel on TPU (the gathered smaller-child buffer is
        one leaf's rows, so no sort is needed), segment_sum elsewhere.
        The f64 reference-parity accumulation keeps segment_sum — the
        Pallas kernel is f32."""
        if self._use_pallas_hist():
            from ..ops.histogram import select_single_hist_fn

            return select_single_hist_fn(self._num_bins, True)
        return None  # grower's default segment_sum path

    def add_valid_dataset(self, valid_set: BinnedDataset, name: str) -> None:
        """GBDT::AddValidDataset (gbdt.cpp:124-140)."""
        assert self.train_set is not None and self.train_set.check_align(valid_set)
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        self.valid_metrics.append(
            create_metrics(self.config, valid_set.metadata, valid_set.num_data)
        )
        K = self.num_class
        vb = self._replicated(valid_set.dense_bins())
        init = valid_set.metadata.init_score
        if init is not None:
            vs = np.asarray(init, np.float32).reshape(K, valid_set.num_data)
        else:
            vs = np.zeros((K, valid_set.num_data), np.float32)
        if not hasattr(self, "_valid_bins"):
            self._valid_bins, self._valid_scores = [], []
        self._valid_bins.append(vb)
        self._valid_scores.append(self._replicated(vs))
        # replay existing model onto the new valid set (continued training)
        if self.models:
            n_iter = len(self.models) // K
            stacked = self._stacked_models(n_iter * K, grouped=True)
            step = self._iter_chunk(valid_set.num_data)
            acc = self._valid_scores[-1]
            for lo in range(0, n_iter, step):  # per-dispatch bound,
                # see _iter_chunk
                part = jax.tree.map(lambda a: a[lo:lo + step], stacked)
                acc = acc + ensemble_sum_binned(part, vb)
            self._valid_scores[-1] = acc

    # ---------------------------------------------------------------- bagging
    def set_base_row_mask(self, mask) -> None:
        """Persistent row mask ANDed under any bagging draw — how cv()
        trains each fold on the SHARED full binned matrix: the fold's
        held-out rows never enter histograms/counts, so the grown trees
        are bitwise the subset-trained ones (same nonzero contributions
        in the same row order; engine.cv, docs/forest_batching.md).

        Requires serial leaf-wise growth, and selects the canonical
        grower for it: the child-choice criterion switches to masked
        counts (choice_by_mask_counts in learners/serial.py explains why
        positional counts would break the subset-parity contract)."""
        if self._learner_devices > 1 or not (
                self._grower[0] == "fused"
                or getattr(self._grow, "func", None) is grow_tree):
            raise ValueError(
                "set_base_row_mask requires the serial leaf-wise tree "
                "learner"
            )
        m = jnp.asarray(mask, jnp.float32)
        self._base_row_mask = m
        self._bag_mask = self._bag_mask * m
        self._bag_cnt = int(jnp.sum(self._bag_mask))
        self._grower = self.select_grower(row_mask=True)
        self._grow = functools.partial(
            self._serial_leafwise_grower(), choice_by_mask_counts=True)

    def _update_bagging(self) -> None:
        """GBDT::Bagging (gbdt.cpp:157-208): every bagging_freq iterations
        draw floor(n * bagging_fraction) rows (query-granular for ranking)."""
        cfg = self.config
        if cfg.bagging_fraction >= 1.0 or cfg.bagging_freq <= 0:
            return
        if self.iter_ % cfg.bagging_freq != 0:
            return
        n = self.num_data
        meta = self.train_set.metadata
        if meta.query_boundaries is not None:
            qb = np.asarray(meta.query_boundaries)
            nq = len(qb) - 1
            take = int(nq * cfg.bagging_fraction)
            qs = self._bag_rng.choice(nq, size=take, replace=False)
            mask = np.zeros(n, np.float32)
            for q in qs:
                mask[qb[q] : qb[q + 1]] = 1.0
        else:
            take = int(n * cfg.bagging_fraction)
            idx = self._bag_rng.choice(n, size=take, replace=False)
            mask = np.zeros(n, np.float32)
            mask[idx] = 1.0
        base = getattr(self, "_base_row_mask", None)
        if base is not None:
            mask = mask * np.asarray(base)
        self._bag_mask = self._by_row(mask)
        self._bag_cnt = int(mask.sum())

    def _sample_features(self) -> jax.Array:
        """Per-tree feature_fraction sample (serial_tree_learner.cpp:160-165)."""
        F = self.train_set.num_features
        frac = float(self.config.feature_fraction)
        if frac >= 1.0:
            return jnp.ones(F, bool)
        take = max(1, int(F * frac))
        idx = self._feat_rng.choice(F, size=take, replace=False)
        mask = np.zeros(F, bool)
        mask[idx] = True
        return jnp.asarray(mask)

    # ------------------------------------------------------------------ train
    def train_one_iter(
        self,
        grad: Optional[np.ndarray] = None,
        hess: Optional[np.ndarray] = None,
    ) -> bool:
        """One boosting iteration (gbdt.cpp:217-252).  Returns True when no
        tree could be grown (training should stop).

        Telemetry: counts the iteration and records its host wall into
        the ``tree_dispatch_s`` reservoir.  That is DISPATCH time —
        under async dispatch the call returns before the chip finishes,
        so per-tree p50/p99 from this reservoir measure how fast the
        host can feed the device, not device time (the distinction the
        jaxlint ``wallclock-without-sync`` rule exists to protect).
        Synced per-tree times come from the bench harness's own timed
        loop; device time by scope from obs.device_time.  Every
        statement of an iteration is under a host span
        (``lgbm.host.gradients``, ``.sample``, ``.grow``,
        ``.stop_check``, ``.post_grow``, ``.book``): with a profiler
        session open they stand on the trace's host plane, where
        obs.device_time puts the device's idle gaps down to them.
        Iteration 0 of a booster, where every program traces and
        compiles, is the span ``lgbm.setup.first_iter`` besides."""
        if self.iter_ == 0:
            with telemetry.span("lgbm.setup.first_iter"):
                return self._booked_iter(grad, hess)
        return self._booked_iter(grad, hess)

    def _booked_iter(self, grad, hess) -> bool:
        t0 = time.perf_counter()
        try:
            # chaos hook (LGBM_TPU_FAULT=oom_dispatch): fake
            # RESOURCE_EXHAUSTED through the same classifier a real one hits
            faults.maybe_oom_dispatch("train")
            return self._train_one_iter_impl(grad, hess)
        except Exception as e:
            # OOM post-mortem (obs/memory.py): flight-recorder dump with
            # the last census + the analytic model's prediction for this
            # shape; non-OOM errors pass through untouched
            obs_memory.classify_dispatch_error(
                e, "train.dispatch", shape=self._memmodel_params(),
                predict_params=self._memmodel_params())
            raise
        finally:
            with telemetry.span("lgbm.host.book"):
                telemetry.count("train_iters")
                telemetry.record_value(
                    "tree_dispatch_s", time.perf_counter() - t0)
                obs_memory.phase_boundary("train")

    def _memmodel_params(self) -> Optional[dict]:
        """This booster's shape in obs/memmodel.predict vocabulary
        (attached to OOM post-mortems so the dump carries the expected
        footprint beside the measured census)."""
        if getattr(self, "_bins_T", None) is None:
            return None
        try:
            return {
                "rows": int(self.num_data),
                "features": int(self._bins_T.shape[0]),
                "bins": int(self._num_bins),
                "leaves": int(self.max_leaves),
                "num_class": int(self.num_class),
                "world": int(jax.process_count()),
                "routing": ("order" if self.config.tree_learner == "serial"
                            else "prefix"),
                "hist_prec": ("float64" if self._use_f64_hist
                              else "float32"),
            }
        except Exception:
            return None

    # -------------------------------------------- forest-batched dispatch
    def _forest_eligible(self) -> bool:
        """May this booster's trees grow through the batched forest path
        (learners/forest.py)?  Mirrors the canonical serial branch of
        _create_tree_learner: single-process leaf-wise growth with the
        segment-sum histograms and jnp search — the op set the explicit
        batched loop reproduces bitwise.  Kernel paths (the fused
        grower, Pallas histograms), f64 accumulation, pooled histograms
        and parallel learners fall back to the sequential grower;
        whether vmap pessimizes those kernels has not been measured on
        the chip (docs/forest_batching.md)."""
        cfg = self.config
        knob = getattr(cfg, "forest_batching", "auto")
        if knob == "off":
            return False
        if not (cfg.tree_learner == "serial" or len(jax.devices()) == 1):
            return False
        if jax.process_count() > 1:
            return False
        if self._use_f64_hist or self._hist_pool_slots():
            return False
        if self._missing_bin is not None:
            # learners/forest.py has no missing-value routing
            return False
        if (self._grower[0] == "fused"
                or self._leafwise_hist_fn() is not None):
            return False
        if knob == "on":
            return True
        # auto: the batched loop's per-split work is O(n) per lane while
        # the sequential windows tier down — measured CPU crossover sits
        # between 2k rows (1.45x) and 4k rows (0.64x); docs carry the
        # sweep.  forest_batching="on" overrides for chip re-evaluation.
        return self.num_data <= _FOREST_AUTO_MAX_ROWS

    def _grow_forest_batched(self, grads, hesses, bag_masks, fmasks,
                             params_lanes):
        """One batched dispatch growing ``B = len(fmasks)`` trees.
        Operands are [B, ...] stacks (grad/hess/bag per lane, feature
        mask per lane, TreeLearnerParams with [B] fields).  Returns the
        batched Tree pytree + leaf_id[B, n]."""
        from ..learners import forest

        gf = forest.make_grow_forest(
            self._num_bins, self.max_leaves,
            choice_by_mask_counts=(
                getattr(self, "_base_row_mask", None) is not None),
        )
        trees, leaf_ids = gf(
            self._bins_T, grads, hesses, bag_masks, fmasks,
            self._nbpf, self._is_cat, params_lanes,
        )
        telemetry.count("forest_dispatches")
        telemetry.count("forest_batched_trees", int(leaf_ids.shape[0]))
        return trees, leaf_ids

    def _forest_begin_iter(self, grad=None, hess=None):
        """First half of a boosting iteration, up to (not including) the
        tree growth: lagged-stop drain, objective gradients, non-finite
        guard, bagging, per-class feature samples.  Returns "stop",
        "skip", or (grad[K, n], hess[K, n], fmasks, nf_snap).  Factored
        out of _train_one_iter_impl so train_forest_round can stack the
        grow work of MANY boosters into one dispatch between identical
        begin/finish halves."""
        K = self.num_class
        # lagged stop check, consume side: BEFORE growing anything this
        # iteration, materialize parked num_leaves values that are now
        # ``lag`` iterations old (computed long ago — the int() does not
        # stall the pipeline).  On terminal detection, roll back every
        # iteration AFTER the terminal stump — the popped entries map
        # one-to-one onto the trees grown after it and nothing from the
        # current call has run yet — leaving the model IDENTICAL to the
        # eager check's (gbdt.cpp:217-252 stops right at the stump).
        while self._pending_stop and len(self._pending_stop) >= max(
            self._stop_lag, 1
        ):
            old = self._pending_stop.pop(0)
            telemetry.host_sync()  # lagged, so ~free — but still a sync
            with telemetry.span("lgbm.host.stop_check"):
                terminal = int(old) <= 1
            if terminal:
                for _ in range(len(self._pending_stop)):
                    self.rollback_one_iter()
                self._pending_stop.clear()
                return "stop"
        if grad is None or hess is None:
            # the span holds the slice before and the reshapes after: each
            # is a program launch of its own (0.4-1.3 ms of host time on the
            # chip, PERF.md section 6, PR 36)
            with telemetry.span("lgbm.host.gradients"):
                scores = self._scores if K > 1 else self._scores[0]
                grad, hess = self.objective.get_gradients(scores)
                if K == 1:
                    grad, hess = grad[None, :], hess[None, :]
        else:
            grad = jnp.asarray(grad, jnp.float32).reshape(K, self.num_data)
            hess = jnp.asarray(hess, jnp.float32).reshape(K, self.num_data)

        if self._row_sharding is not None:
            # no-op when the objective's output already lies by row
            grad, hess = self._by_row(grad), self._by_row(hess)

        # chaos hook (LGBM_TPU_FAULT=nan_grads:J): deterministic gradient
        # poisoning, so the guard below is exercised by tests, not trusted
        grad, hess = faults.poison_grads(grad, hess, self.iter_)
        nf_snap = None
        if self._nf_guard is not None:
            if self._nf_guard.policy == "raise":
                # pre-iteration snapshot: the only rollback that works
                # once NaN reaches the score buffers is an exact restore
                # (see NonFiniteGuard.raise_if_poisoned).  One async
                # device copy of the score buffers per iteration — the
                # opt-in policy's cost, never the default path's.
                nf_snap = self.snapshot_state()
            grad, hess, skip_iter = self._nf_guard.check_gradients(grad, hess)
            if skip_iter:
                return "skip"

        with telemetry.span("lgbm.host.sample"):
            self._update_bagging()
            # per-class feature samples drawn in k-order BEFORE any growth:
            # same _feat_rng consumption sequence as the sequential k-loop
            # (nothing else draws between them), so stacked == loop trees
            fmasks = [self._sample_features() for _ in range(K)]
        return grad, hess, fmasks, nf_snap

    def _forest_finish_tree(self, k: int, tree, leaf_id) -> bool:
        """Second half, per grown tree: lagged-stop bookkeeping,
        non-finite leaf guard, shrinkage + score/threshold dispatch,
        valid-score updates, model append.  Returns could_split."""
        K = self.num_class
        if self._stop_lag <= 0 or K != 1:
            with telemetry.span("lgbm.host.stop_check"):
                could_split = int(tree.num_leaves) > 1
        else:
            # lagged stop check (LGBM_TPU_STOP_LAG): int(num_leaves)
            # every iteration blocks the host on the WHOLE tree
            # computation, draining the dispatch pipeline (cost on
            # this machine: not measured; ROADMAP queue 1 item 3).
            # Park the device scalar and start its host
            # copy; the NEXT call materializes values that are
            # ``lag`` iterations old (see _forest_begin_iter) and
            # rolls back to the exact eager-mode state on terminal
            # detection.
            nl = tree.num_leaves
            try:
                nl.copy_to_host_async()
            except Exception:
                pass
            self._pending_stop.append(nl)
            could_split = True
        if self._nf_guard is not None:
            # leaf-output guard (clip/count); never drops a tree —
            # the models list must stay iter-major K-aligned
            tree, _ = self._nf_guard.check_tree(tree)
        # shrink + score apply + threshold finalization as ONE
        # dispatch (each eager jnp op is its own launch; the host-side
        # finalize_thresholds even forced a full device sync per tree)
        with telemetry.span("lgbm.host.post_grow"):
            tree, self._scores = _post_grow_step(
                tree, self._scores, jnp.int32(k),
                leaf_id, jnp.float32(self.learning_rate),
                self._bounds_mat, self._real_feat_dev,
            )
            for vi in range(len(self.valid_sets)):
                self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                    predict_binned(tree, self._valid_bins[vi],
                                   self._missing_bin)
                )
        with telemetry.span("lgbm.host.book"):
            self.models.append(tree)
        return could_split

    def _forest_finish_iter(self, grown, nf_snap) -> bool:
        """Close an iteration whose K trees were grown elsewhere (the
        batched dispatch).  ``grown`` is [(tree, leaf_id)] in class
        order.  Returns True when training should stop."""
        could_split_any = False
        for k, (tree, leaf_id) in enumerate(grown):
            if self._forest_finish_tree(k, tree, leaf_id):
                could_split_any = True
        self.iter_ += 1
        self._model_version += 1
        if self._nf_guard is not None:
            self._nf_guard.raise_if_poisoned(self, nf_snap)
        return not could_split_any

    def _train_one_iter_impl(
        self,
        grad: Optional[np.ndarray] = None,
        hess: Optional[np.ndarray] = None,
    ) -> bool:
        K = self.num_class
        pre = self._forest_begin_iter(grad, hess)
        if pre == "stop":
            return True
        if pre == "skip":
            return False
        grad, hess, fmasks, nf_snap = pre

        if K > 1 and self._forest_eligible():
            # multiclass: the K per-class trees of ONE iteration share
            # grad/hess batches and the bagging mask already — grow all
            # K in one batched dispatch (ROADMAP item 2), bitwise the
            # sequential k-loop's trees (tier-1 pins this)
            from ..learners import forest

            params_lanes = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (K,)), self._learner_params)
            with telemetry.span("lgbm.host.grow"):
                trees_b, lids = self._grow_forest_batched(
                    grad, hess,
                    jnp.broadcast_to(self._bag_mask, (K, self.num_data)),
                    jnp.stack(fmasks), params_lanes,
                )
            grown = [(forest.unstack_tree(trees_b, k), lids[k])
                     for k in range(K)]
            return self._forest_finish_iter(grown, nf_snap)

        could_split_any = False
        for k in range(K):
            fmask = fmasks[k]
            with telemetry.span("lgbm.host.grow"):
                if self._use_f64_hist:
                    with jax.enable_x64(True):
                        gk = grad[k].astype(jnp.float64)
                        hk = hess[k].astype(jnp.float64)
                        tree, leaf_id = self._grow(
                            self._bins_T, gk, hk, self._bag_mask, fmask,
                            self._nbpf, self._is_cat, self._learner_params,
                        )
                        tree = jax.tree.map(
                            lambda a: a.astype(jnp.float32)
                            if a.dtype == jnp.float64 else a,
                            tree,
                        )
                else:
                    tree, leaf_id = self._grow(
                        self._bins_T, grad[k], hess[k], self._bag_mask,
                        fmask, self._nbpf, self._is_cat,
                        self._learner_params,
                    )
            if self._forest_finish_tree(k, tree, leaf_id):
                could_split_any = True
        self.iter_ += 1
        self._model_version += 1
        if self._nf_guard is not None:
            # policy=raise drains its parked device counts here — the
            # iteration's end, where the eager stop check already synced
            self._nf_guard.raise_if_poisoned(self, nf_snap)
        return not could_split_any

    def finish_lagged_stop(self) -> None:
        """Drain the lagged stop check's parked values after the LAST
        train_one_iter call.  When training ends by iteration count, the
        parked num_leaves of the final ``lag`` iterations were never
        materialized; a terminal stump among them means later iterations
        must be rolled back to restore the eager-mode model.  No-op
        without LGBM_TPU_STOP_LAG."""
        while self._pending_stop:
            old = self._pending_stop.pop(0)
            telemetry.host_sync()
            with telemetry.span("lgbm.host.stop_check"):
                terminal = int(old) <= 1
            if terminal:
                for _ in range(len(self._pending_stop)):
                    self.rollback_one_iter()
                self._pending_stop.clear()
                break

    def finalize_guards(self) -> None:
        """End-of-training drain of the non-finite guard's lazily
        accumulated counts (policy=clip batches device fetches; without
        this drain a short run would report zero clipped values and the
        degradation would be invisible).  Under policy=raise a pending
        poisoned final iteration surfaces here as NonFiniteError."""
        if self._nf_guard is not None:
            self._nf_guard.finalize()

    def snapshot_state(self) -> tuple:
        """Capture every per-iteration mutable of the training state
        for an EXACT rewind (restore_state).  Unlike rollback_one_iter
        — whose (s + d) - d float32 round trip leaves ulp residue in
        the scores — restore is bit-exact: the score buffers are device
        COPIES (a bare reference would be donated into the next
        _post_grow_step and deleted).  Used by bench.py to discard
        warm-up trees so the timed model is byte-identical to a fresh
        one.  Keep this field list in sync with train_one_iter's state
        mutations."""
        return (
            jnp.array(self._scores),
            len(self.models),
            self.iter_,
            self._bag_rng.get_state(),
            self._feat_rng.get_state(),
            self._bag_mask,  # immutable and never donated: ref is safe
            self._bag_cnt,
            [jnp.array(v) for v in getattr(self, "_valid_scores", [])],
            # parked lagged-stop scalars (LGBM_TPU_STOP_LAG): device
            # scalars, never donated — the shallow copy suffices
            list(self._pending_stop),
        )

    def restore_state(self, snap: tuple) -> None:
        """Rewind to a snapshot_state() capture (see its contract).
        Restores COPIES of the score buffers so the snapshot stays
        reusable — installing the captured array itself would let the
        next _post_grow_step donate-and-delete it, making a second
        restore crash on a deleted buffer."""
        (scores, n_models, it, bag_state, feat_state, bag_mask,
         bag_cnt, valid_scores, pending_stop) = snap
        self._scores = jnp.array(scores)
        del self.models[n_models:]
        self.iter_ = it
        self._bag_rng.set_state(bag_state)
        self._feat_rng.set_state(feat_state)
        self._bag_mask = bag_mask
        self._bag_cnt = bag_cnt
        for i, v in enumerate(valid_scores):
            self._valid_scores[i] = jnp.array(v)
        self._pending_stop[:] = pending_stop
        self._model_version += 1

    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:254-271): subtract the last
        iteration's trees from all scores and pop them."""
        if self.iter_ <= 0:
            return
        K = self.num_class
        last = self.models[-K:]
        # any rollback invalidates the parked lagged-stop values: their
        # indices no longer line up with self.models (the detection path
        # clears this anyway; external callers get a fresh start —
        # a still-terminal state is simply re-detected a lag later)
        self._pending_stop.clear()
        for k, tree in enumerate(last):
            # negative shrinkage = subtraction
            delta = predict_binned(tree, self._bins_T.T, self._missing_bin)
            self._scores = self._scores.at[k].add(-delta)
            for vi in range(len(self.valid_sets)):
                self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                    -predict_binned(tree, self._valid_bins[vi],
                                    self._missing_bin)
                )
        del self.models[-K:]
        self.iter_ -= 1
        self._model_version += 1

    # ------------------------------------------------------------------- eval
    def eval_at(self, data_idx: int, only=None) -> Dict[str, float]:
        """Metric evaluation: data_idx 0 = train, 1.. = valid sets
        (GBDT::GetPredictAt semantics, gbdt.cpp:388-426).  ``only``
        restricts to a set of metric names (callers that handle
        multi-position metrics themselves skip them here)."""
        if data_idx == 0:
            scores, metrics = self._scores, self.train_metrics
        else:
            scores = self._valid_scores[data_idx - 1]
            metrics = self.valid_metrics[data_idx - 1]
        dev = scores if self.num_class > 1 else scores[0]
        out: Dict[str, float] = {}
        if only is not None:
            metrics = [m for m in metrics if m.name in only]
        # ALL device-path metric evals dispatch first (scores stay in
        # HBM, each returns an async device scalar), host-path metrics
        # run next behind ONE score materialization, and a single
        # device_get drains the pending scalars last — the previous
        # per-metric float() paid one pipeline-draining sync per metric
        # per iteration (jaxlint host-sync-in-loop; the stall class the
        # lagged stop check exists for), and materializing host scores
        # BEFORE dispatching would re-serialize the same pipeline
        pending: Dict[str, object] = {}
        host_metrics: List[Metric] = []
        for m in metrics:
            out[m.name] = float("nan")  # placeholder keeps dict order
            if m.eval_jax is not None:
                pending[m.name] = m.eval_jax_jit(dev)
            else:
                host_metrics.append(m)
        if host_metrics:
            telemetry.host_sync()
            host = np.asarray(dev)
            for m in host_metrics:
                out[m.name] = m.eval(host)
        if pending:
            telemetry.host_sync()
            for name, val in zip(pending,
                                 jax.device_get(list(pending.values()))):
                out[name] = float(val)
        return out

    def predict_at(self, data_idx: int) -> np.ndarray:
        scores = self._scores if data_idx == 0 else self._valid_scores[data_idx - 1]
        return np.asarray(scores)

    # ---------------------------------------------------------------- predict
    def _versioned_cache(self, attr: str, key, build):
        """Model-version-keyed memo shared by the stack and table
        caches: one copy of the invalidation protocol (the explicit
        _model_version counter, bumped by every mutation of
        ``self.models``)."""
        version = getattr(self, "_model_version", 0)
        cache = getattr(self, attr, None)
        if cache is None or cache[0] != version:
            cache = (version, {})
            setattr(self, attr, cache)
        if key not in cache[1]:
            cache[1][key] = build()
        return cache[1][key]

    def _stacked_models(self, n_trees: int, grouped: bool):
        """Stack the first ``n_trees`` trees into one batched Tree pytree
        (leading axis [T], or [T//K, K] when ``grouped``)."""

        def build():
            stacked = stack_trees(self.models[:n_trees])
            if grouped:
                K = self.num_class
                stacked = jax.tree.map(
                    lambda a: a.reshape((n_trees // K, K) + a.shape[1:]),
                    stacked,
                )
            return stacked

        return self._versioned_cache("_stack_cache", (n_trees, grouped), build)

    def _stacked_tables(self, n_trees: int, grouped: bool):
        """Path-incidence tables (ops/predict_matmul.py) for the stacked
        model — cached next to the stack under the same version key."""

        def build():
            from ..ops.predict_matmul import build_path_tables

            return build_path_tables(self._stacked_models(n_trees, grouped))

        return self._versioned_cache("_table_cache", (n_trees, grouped), build)

    def _iter_chunk(self, n_rows: int) -> int:
        """Boosting iterations per prediction dispatch: the ensemble walk
        does O(rows * TREES * depth) indexed gathers in one device
        program.  Bound rows*TREES per dispatch — each iteration is
        num_class trees — and accumulate the chunks' partial sums on
        device.  The bound dates from a machine that is gone, where one
        program of 1M rows x 100 trees ran for minutes and was killed;
        whether this machine needs it is not measured.  The constant
        moves predict time (it also sizes the matmul path's tree chunks),
        so it stays until a predict cell measures it."""
        return max(1, 16_000_000 // max(n_rows * self.num_class, 1))

    def _raw_scores(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """Whole-ensemble prediction in tree-chunked device programs
        (stacked-tree scan, models/tree.py ensemble_sum_raw) — replaces
        the reference's per-tree per-row traversal loop
        (gbdt.cpp:388-426)."""
        K = self.num_class
        n_iter = len(self.models) // K
        if num_iteration > 0:
            n_iter = min(n_iter, num_iteration)
        X = jnp.asarray(np.ascontiguousarray(X, np.float32))
        if n_iter == 0:
            return np.zeros((K, X.shape[0]), np.float64)
        stacked = self._stacked_models(n_iter * K, grouped=True)
        if _use_matmul_predict():
            from ..ops.predict_matmul import ensemble_sum_matmul

            tables = self._stacked_tables(n_iter * K, grouped=True)
            # tree-chunking: no long per-row serial walk, so each
            # dispatch carries ~10x the walk path's rows*trees budget
            # (see _iter_chunk).  ROW-chunking
            # bounds the per-tree dense intermediates (vals/go/match are
            # [rows, L]-shaped, ~2.5KB/row at L=255 — 10M rows would
            # OOM a 16GB chip without it).
            step = max(1, 10 * self._iter_chunk(min(X.shape[0], _ROW_CHUNK)))
            parts = []
            for rlo in range(0, X.shape[0], _ROW_CHUNK):
                Xc = X[rlo:rlo + _ROW_CHUNK]
                acc = None
                for lo in range(0, n_iter, step):
                    part = jax.tree.map(lambda a: a[lo:lo + step], stacked)
                    tpart = jax.tree.map(lambda a: a[lo:lo + step], tables)
                    out = ensemble_sum_matmul(tpart, part, Xc)
                    acc = out if acc is None else acc + out
                # per-chunk materialization IS the product here (the
                # chunking exists to bound device memory)
                parts.append(np.asarray(acc, np.float64))  # jaxlint: disable=host-sync-in-loop
            return np.concatenate(parts, axis=1)
        step = self._iter_chunk(X.shape[0])
        acc = None
        for lo in range(0, n_iter, step):
            part = jax.tree.map(lambda a: a[lo:lo + step], stacked)
            out = ensemble_sum_raw(part, X)
            acc = out if acc is None else acc + out
        return np.asarray(acc, np.float64)

    def predict_raw_score(self, X, num_iteration: int = -1) -> np.ndarray:
        return raw_score_output(self._raw_scores(X, num_iteration),
                                self.num_class)

    def predict(self, X, num_iteration: int = -1) -> np.ndarray:
        """With transform (GBDT::Predict, gbdt.cpp:631-645)."""
        return transform_scores(self._raw_scores(X, num_iteration),
                                self.num_class, self.sigmoid,
                                self.objective_name())

    def predict_leaf_index(self, X, num_iteration: int = -1) -> np.ndarray:
        K = self.num_class
        n_iter = len(self.models) // K
        if num_iteration > 0:
            n_iter = min(n_iter, num_iteration)
        X = jnp.asarray(np.ascontiguousarray(X, np.float32))
        if n_iter == 0:
            return np.zeros((X.shape[0], 0), np.int32)
        stacked = self._stacked_models(n_iter * K, grouped=False)
        # flat tree-major stack: _iter_chunk already accounts for K
        step = max(K, self._iter_chunk(X.shape[0]) * K)
        if _use_matmul_predict():
            from ..ops.predict_matmul import ensemble_leaves_matmul

            tables = self._stacked_tables(n_iter * K, grouped=False)
            step *= 10  # no serial walk per dispatch; see _raw_scores
            parts = []
            for rlo in range(0, X.shape[0], _ROW_CHUNK):
                Xc = X[rlo:rlo + _ROW_CHUNK]
                outs = []
                for lo in range(0, n_iter * K, step):
                    part = jax.tree.map(lambda a: a[lo:lo + step], stacked)
                    tpart = jax.tree.map(lambda a: a[lo:lo + step], tables)
                    # chunked materialization bounds device memory
                    outs.append(np.asarray(  # jaxlint: disable=host-sync-in-loop
                        ensemble_leaves_matmul(tpart, part, Xc)))
                parts.append(np.concatenate(outs, axis=0))
            return np.concatenate(parts, axis=1).T
        outs = []
        for lo in range(0, n_iter * K, step):
            part = jax.tree.map(lambda a: a[lo:lo + step], stacked)
            # chunked materialization bounds device memory
            outs.append(np.asarray(ensemble_leaves_raw(part, X)))  # jaxlint: disable=host-sync-in-loop
        return np.concatenate(outs, axis=0).T

    def objective_name(self) -> str:
        if self.objective is not None:
            return self.objective.name
        return getattr(self, "_loaded_objective", "")

    # ------------------------------------------------------------- model text
    def feature_importance(self) -> Dict[str, int]:
        """Split-count importance keyed by name (gbdt.cpp:594-619)."""
        imp = self.feature_importance_array("split")
        names = self.feature_names or [
            f"Column_{i}" for i in range(self.max_feature_idx + 1)
        ]
        return {names[i]: int(imp[i]) for i in range(len(imp)) if imp[i] > 0}

    def _lagged_terminal_drop(self) -> int:
        """Number of TRAILING trees a finish_lagged_stop() drain would
        roll back, computed WITHOUT mutating state: the parked values are
        synced (a save reads host arrays anyway) but nothing is popped —
        a mid-training checkpoint must not yank trees out from under the
        running train loop (ADVICE r3 / review r4)."""
        for i, old in enumerate(self._pending_stop):
            if int(old) <= 1:
                return (len(self._pending_stop) - 1 - i) * self.num_class
        return 0

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        """Reference text format (gbdt.cpp:479-521).  With a lagged stop
        check (LGBM_TPU_STOP_LAG) active, trees a future drain would roll
        back are excluded from the STRING only — in-memory state is not
        touched, so checkpoint-every-iteration callbacks stay safe."""
        out = [self.name]
        out.append(f"num_class={self.num_class}")
        out.append(f"label_index={self.label_idx}")
        out.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective_name():
            out.append(f"objective={self.objective_name()}")
        out.append(f"sigmoid={_fmt(self.sigmoid)}")
        names = self.feature_names or [
            f"Column_{i}" for i in range(self.max_feature_idx + 1)
        ]
        out.append("feature_names=" + " ".join(names))
        out.append("")
        num_used = len(self.models) - self._lagged_terminal_drop()
        if num_iteration > 0:
            num_used = min(num_iteration * self.num_class, num_used)
        for i in range(num_used):
            out.append(f"Tree={i}")
            out.append(_tree_to_string(self.models[i]))
        out.append("")
        out.append("feature importances:")
        pairs = sorted(self.feature_importance().items(), key=lambda kv: -kv[1])
        for name, cnt in pairs:
            out.append(f"{name}={cnt}")
        return "\n".join(out) + "\n"

    def save_model_to_file(self, filename: str, num_iteration: int = -1) -> None:
        # atomic + checksummed: a preemption mid-save must never leave a
        # truncated model (which would silently LOAD, with fewer trees)
        # under the real name; the .sha256 sidecar makes "is this model
        # intact?" checkable (resilience/atomic.py)
        atomic_write(filename, self.save_model_to_string(num_iteration),
                     checksum=True)

    def load_model_from_string(self, model_str: str) -> None:
        """gbdt.cpp:523-592."""
        lines = model_str.splitlines()
        kv = {}
        tree_blocks: List[List[str]] = []
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree="):
                i += 1
                block = []
                while i < len(lines) and not lines[i].startswith("Tree=") and not lines[
                    i
                ].startswith("feature importances"):
                    block.append(lines[i])
                    i += 1
                tree_blocks.append(block)
                continue
            if "=" in line:
                k, v = line.split("=", 1)
                kv.setdefault(k.strip(), v.strip())
            i += 1
        self.num_class = int(kv.get("num_class", 1))
        self.label_idx = int(kv.get("label_index", 0))
        self.max_feature_idx = int(kv.get("max_feature_idx", -1))
        self.sigmoid = float(kv.get("sigmoid", -1.0))
        self._loaded_objective = kv.get("objective", "")
        self.feature_names = kv.get("feature_names", "").split()
        self.models = [_tree_from_lines(b) for b in tree_blocks]
        self._model_version = getattr(self, "_model_version", 0) + 1
        self.num_init_iteration = len(self.models) // max(self.num_class, 1)
        self.iter_ = 0

    def merge_from(self, other: "GBDT", prepend: bool = False) -> None:
        """GBDT::MergeFrom (gbdt.h:44-61): concatenate another model's
        trees.  ``prepend=True`` puts the other model first (continued
        training from ``input_model``, gbdt.cpp:589-592) and replays its
        predictions into the current train/valid scores."""
        if other.num_class != self.num_class:
            raise ValueError("cannot merge models with different num_class")
        K = self.num_class
        incoming = list(other.models)
        if self.train_set is not None:
            # re-bind foreign trees into THIS dataset's bin space so every
            # stored model is safe for predict_binned (valid-set replay in
            # add_valid_dataset, score updates here)
            incoming = [self._rebind_tree(t) for t in incoming]
        if prepend:
            self.models = incoming + self.models
            self._model_version += 1
            self.num_init_iteration = len(incoming) // K
            # replay other's trees into live scores (init_score seeding,
            # application.cpp:110-115)
            if self.train_set is not None and incoming:
                stacked = stack_trees(incoming)
                stacked = jax.tree.map(
                    lambda a: a.reshape((len(incoming) // K, K) + a.shape[1:]),
                    stacked,
                )
                self._scores = self._scores + ensemble_sum_binned(
                    stacked, self._bins_T.T
                )
                for vi in range(len(self.valid_sets)):
                    self._valid_scores[vi] = self._valid_scores[vi] + (
                        ensemble_sum_binned(stacked, self._valid_bins[vi])
                    )
        else:
            self.models = self.models + incoming
            self._model_version += 1
        self.iter_ = len(self.models) // K - self.num_init_iteration

    def _rebind_tree(self, tree: Tree) -> Tree:
        """Map a tree from another model into THIS dataset's bin space.

        The tree's own bin-space fields are never trusted — they belong to
        whatever dataset the tree was trained on.  Only threshold_real /
        split_feature_real (the raw-value decision program the reference
        also uses for loaded models, tree.h:226-238) are consulted.
        """
        nl = int(tree.num_leaves)
        if nl <= 1:
            return tree
        sf = np.asarray(tree.split_feature_real)
        tr = np.asarray(tree.threshold_real)
        dt = np.asarray(tree.decision_type)
        num_bins = self._num_bins
        tb = np.zeros(tree.threshold_bin.shape, np.int32)
        sf_inner = np.zeros(sf.shape, np.int32)
        dt2 = dt.copy()
        for i in range(nl - 1):
            f_real = int(sf[i])
            if f_real < 0:
                continue
            inner = int(self.train_set.used_feature_map[f_real])
            if inner < 0:
                # feature is trivial (constant) here: we cannot evaluate
                # const <=/== threshold without the raw value, so force a
                # deterministic all-left route via an impossible-to-fail
                # numerical compare (bin <= num_bins)
                sf_inner[i] = 0
                tb[i] = num_bins
                dt2[i] = 0
                continue
            sf_inner[i] = inner
            mapper = self.train_set.bin_mappers[inner]
            if dt[i] & 1:  # categorical: threshold is the category id
                tb[i] = mapper.category_to_bin.get(int(tr[i]), num_bins)
            else:
                # threshold_real == bounds[threshold_bin]; recover the bin
                # as the first bound >= t (tolerating text-format fp noise)
                bounds = self._bin_thresholds[inner]
                eps = abs(tr[i]) * 1e-9 + 1e-12
                tb[i] = min(int(np.searchsorted(bounds, tr[i] - eps)), len(bounds) - 1)
        return tree._replace(
            split_feature=jnp.asarray(sf_inner),
            threshold_bin=jnp.asarray(tb),
            decision_type=jnp.asarray(dt2),
        )

    # ------------------------------------------------------------ JSON dump
    def dump_model(self, num_iteration: int = -1) -> Dict:
        """GBDT::DumpModel (gbdt.cpp:438-477): JSON-style dict."""
        names = self.feature_names or [
            f"Column_{i}" for i in range(self.max_feature_idx + 1)
        ]
        # same non-mutating guarantee as save_model_to_string
        num_used = len(self.models) - self._lagged_terminal_drop()
        if num_iteration > 0:
            num_used = min(num_iteration * self.num_class, num_used)
        return {
            "name": self.name,
            "num_class": self.num_class,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": self.objective_name(),
            "sigmoid": self.sigmoid,
            "feature_names": names,
            "tree_info": [
                _tree_to_json(self.models[i], i) for i in range(num_used)
            ],
        }

    def feature_importance_array(self, importance_type: str = "split") -> np.ndarray:
        """Importances as an array over all original columns."""
        imp = np.zeros(self.max_feature_idx + 1, np.float64)
        # cold path (model save/dump), inherently host-side per tree
        for tree in self.models:
            nl = int(tree.num_leaves)
            sfr = np.asarray(tree.split_feature_real)[: nl - 1]  # jaxlint: disable=host-sync-in-loop
            gains = np.asarray(tree.split_gain)[: nl - 1]  # jaxlint: disable=host-sync-in-loop
            for j, f in enumerate(sfr):
                if f >= 0:
                    imp[f] += gains[j] if importance_type == "gain" else 1
        return imp

    @property
    def num_trees(self) -> int:
        return len(self.models)

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_class, 1)


def train_forest_round(gbdts: List["GBDT"]) -> List[bool]:
    """Advance every booster in ``gbdts`` one boosting iteration,
    growing ALL their trees (sum of num_class lanes) in ONE batched
    dispatch (learners/forest.py).  This is the cross-model B-source:
    engine.train_many's N independent small models and engine.cv's
    folds share a binned dataset, so their per-iteration grow work is
    shape-identical and stacks along the lane axis.

    Requirements (raise ValueError otherwise — the callers validate
    configs upfront and fall back to per-booster sequential training):
    every booster _forest_eligible() under its own knob, same binned
    matrix object (dense_bins_T_device cache), same num_bins and
    max_leaves.  Per-lane TreeLearnerParams may differ (lambda_l1/l2,
    min_data_in_leaf, ... ride the stacked params lanes).

    Returns a per-booster "should stop" flag, aligned with ``gbdts``.
    Boosters whose begin-half says "stop"/"skip" simply contribute no
    lanes; a shrinking active set retraces once per distinct lane
    count (cached in make_grow_forest's lru table).
    """
    from ..learners import forest

    if not gbdts:
        return []
    ref = gbdts[0]
    for b in gbdts:
        if not b._forest_eligible():
            raise ValueError(
                "train_forest_round: booster not forest-eligible "
                "(forest_batching=off, kernel/f64/pooled-histogram path, "
                "or parallel learner)"
            )
        if b._bins_T is not ref._bins_T:
            raise ValueError(
                "train_forest_round: boosters must share one binned "
                "dataset (same Dataset object, bin once)"
            )
        if (b._num_bins != ref._num_bins
                or b.max_leaves != ref.max_leaves):
            raise ValueError(
                "train_forest_round: max_bin and num_leaves must match "
                "across boosters (they fix the traced program shape)"
            )
        if ((getattr(b, "_base_row_mask", None) is None)
                != (getattr(ref, "_base_row_mask", None) is None)):
            raise ValueError(
                "train_forest_round: base row masks (cv fold mode) must "
                "be set on all boosters or none (the child-choice "
                "criterion is static per traced program)"
            )

    stops: List[bool] = [False] * len(gbdts)
    active: List[int] = []  # indices into gbdts with grow work
    pres = []
    for i, b in enumerate(gbdts):
        pre = b._forest_begin_iter()
        if pre == "stop":
            stops[i] = True
        elif pre == "skip":
            stops[i] = False
        else:
            active.append(i)
            pres.append(pre)
    if not active:
        return stops

    grads, hesses, bags, fmasks, plist = [], [], [], [], []
    lane_of = []  # (booster index, class k) per lane
    for i, (grad, hess, fms, _snap) in zip(active, pres):
        b = gbdts[i]
        for k in range(b.num_class):
            grads.append(grad[k])
            hesses.append(hess[k])
            bags.append(b._bag_mask)
            fmasks.append(fms[k])
            plist.append(b._learner_params)
            lane_of.append((i, k))

    gf = forest.make_grow_forest(
        ref._num_bins, ref.max_leaves,
        choice_by_mask_counts=(
            getattr(ref, "_base_row_mask", None) is not None),
    )
    trees_b, lids = gf(
        ref._bins_T, jnp.stack(grads), jnp.stack(hesses),
        jnp.stack(bags), jnp.stack(fmasks), ref._nbpf, ref._is_cat,
        forest.stack_learner_params(plist),
    )
    telemetry.count("forest_dispatches")
    telemetry.count("forest_batched_trees", len(lane_of))

    # distribute lanes back booster-major (lane_of is already grouped)
    per_booster: Dict[int, list] = {}
    for lane, (i, _k) in enumerate(lane_of):
        per_booster.setdefault(i, []).append(
            (forest.unstack_tree(trees_b, lane), lids[lane])
        )
    for pos, i in enumerate(active):
        nf_snap = pres[pos][3]
        stops[i] = gbdts[i]._forest_finish_iter(per_booster[i], nf_snap)
    return stops


def _fmt(x) -> str:
    """Compact float formatting matching C++ default ostream behavior."""
    x = float(x)
    if x == int(x) and abs(x) < 1e15:
        return str(int(x))
    return repr(x)


def _arr_str(a, n, fmt=str) -> str:
    return " ".join(fmt(v) for v in np.asarray(a)[:n])


def _tree_to_string(tree: Tree) -> str:
    """Tree::ToString (tree.cpp:124-151)."""
    nl = int(tree.num_leaves)
    ni = max(nl - 1, 0)
    f = lambda v: _fmt(float(v))
    out = [f"num_leaves={nl}"]
    out.append("split_feature=" + _arr_str(tree.split_feature_real, ni))
    out.append("split_gain=" + _arr_str(tree.split_gain, ni, f))
    out.append("threshold=" + _arr_str(tree.threshold_real, ni, f))
    out.append("decision_type=" + _arr_str(tree.decision_type, ni))
    out.append("left_child=" + _arr_str(tree.left_child, ni))
    out.append("right_child=" + _arr_str(tree.right_child, ni))
    out.append("leaf_parent=" + _arr_str(tree.leaf_parent, nl))
    out.append("leaf_value=" + _arr_str(tree.leaf_value, nl, f))
    out.append("leaf_count=" + _arr_str(tree.leaf_count, nl, lambda v: str(int(float(v)))))
    out.append("internal_value=" + _arr_str(tree.internal_value, ni, f))
    out.append(
        "internal_count=" + _arr_str(tree.internal_count, ni, lambda v: str(int(float(v))))
    )
    out.append("")
    return "\n".join(out)


def _tree_to_json(tree: Tree, index: int) -> Dict:
    """Tree::ToJSON (tree.cpp:153-191): recursive node dict."""
    nl = int(tree.num_leaves)
    sf = np.asarray(tree.split_feature_real)
    sg = np.asarray(tree.split_gain)
    tr = np.asarray(tree.threshold_real)
    dt = np.asarray(tree.decision_type)
    lc = np.asarray(tree.left_child)
    rc = np.asarray(tree.right_child)
    iv = np.asarray(tree.internal_value)
    ic = np.asarray(tree.internal_count)
    lv = np.asarray(tree.leaf_value)
    lcnt = np.asarray(tree.leaf_count)
    lp = np.asarray(tree.leaf_parent)

    def leaf_node(leaf: int) -> Dict:
        return {
            "leaf_index": int(leaf),
            "leaf_parent": int(lp[leaf]),
            "leaf_value": float(lv[leaf]),
            "leaf_count": int(lcnt[leaf]),
        }

    # children are always created after their parent (tree.cpp:52-96), so a
    # reverse sweep builds every child dict before its parent — no recursion
    built: Dict[int, Dict] = {}
    for i in range(nl - 2, -1, -1):
        li, ri = int(lc[i]), int(rc[i])
        built[i] = {
            "split_index": int(i),
            "split_feature": int(sf[i]),
            "split_gain": float(sg[i]),
            "threshold": float(tr[i]),
            "decision_type": "==" if dt[i] & 1 else "<=",
            "default_left": bool(dt[i] & 2),
            "missing_type": "NaN" if dt[i] & 12 == 8 else "None",
            "internal_value": float(iv[i]),
            "internal_count": int(ic[i]),
            "left_child": built[li] if li >= 0 else leaf_node(~li),
            "right_child": built[ri] if ri >= 0 else leaf_node(~ri),
        }

    return {
        "tree_index": index,
        "num_leaves": nl,
        "tree_structure": built[0] if nl > 1 else leaf_node(0),
    }


def _tree_from_lines(lines: List[str]) -> Tree:
    """Tree::Tree(const string&) (tree.cpp:193-231).  Bin-space fields are
    unavailable in the text format; loaded trees predict on raw values."""
    kv = {}
    for line in lines:
        if "=" in line:
            k, v = line.split("=", 1)
            if k.strip() and v.strip():
                kv[k.strip()] = v.strip()
    nl = int(kv["num_leaves"])
    max_leaves = max(nl, 2)
    t = empty_tree(max_leaves)

    def parse(key, n, dtype):
        if n == 0 or key not in kv:
            return np.zeros(n, dtype)
        vals = np.array(kv[key].split()[:n], dtype=np.float64)
        return vals.astype(dtype)

    ni = nl - 1
    pad_i = max_leaves - 1 - ni
    pad_l = max_leaves - nl

    def padded(key, n, pad, dtype, fill=0):
        v = parse(key, n, dtype)
        if pad > 0:
            v = np.concatenate([v, np.full(pad, fill, dtype)])
        return jnp.asarray(v)

    return t._replace(
        num_leaves=jnp.int32(nl),
        split_feature=padded("split_feature", ni, pad_i, np.int32),
        split_feature_real=padded("split_feature", ni, pad_i, np.int32),
        threshold_real=padded("threshold", ni, pad_i, np.float32),
        decision_type=padded("decision_type", ni, pad_i, np.int32),
        left_child=padded("left_child", ni, pad_i, np.int32),
        right_child=padded("right_child", ni, pad_i, np.int32),
        split_gain=padded("split_gain", ni, pad_i, np.float32),
        internal_value=padded("internal_value", ni, pad_i, np.float32),
        internal_count=padded("internal_count", ni, pad_i, np.float32),
        leaf_value=padded("leaf_value", nl, pad_l, np.float32),
        leaf_count=padded("leaf_count", nl, pad_l, np.float32),
        leaf_parent=padded("leaf_parent", nl, pad_l, np.int32, -1),
    )
