"""Command-line application: ``python -m lightgbm_tpu config=train.conf``.

Mirrors the reference Application (src/application/application.cpp,
src/main.cpp): ``key=value`` argv merged over a config file (argv wins,
application.cpp:46-104), then Train (application.cpp:187-239) — data
load, boosting/objective construction, per-iteration timing log, metric
output every ``metric_freq``, early stopping, model save — or Predict
(application.cpp:242-256) via the batch :class:`Predictor`.

Reference ``examples/*/train.conf`` files parse and run unchanged.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional

from .config import (Config, key_alias_transform, parse_config_file,
                     parse_line_params)
from .io.dataset import BinnedDataset
from .log import Log
from .models.dart import create_boosting
from .models.gbdt import GBDT
from .obs import RunManifest, flightrec, manifest_path, telemetry
from .objectives import create_objective
from .resilience import EXIT_PREEMPTED
from .serving.batch import DEFAULT_CHUNK_ROWS, DEFAULT_STREAM_THRESHOLD


def load_parameters(argv: List[str]) -> Dict[str, str]:
    """argv ``key=value`` pairs + optional config file; argv wins
    (application.cpp:46-104).  Bare ``--flag`` tokens are accepted as
    ``flag=true`` (``python -m lightgbm_tpu ... --resume``)."""
    argv = [a[2:] + "=true" if a.startswith("--") and "=" not in a
            else a.lstrip("-") for a in argv]
    # Canonicalize alias keys BEFORE merging so argv wins across aliases
    # too (argv ``valid=`` must override a conf-file ``valid_data=``),
    # matching the reference's alias transform + priority merge
    # (config.cpp Config::KV2Map / KeyAliasTransform).
    params = key_alias_transform(parse_line_params(argv))
    conf_path = params.pop("config_file", "")  # 'config' canonicalizes here
    if conf_path:
        file_params = key_alias_transform(parse_config_file(conf_path))
        for k, v in file_params.items():
            params.setdefault(k, v)
    params.pop("config_file", None)
    return params


class Predictor:
    """Batch file prediction -> result file (src/application/predictor.hpp:
    24-155): parse input rows, run normal/raw/leaf-index prediction,
    write one line per row (tab-separated for multi-output).

    The heavy lifting lives in serving/batch.py: large CSV/TSV inputs
    stream through an overlapped parse -> predict -> write pipeline
    (reader thread prefetches the next chunk while the device runs the
    current one; a writer thread formats/writes under the crash-safe
    ``atomic_writer``).  ``overlap=False`` restores the old strictly
    sequential behavior; both are byte-identical."""

    # the single source of truth for both knobs is serving/batch.py;
    # these are instance-overridable mirrors, not independent copies
    stream_threshold = DEFAULT_STREAM_THRESHOLD
    chunk_rows = DEFAULT_CHUNK_ROWS
    overlap = True

    def __init__(self, booster, is_raw_score: bool, is_predict_leaf_index: bool):
        self.booster = booster
        self.is_raw_score = is_raw_score
        self.is_leaf = is_predict_leaf_index

    def predict_file(self, data_path: str, result_path: str, has_header: bool = False,
                     num_iteration: int = -1) -> dict:
        from .serving.batch import pipelined_predict_file

        return pipelined_predict_file(
            self.booster, data_path, result_path, has_header=has_header,
            num_iteration=num_iteration, raw_score=self.is_raw_score,
            pred_leaf=self.is_leaf,
            stream_threshold=self.stream_threshold,
            chunk_rows=self.chunk_rows, overlap=self.overlap,
        )

    def _predict_chunks(self, data_path, has_header, num_iteration):
        """The parity seam (tests pin streamed == one-shot bytes):
        prediction arrays chunk by chunk via the shared stream."""
        from .serving.batch import predict_chunk_stream

        yield from predict_chunk_stream(
            self.booster, data_path, has_header=has_header,
            num_iteration=num_iteration, raw_score=self.is_raw_score,
            pred_leaf=self.is_leaf,
            stream_threshold=self.stream_threshold,
            chunk_rows=self.chunk_rows,
        )


def _output_metrics(gbdt: GBDT, iter_num: int, names: List[str],
                    is_training_metric: bool) -> List[tuple]:
    """OutputMetric (gbdt.cpp:299-356): print + return (set_idx, metric,
    value, bigger_is_better) rows for early-stopping bookkeeping."""
    rows = []
    sets = []
    if is_training_metric:
        sets.append((0, "training"))
    sets.extend((i + 1, names[i]) for i in range(len(names)))
    for data_idx, name in sets:
        metrics = gbdt.train_metrics if data_idx == 0 else gbdt.valid_metrics[data_idx - 1]
        # device-resident eval where supported (scores stay in HBM); the
        # host copy is pulled lazily, only if some metric needs it
        plain = [m for m in metrics if not hasattr(m, "eval_multi")]
        dev_vals = (
            gbdt.eval_at(data_idx, only={m.name for m in plain})
            if plain else {}
        )
        s = None
        for m in metrics:
            if hasattr(m, "eval_multi"):
                # print every position, but early stopping judges a
                # multi-position metric only by its LAST position, like
                # the reference (gbdt.cpp OutputMetric: test_scores.back())
                if s is None:
                    scores = gbdt.predict_at(data_idx)
                    s = scores if gbdt.num_class > 1 else scores[0]
                values = m.eval_multi(s)
                for k, v in zip(m.eval_at, values):
                    Log.info(f"Iteration: {iter_num}, {name} {m.name}@{k} : {v:g}")
                if data_idx > 0 and len(values):
                    rows.append((data_idx, m.name, values[-1], m.bigger_is_better))
            else:
                v = dev_vals[m.name]
                Log.info(f"Iteration: {iter_num}, {name} {m.name} : {v:g}")
                if data_idx > 0:
                    rows.append((data_idx, m.name, v, m.bigger_is_better))
    return rows


def run_train(cfg: Config) -> GBDT:
    """InitTrain + Train (application.cpp:187-239)."""
    # install the backend-compile listener BEFORE the first jax trace so
    # the run manifest's compile count covers the whole run (the
    # listener only sees events fired after registration)
    from .analysis.recompile import compile_counter

    compile_counter()
    # a preempted/poisoned run dumps its flight recorder next to the
    # model it was training (LGBM_TPU_FLIGHTREC_DIR overrides)
    flightrec.configure_dir(
        os.path.dirname(os.path.abspath(cfg.output_model)))
    if cfg.is_parallel and cfg.num_machines > 1:
        # Network::Init analog (application.cpp:190): attach this process
        # to the multi-host JAX runtime before any data loads, so the
        # per-rank ingest partition and mapper allgather see the world
        from .parallel.multihost import (initialize_from_config,
                                         sync_config_across_processes)

        initialize_from_config(cfg)
        # GlobalSyncUpByMin analog (application.cpp:110-127, 190-198):
        # reconcile seeds/fractions, verify structural params match
        sync_config_across_processes(cfg)
    t0 = time.perf_counter()
    train = BinnedDataset.from_file(cfg.data, cfg)
    Log.info(
        f"Finish loading data, use {time.perf_counter() - t0:.6f} seconds"
    )
    objective = create_objective(cfg, train.metadata, train.num_data)
    booster = create_boosting(cfg, train, objective)

    valid_names: List[str] = []
    for path in cfg.valid_data:
        vset = BinnedDataset.from_file(path, cfg, reference=train)
        name = os.path.basename(path)
        booster.add_valid_dataset(vset, name)
        valid_names.append(name)

    if cfg.input_model:
        from .basic import Booster

        init = Booster(model_file=cfg.input_model)
        booster.merge_from(init._gbdt, prepend=True)
        Log.info(
            f"Continued training from {cfg.input_model} "
            f"({init._gbdt.num_trees} trees)"
        )

    # early-stopping state per (valid set, metric) (gbdt.cpp:336-347)
    best_score: Dict[tuple, float] = {}
    best_iter: Dict[tuple, int] = {}
    best_model_iter = 0

    # checkpoint resume (resilience/checkpoint.py): restore the EXACT
    # training state — trees, score buffers, RNGs, bagging mask, early-
    # stop bests — so the final model is bitwise-identical to an
    # uninterrupted run.  Validation (checksum, config fingerprint) is
    # loud; only "no checkpoint exists yet" silently starts fresh (a
    # preemption before the first snapshot loses nothing).
    from .resilience import checkpoint as ckpt

    start_iter = 0
    if cfg.resume:
        found = ckpt.load_latest_for(cfg)
        if found is not None:
            ck_path, payload = found
            start_iter = ckpt.restore_training_state(
                booster, payload, best_score, best_iter)
            Log.info(
                f"Resumed from {ck_path}: {booster.num_trees} trees, "
                f"continuing at iteration {start_iter + 1}")
        else:
            Log.warning(
                "resume=true but no checkpoint found in "
                f"{ckpt.checkpoint_dir(cfg)}; starting fresh")

    profiler_ctx = None
    if cfg.profile:
        # TPU-native replacement for the reference's per-iteration
        # wall-clock logging (application.cpp:228-235): a full
        # jax.profiler trace with per-kernel XLA cost breakdown
        import jax

        jax.profiler.start_trace(cfg.profile_dir)
        profiler_ctx = cfg.profile_dir

    # gang membership (resilience/gang.py): when a GangSupervisor
    # launched us, announce readiness just before the loop starts,
    # heartbeat every completed iteration, and stamp the rank topology
    # + barrier ids into every checkpoint manifest
    from .resilience.gang import beacon_from_env

    beacon = beacon_from_env()
    gang_block = None
    heartbeat = None
    if beacon is not None:
        gang_block = beacon.gang_block()
        heartbeat = beacon.heartbeat
        beacon.ready()
        if start_iter:
            beacon.heartbeat(start_iter)

    start = time.perf_counter()
    stop_iter = None
    try:
        with ckpt.CheckpointManager(cfg, booster, best_score, best_iter,
                                    gang=gang_block,
                                    heartbeat=heartbeat) as ckmgr:
            stop_iter = _train_loop(cfg, booster, valid_names, best_score,
                                    best_iter, start, start_iter, ckmgr)
    finally:
        if profiler_ctx is not None:
            import jax

            jax.profiler.stop_trace()
            Log.info(f"Saved profiler trace to {profiler_ctx}")
    # drain the non-finite guard's lazy counters BEFORE the model save
    # and manifest snapshot, so nonfinite_values_clipped is accurate in
    # both (short clip-policy runs would otherwise report 0)
    booster.finalize_guards()
    stop_early = stop_iter is not None
    if stop_early:
        best_model_iter = stop_iter + 1

    # slice counts iterations from the model start, so prepended
    # init-model trees are part of the budget (gbdt.cpp:589-592)
    num_iteration = (
        booster.num_init_iteration + best_model_iter if stop_early else -1
    )
    booster.save_model_to_file(cfg.output_model, num_iteration)
    Log.info(f"Finished training, saved model to {cfg.output_model}")
    _write_train_manifest(cfg, booster, time.perf_counter() - start,
                          profiler_ctx)
    return booster


def _write_train_manifest(cfg: Config, booster: GBDT, train_s: float,
                          profile_dir: Optional[str]) -> None:
    """RunManifest next to the saved model (``<output_model>.manifest
    .json``): every CLI training run leaves the same self-describing
    evidence as a bench run.  When ``profile=true`` captured a trace,
    ``phases`` is its device seconds by ``lgbm.*`` scope
    (obs/device_time.py); otherwise it stays empty (host timers cannot
    see inside the jitted loop).
    Best-effort: a manifest failure must not fail a finished training
    run.

    Multi-rank runs (obs/dist.py): every rank publishes its telemetry
    snapshot into the exchange dir (``LGBM_TPU_RANK_OBS_DIR`` or a
    ``<output_model>.manifest.json.rankobs`` sibling), rank 0 gathers,
    merges, and writes the ONE manifest carrying a ``ranks[]`` section
    plus the merged counters/skew — non-zero ranks write no manifest
    (today's every-rank-writes-the-same-path race becomes the per-rank
    snapshot files instead)."""
    try:
        phases = {}
        if profile_dir:
            from .obs import device_time

            xplane = device_time.newest_xplane(profile_dir)
            phases = device_time.seconds_by_scope(xplane) if xplane else {}
        ranks: list = []
        extra: dict = {}
        from .obs import dist
        from .resilience.gang import beacon_from_env

        beacon = beacon_from_env()
        if beacon is not None:
            # gang ranks are independent single-process jax worlds
            # (redundant data-parallel mode), so the >1-world exchange
            # below never triggers for them: publish the gang-stamped
            # snapshot under the formation rank so the supervisor's
            # train-fleet manifest carries every rank's telemetry
            # (resilience/gang.py write_train_fleet_artifact)
            dist.write_rank_snapshot(
                os.environ.get("LGBM_TPU_RANK_OBS_DIR") or
                dist.exchange_dir_for(manifest_path(cfg.output_model)),
                dist.rank_snapshot(rank=beacon.rank, world=beacon.world))

        if dist.process_count() > 1:
            xdir = dist.exchange_dir_for(manifest_path(cfg.output_model))
            dist.write_rank_snapshot(xdir)
            if dist.process_index() != 0:
                Log.info(
                    f"rank {dist.process_index()}: published telemetry "
                    f"snapshot to {xdir}; rank 0 writes the merged "
                    "manifest")
                return
            try:
                snaps = dist.gather_rank_snapshots(
                    xdir, dist.process_count(), timeout_s=120.0)
                ranks = dist.ranks_section(snaps)
                extra["distributed"] = dist.merged_manifest_extra(
                    dist.merge_snapshots(snaps))
            except Exception as e:  # noqa: BLE001 — degrade, don't lose
                # a peer that died before publishing must not cost the
                # finished run its manifest: fall back to rank 0's own
                # process-local view, with the failure ON the record
                Log.warning(
                    f"rank-snapshot gather failed ({type(e).__name__}: "
                    f"{str(e)[:200]}); writing a single-rank manifest")
                ranks = []
                extra["distributed"] = {
                    "gather_error": f"{type(e).__name__}: {str(e)[:300]}"}
        try:
            from .obs import memory as obs_memory

            mem_section = obs_memory.manifest_memory_section()
        except Exception:
            mem_section = {}
        manifest = RunManifest.collect(
            "cli.train", config=cfg,
            result={"num_trees": booster.num_trees,
                    "train_wall_s": round(train_s, 3),
                    "output_model": cfg.output_model},
            phases=phases,
            per_tree_reservoir="tree_dispatch_s",
            ranks=ranks,
            extra=extra,
            memory=mem_section,
        )
        path = manifest.write(manifest_path(cfg.output_model))
        Log.info(f"Wrote run manifest to {path}")
        if cfg.verbose >= 2:
            # structured telemetry tail (docs/observability.md): one
            # debug line a tool can parse out of the CLI log
            Log.debug("telemetry " + json.dumps(
                telemetry.get_telemetry().snapshot(), sort_keys=True))
    except Exception as e:
        Log.warning(f"run manifest write failed: {type(e).__name__}: {e}")


def _train_loop(cfg: Config, booster: GBDT, valid_names: List[str],
                best_score: Dict, best_iter: Dict, start: float,
                start_iter: int = 0, ckmgr=None):
    """The iteration loop (application.cpp:223-239); returns the best
    0-based iteration when early stopping fired, else None.

    Early stopping matches the reference (gbdt.cpp:336-349): it fires as
    soon as ANY (valid set, metric) pair has gone early_stopping_round
    iterations without improving, and the model is truncated to THAT
    pair's best iteration — not the max over all pairs.

    ``ckmgr.after_iteration`` runs once per completed iteration: it
    writes due snapshots and, after a SIGTERM/SIGINT, checkpoints and
    raises TrainingPreempted (the in-flight iteration always finishes
    first — a half-grown tree is not a resumable state)."""
    for it in range(start_iter, cfg.num_iterations):
        finished = booster.train_one_iter()
        Log.info(
            f"{time.perf_counter() - start:.6f} seconds elapsed, "
            f"finished iteration {it + 1}"
        )
        if cfg.metric_freq > 0 and (it + 1) % cfg.metric_freq == 0:
            rows = _output_metrics(booster, it + 1, valid_names, cfg.is_training_metric)
            if cfg.early_stopping_round > 0:
                for data_idx, mname, v, bigger in rows:
                    key = (data_idx, mname)
                    better = (
                        key not in best_score
                        or (v > best_score[key] if bigger else v < best_score[key])
                    )
                    if better:
                        best_score[key], best_iter[key] = v, it
                    elif it - best_iter[key] >= cfg.early_stopping_round:
                        Log.info(
                            f"Early stopping at iteration {it + 1}, the best "
                            f"iteration round is {best_iter[key] + 1}"
                        )
                        return best_iter[key]
        if finished:
            Log.info("Stopped training because there are no more leaves "
                     "that meet the split requirements.")
            break
        # AFTER the metric/early-stop bookkeeping: a checkpoint at
        # iteration k must carry k's best-score updates or a resumed
        # run's early stopping would diverge from the uninterrupted one
        if ckmgr is not None:
            ckmgr.after_iteration(it)
    # drain the lagged stop check when the loop ended by iteration count
    # (no-op unless LGBM_TPU_STOP_LAG is set)
    booster.finish_lagged_stop()
    return None


def run_train_many(cfg: Config, params: Dict[str, str]) -> None:
    """``task=train_many``: N independent models, one shared binned
    dataset, every boosting round advanced as ONE batched forest
    dispatch (engine.train_many; docs/forest_batching.md).  Model i
    trains with master seed ``seed + i`` — a seed-ensemble sweep — and
    saves to ``<output_model>.<i>``."""
    from .analysis.recompile import compile_counter
    from .basic import Dataset
    from .engine import train_many

    compile_counter()
    if cfg.num_models < 1:
        Log.fatal("num_models must be >= 1 for task=train_many")
    base = {
        k: v for k, v in params.items()
        if k not in ("task", "num_models", "data", "output_model")
    }
    plist = []
    for i in range(cfg.num_models):
        p = dict(base)
        p["seed"] = cfg.seed + i
        plist.append(p)
    t0 = time.perf_counter()
    ds = Dataset(cfg.data, params=dict(base))
    boosters = train_many(plist, ds, num_boost_round=cfg.num_iterations)
    Log.info(
        f"Finished training {len(boosters)} models in "
        f"{time.perf_counter() - t0:.6f} seconds"
    )
    for i, bst in enumerate(boosters):
        path = f"{cfg.output_model}.{i}"
        bst.save_model(path)
        Log.info(f"Saved model {i} ({bst.num_trees()} trees) to {path}")


def run_predict(cfg: Config) -> None:
    """Application::Predict (application.cpp:242-256)."""
    from .basic import Booster

    if not cfg.input_model:
        Log.fatal("input_model should not be empty for prediction task")
    booster = Booster(model_file=cfg.input_model)
    t0 = time.perf_counter()
    stats = Predictor(
        booster, cfg.is_predict_raw_score, cfg.is_predict_leaf_index
    ).predict_file(
        cfg.data, cfg.output_result, cfg.has_header,
        num_iteration=cfg.num_iteration_predict,
    )
    Log.info(
        f"Finish prediction, use {time.perf_counter() - t0:.6f} seconds; "
        f"saved to {cfg.output_result}"
    )
    if cfg.verbose >= 2:
        Log.debug("predict pipeline " + json.dumps(stats, sort_keys=True))


def run_serve(cfg: Config) -> int:
    """``task=serve``: the online micro-batched inference service
    (serving/server.py; docs/serving.md) — a persistent on-device
    ensemble behind shape-bucketed dispatch with checksum-verified
    hot-swap, serving until SIGINT/SIGTERM, then draining gracefully
    and exiting 75 (the supervisor-relaunch contract)."""
    from .serving import serve_from_config

    if not cfg.input_model:
        Log.fatal("input_model should not be empty for serve task")
    return int(serve_from_config(cfg, block=True) or 0)


def run_serve_fleet(cfg: Config) -> int:
    """``task=serve_fleet``: the replica supervisor
    (serving/supervisor.py; docs/serving.md) — N ``task=serve``
    subprocesses behind one round-robin front end, health-checked,
    restarted on crash/preemption with jittered backoff, scaled between
    ``serve_replicas`` and ``serve_max_replicas`` off the queue-depth
    gauge."""
    from .serving.supervisor import serve_fleet_from_config

    if not cfg.input_model:
        Log.fatal("input_model should not be empty for serve_fleet task")
    return int(serve_fleet_from_config(cfg) or 0)


def main(argv: Optional[List[str]] = None) -> int:
    """main.cpp:4-22."""
    argv = sys.argv[1:] if argv is None else list(argv)
    from .resilience.checkpoint import TrainingPreempted

    try:
        params = load_parameters(argv)
        cfg = Config.from_dict(params)
        Log.reset_log_level(cfg.verbose)
        if cfg.task == "train":
            run_train(cfg)
        elif cfg.task == "train_many":
            run_train_many(cfg, params)
        elif cfg.task in ("predict", "prediction", "test"):
            run_predict(cfg)
        elif cfg.task == "serve":
            return run_serve(cfg)
        elif cfg.task == "serve_fleet":
            return run_serve_fleet(cfg)
        elif cfg.task == "train_fleet":
            # elastic gang training (resilience/gang.py): supervise
            # train_ranks rank subprocesses with coordinated checkpoint
            # barriers and the restart/shrink recovery ladder.  The
            # supervisor imports no jax — only the children pay for a
            # device runtime.
            from .resilience.gang import train_fleet_from_config

            return train_fleet_from_config(cfg)
        else:
            Log.fatal(f"Unknown task: {cfg.task!r}")
    except TrainingPreempted as ex:
        # distinct exit status (sysexits EX_TEMPFAIL): the supervisor
        # re-launches with resume=true and loses nothing.  The flight
        # recorder dumps LAST so its tail is the preemption itself —
        # checkpoint path, iteration, signal — next to the model.
        print(f"Preempted:\n{ex}", file=sys.stderr)
        flightrec.record("preempted", iteration=ex.iteration,
                         checkpoint=ex.path)
        flightrec.dump(reason="preempted")
        return EXIT_PREEMPTED
    except Exception as ex:
        from .resilience.guards import NonFiniteError

        if isinstance(ex, NonFiniteError):
            # the guard already recorded its trip at the raise site;
            # the dump's tail names the escalation that killed the run
            flightrec.record("nonfinite_abort", error=str(ex)[:400])
            flightrec.dump(reason="nonfinite")
        print(f"Met Exceptions:\n{ex}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
