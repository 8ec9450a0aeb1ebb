"""Benchmark: GBDT training throughput on a HIGGS-like synthetic workload.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Workload (mirrors BASELINE.json config #2 scaled down): binary
classification, 28 continuous features, 255 bins, 255 leaves.
``vs_baseline`` is the speedup of this framework (on the default JAX
device — the TPU chip under the driver) over the REFERENCE LightGBM CLI
built from /root/reference and run on the same machine's CPU with the
same data and parameters.  The reference baseline (sec/tree) is measured
once and cached in .bench/baseline_<key>.json.

Env overrides: BENCH_ROWS (default 1e6), BENCH_TREES (default 10),
BENCH_BUDGET_S (wall budget for the timed section, default 300).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench")
ROWS = int(float(os.environ.get("BENCH_ROWS", 1_000_000)))
TREES = int(os.environ.get("BENCH_TREES", 10))
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 300))
# held-out rows for the out-of-sample AUC column (VERDICT r3 item 5:
# "identical AUC" must be evidenced out-of-sample, not just on train)
VROWS = int(float(os.environ.get("BENCH_VALID", max(ROWS // 5, 1))))
N_FEAT, NUM_BINS, NUM_LEAVES = 28, 255, 255
LEARNING_RATE, MIN_DATA = 0.1, 100


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_data(n: int, seed: int = 7, n_valid: int = 0):
    """HIGGS-like: 28 correlated features, nonlinear decision boundary.

    With ``n_valid`` > 0 also returns a held-out set drawn from the SAME
    decision boundary (w1/w2), appended to the return tuple.  The train
    rows are drawn first so they stay bit-identical to the n_valid=0
    call — cached reference-CLI baselines keyed on the train data remain
    valid.
    """
    rng = np.random.RandomState(seed)

    def draw(m):
        X = rng.randn(m, N_FEAT).astype(np.float32)
        return X

    def label(X, w1, w2):
        z = X @ w1 + 0.5 * (X**2 - 1.0) @ w2 + 0.8 * X[:, 0] * X[:, 1]
        z = (z - z.mean()) / z.std()
        return (z + 0.5 * rng.randn(len(X)) > 0).astype(np.float32)

    X = draw(n)
    w1, w2 = rng.randn(N_FEAT), rng.randn(N_FEAT)
    y = label(X, w1, w2)
    if not n_valid:
        return X, y
    Xv = draw(n_valid)
    yv = label(Xv, w1, w2)
    return X, y, Xv, yv


# --------------------------------------------------------------- reference
def build_reference_cli() -> str | None:
    """Build the reference LightGBM CLI from a /tmp copy (its CMake writes
    the binary into the source tree, which must stay untouched)."""
    exe = "/tmp/lgbm_ref_src/lightgbm"
    if os.path.exists(exe):
        return exe
    if not os.path.isdir("/root/reference"):
        return None
    try:
        shutil.copytree("/root/reference", "/tmp/lgbm_ref_src", dirs_exist_ok=True)
        os.makedirs("/tmp/lgbm_ref_build", exist_ok=True)
        subprocess.run(
            ["cmake", "-DCMAKE_POLICY_VERSION_MINIMUM=3.5",
             "-DCMAKE_BUILD_TYPE=Release",
             "-DCMAKE_CXX_FLAGS=-include limits", "/tmp/lgbm_ref_src"],
            cwd="/tmp/lgbm_ref_build", check=True, capture_output=True)
        subprocess.run(["make", "-j4", "lightgbm"], cwd="/tmp/lgbm_ref_build",
                       check=True, capture_output=True)
        return exe if os.path.exists(exe) else None
    except Exception as e:  # baseline is best-effort
        log(f"reference build failed: {e}")
        return None


def run_reference_cli(exe: str, data_path: str, model_path: str,
                      trees: int, timeout_s: float = 3600):
    """Run the reference CLI at the bench config and isolate training
    time from data loading via its own per-iteration log
    (application.cpp:228-235).  Returns (sec_per_tree, total_s, proc) or
    (None, total_s, proc) on failure."""
    import subprocess

    conf = [
        "task=train", f"data={data_path}", "objective=binary",
        f"num_trees={trees}", f"num_leaves={NUM_LEAVES}",
        f"max_bin={NUM_BINS}", f"learning_rate={LEARNING_RATE}",
        f"min_data_in_leaf={MIN_DATA}", "verbosity=1",
        f"output_model={model_path}", "is_save_binary_file=false",
    ]
    t0 = time.perf_counter()
    proc = subprocess.run([exe] + conf, capture_output=True, text=True,
                          timeout=timeout_s)
    total = time.perf_counter() - t0
    if proc.returncode != 0:
        return None, total, proc
    sec = None
    for line in proc.stdout.splitlines():
        if "seconds elapsed, finished iteration" in line:
            sec = float(line.split("]")[-1].strip().split()[0])
    return ((sec / trees) if sec else total / trees), total, proc


def reference_sec_per_tree(X, y, key: str, Xv=None, yv=None):
    """Returns (sec_per_tree, ref_train_auc, ref_valid_auc)."""
    # crash-safe cache writes (resilience/atomic.py); imported lazily so
    # the module keeps its no-package-import-before-backend-pinning rule
    from lightgbm_tpu.resilience.atomic import atomic_write_json

    os.makedirs(CACHE_DIR, exist_ok=True)
    cache = os.path.join(CACHE_DIR, f"baseline_{key}.json")
    model_path = f"/tmp/bench_ref_model_{key}.txt"  # keyed: a stale or
    # differently-sized model must never feed the AUC parity evidence
    if os.path.exists(cache):
        with open(cache) as fh:
            data = json.load(fh)
        dirty = False
        if data.get("ref_auc") is None and os.path.exists(model_path):
            try:  # cache predates the AUC field — backfill it
                data["ref_auc"] = _model_train_auc(model_path, X, y)
                dirty = True
            except Exception as e:
                log(f"reference AUC backfill failed: {e}")
        if (Xv is not None and os.path.exists(model_path)
                and data.get("ref_valid_auc_rows") != len(Xv)):
            try:  # valid AUC keyed by held-out size (backfill/refresh)
                data["ref_valid_auc"] = _model_train_auc(model_path, Xv, yv)
                data["ref_valid_auc_rows"] = len(Xv)
                dirty = True
            except Exception as e:
                log(f"reference valid-AUC backfill failed: {e}")
        if dirty:
            atomic_write_json(cache, data, indent=None)
        # a valid AUC computed for a DIFFERENT held-out size must never
        # feed this run's parity columns (possible when the model file is
        # gone so the backfill above couldn't refresh it)
        v_auc = data.get("ref_valid_auc")
        if Xv is None or data.get("ref_valid_auc_rows") != len(Xv):
            v_auc = None
        return data["sec_per_tree"], data.get("ref_auc"), v_auc
    exe = build_reference_cli()
    if exe is None:
        return None, None, None
    data_path = f"/tmp/bench_{key}.csv"
    if not os.path.exists(data_path):
        log("writing reference CSV ...")
        arr = np.column_stack([y, X])
        np.savetxt(data_path, arr, fmt="%.6g", delimiter=",")
    log("running reference CLI baseline ...")
    sec_per_tree, total, proc = run_reference_cli(
        exe, data_path, model_path, TREES)
    if sec_per_tree is None:
        log(f"reference run failed: {proc.stdout[-500:]} {proc.stderr[-500:]}")
        return None, None, None
    ref_auc = ref_valid_auc = None
    try:  # train AUC of the reference model, for the identical-AUC claim
        ref_auc = _model_train_auc(model_path, X, y)
    except Exception as e:
        log(f"reference AUC computation failed: {e}")
    if Xv is not None:
        try:
            ref_valid_auc = _model_train_auc(model_path, Xv, yv)
        except Exception as e:
            log(f"reference valid-AUC computation failed: {e}")
    # ref_valid_auc_rows is only stamped on SUCCESS: a transient
    # failure must leave the backfill (keyed on rows mismatch) armed
    atomic_write_json(
        cache,
        {"sec_per_tree": sec_per_tree, "total_s": total,
         "trees": TREES, "rows": ROWS, "ref_auc": ref_auc,
         "ref_valid_auc": ref_valid_auc,
         "ref_valid_auc_rows": None if ref_valid_auc is None else len(Xv)},
        indent=None)
    log(f"reference baseline: {sec_per_tree:.3f}s/tree (total {total:.1f}s, "
        f"train AUC={ref_auc}, valid AUC={ref_valid_auc})")
    return sec_per_tree, ref_auc, ref_valid_auc


def _model_train_auc(model_path: str, X, y) -> float:
    """Train AUC of a saved (reference-format) model via this framework's
    model loader + batch predictor — the text format is compatible."""
    from lightgbm_tpu.basic import Booster
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.metrics import create_metrics

    pred = Booster(model_file=model_path).predict(X, raw_score=True)
    m = create_metrics(
        Config(objective="binary", metric=["auc"]),
        Metadata(label=y.astype(np.float32)), len(y),
    )[0]
    return float(m.eval(np.asarray(pred, np.float64)))


# --------------------------------------------------------------------- ours
def _init_backend() -> str:
    """Resolve the JAX backend and return its platform name.  The bench
    measures the chip: a run that resolves anything but ``tpu`` raises,
    unless ``BENCH_PLATFORM`` names the platform explicitly (tier-1
    passes ``cpu`` for its contract checks)."""
    import jax

    plat = os.environ.get("BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    devs = jax.devices()
    log(f"devices: {devs}")
    platform = devs[0].platform
    if platform != "tpu" and not plat:
        raise RuntimeError(
            f"bench.py measures the TPU but JAX resolved {platform!r}; "
            "set BENCH_PLATFORM to name another platform explicitly")
    return platform


_DATASET_CACHE: dict = {}


def warm_until_compile_stable(step, max_warm: int | None = None,
                              log_fn=log):
    """Run ``step()`` (one warm iteration INCLUDING its sync) until the
    two-signal gate says the loop is honest to time (ROADMAP item 1):
    zero new backend compiles AND iteration-time stability (lazy Mosaic
    kernels compile inside an already-compiled executable and emit no
    JAX event — they show up as a slow iteration instead).  At least
    two iterations: the stability test needs a baseline before a slow
    (lazily-compiling) iteration can be told apart from steady state.

    Returns ``(warmed_iters, compile_stable)``."""
    from lightgbm_tpu.analysis.recompile import compile_counter

    if max_warm is None:
        max_warm = int(os.environ.get("BENCH_MAX_WARM", "12"))
    cc = compile_counter()
    t_min = None
    warmed = 0
    for warmed in range(1, max_warm + 1):
        t1 = time.perf_counter()
        step()
        dt = time.perf_counter() - t1
        new_compiles = cc.delta()
        cc.reset()
        t_min = dt if t_min is None else min(t_min, dt)
        if warmed >= 2 and new_compiles == 0 and dt <= 1.5 * t_min:
            log_fn(f"warm-up compile-stable after {warmed} extra "
                   f"iteration(s) (last {dt:.3f}s)")
            return warmed, True
        log_fn(f"warm-up iter {warmed}: {dt:.3f}s, "
               f"{new_compiles} new compile(s)")
    if max_warm > 0:
        log_fn(f"warm-up NOT compile-stable after {max_warm} iterations; "
               "timing anyway (BENCH_MAX_WARM to raise)")
    return warmed, False


def ours_sec_per_tree(X, y, Xv=None, yv=None):
    """Train TREES trees; caller has already resolved the backend via
    _init_backend() (so failures here happen ON the resolved platform).

    Returns ``(sec_per_tree, train_auc, valid_auc, info)`` where
    ``info`` carries the run's self-description (warm-up iteration
    count, discarded warm trees, compile counters for the warm-up and
    the timed loop, optional phase breakdown) — the evidence the
    RunManifest and the BENCH json record so a regression like round
    5's (12 lazy compiles inside the timed segment, unrecorded) can
    never again hide behind a bare s/tree number.  The timed per-tree
    times land in the telemetry reservoir "tree_s"."""

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    cfg = Config(
        objective="binary", num_leaves=NUM_LEAVES, max_bin=NUM_BINS,
        learning_rate=LEARNING_RATE, min_data_in_leaf=MIN_DATA,
        metric=["auc"],
    )
    from lightgbm_tpu.obs import telemetry

    if "ds" in _DATASET_CACHE:
        ds = _DATASET_CACHE["ds"]
    else:
        t0 = time.perf_counter()
        with telemetry.span("bench.binning"):
            ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
        log(f"binning: {time.perf_counter() - t0:.1f}s")
        _DATASET_CACHE["ds"] = ds
    obj = create_objective(cfg, ds.metadata, ds.num_data)
    # lagged stop check: the eager per-iter int(num_leaves) sync drains
    # the dispatch pipeline (its cost on this machine: not measured);
    # the lagged mode rolls back to an identical final model if the
    # no-split terminal state ever fires (it never does at bench scale).
    # The library default is 0; which behaviour wins is ROADMAP queue 1
    # item 3, and the row prints the value in force ("stop_lag").
    os.environ.setdefault("LGBM_TPU_STOP_LAG", "4")
    booster = GBDT(cfg, ds, obj)

    # pre-warm-up snapshot (GBDT.snapshot_state): lets the whole
    # warm-up be undone BIT-EXACTLY afterwards, so the timed model is
    # byte-identical to a fresh TREES-tree model — which is what the
    # AUC parity columns compare against the reference CLI's
    # TREES-tree run
    snap = booster.snapshot_state()

    # warmup: first iteration compiles
    from lightgbm_tpu.analysis.recompile import compile_counter

    cc_phase = compile_counter()  # compiles per bench phase (manifest)
    t0 = time.perf_counter()
    booster.train_one_iter()
    _ = np.asarray(booster._scores)  # force completion (async dispatch)
    log(f"compile + first tree: {time.perf_counter() - t0:.1f}s")

    # ---- warm until compile-stable (ROADMAP item 1).  One warm
    # iteration is NOT enough: the tier-capacity Mosaic kernels compile
    # lazily the first time a SPLIT lands in their branch, which can be
    # trees into the run — round 5's timed loops carried ~12 lazy
    # per-tier compiles in their first segment.  Gate shared with the
    # overhead proof: warm_until_compile_stable above.
    def _warm_step():
        booster.train_one_iter()
        _ = np.asarray(booster._scores[0, :1])

    with telemetry.span("bench.warmup"):
        warmed, compile_stable = warm_until_compile_stable(_warm_step)

    # restore the pre-warm-up snapshot (the compile tree included) so
    # the timed model ends at EXACTLY the trees the reference CLI
    # trains — previously the AUC parity columns compared a
    # (TREES+warm)-tree model against the reference's TREES-tree
    # model.  Restoring the held (immutable) initial score buffer is
    # bit-exact and O(1), unlike an arithmetic rollback whose
    # (s + d) - d float32 round trip leaves ulp residue in the timed
    # run's first gradients.
    warm_trees = len(booster.models) - snap[1]
    booster.restore_state(snap)
    log(f"discarded {warm_trees} warm-up tree(s); timed model will "
        f"hold exactly the trees it grows")
    compiles_warmup = cc_phase.delta()
    cc_phase.reset()

    done = 0
    t0 = time.perf_counter()
    with telemetry.span("bench.timed_loop"):
        for i in range(TREES):
            t_iter = time.perf_counter()
            booster.train_one_iter()
            # sync only every 5 trees (for the budget check): a
            # per-tree block_until_ready stalls the dispatch
            # pipeline each iteration (cost on this machine: not
            # measured)
            done += 1
            if i % 5 == 4:
                telemetry.host_sync()
                _ = np.asarray(booster._scores[0, :1])
            # per-tree reservoir (manifest p50/p99): dispatch wall
            # for 4 of 5 trees, the 5th absorbs the sync — the p50
            # tracks dispatch cost, the p99 the sync'd envelope
            telemetry.record_value("tree_s",
                                   time.perf_counter() - t_iter)
            if i % 5 == 4 and time.perf_counter() - t0 > BUDGET_S:
                log(f"budget hit after {done} trees")
                break
    _ = np.asarray(booster._scores)
    elapsed = time.perf_counter() - t0
    compiles_timed = cc_phase.delta()
    booster.finish_lagged_stop()
    auc = booster.eval_at(0).get("auc", float("nan"))
    valid_auc = float("nan")
    if Xv is not None:
        # attached AFTER the timed loop: add_valid_dataset replays the
        # trained model onto the valid scores, so the out-of-sample AUC
        # column costs the timed section nothing
        ds = _DATASET_CACHE["ds"]
        va = ds.align_with(Xv, Metadata(label=yv.astype(np.float32)))
        booster.add_valid_dataset(va, "bench_valid")
        valid_auc = booster.eval_at(1).get("auc", float("nan"))
    log(f"ours: {done} trees in {elapsed:.1f}s, train AUC={auc:.4f}, "
        f"valid AUC={valid_auc:.4f}")
    info = {
        "warmup_iters": warmed,
        "warm_trees_discarded": warm_trees,
        "compile_stable": compile_stable,
        "compiles_warmup": compiles_warmup,
        "compiles_timed": compiles_timed,
        "timed_trees": done,
    }
    return elapsed / done, auc, valid_auc, info


def _emit_result(out: dict, info: dict, key: str) -> None:
    """Write the RunManifest next to the bench artifacts, then print the
    single JSON result line (ALWAYS the last thing on stdout, manifest
    failure included — the driver contract is one JSON line, whatever
    happens)."""
    try:
        from lightgbm_tpu.obs import RunManifest
        from lightgbm_tpu.obs import memory as obs_memory

        # device-memory evidence ships INSIDE the row like the warm-up
        # evidence (hbm_peak_bytes is benchdiff's +15% memory gate) and
        # in full as the manifest's memory{} section
        try:
            mem_section = obs_memory.manifest_memory_section()
            peak = int(mem_section["hbm"]["hbm_peak_bytes"]
                       or obs_memory.peak_bytes())
            if peak:
                out.setdefault("hbm_peak_bytes", peak)
        except Exception:
            mem_section = {}
        mdir = os.environ.get("BENCH_MANIFEST_DIR", CACHE_DIR)
        path = os.path.join(mdir, f"bench_{key}.manifest.json")
        manifest = RunManifest.collect(
            "bench.py",
            config={"rows": ROWS, "trees": TREES, "valid_rows": VROWS,
                    "num_leaves": NUM_LEAVES, "num_bins": NUM_BINS,
                    "learning_rate": LEARNING_RATE, "min_data": MIN_DATA,
                    "growth": out.get("growth")},
            result=out,
            warmup={k: info[k] for k in (
                "warmup_iters", "warm_trees_discarded", "compile_stable",
                "compiles_warmup", "compiles_timed") if k in info},
            memory=mem_section,
        )
        manifest.write(path)
        repo = os.path.dirname(os.path.abspath(__file__))
        out["manifest"] = os.path.relpath(path, repo)
    except Exception as e:
        log(f"manifest write failed: {type(e).__name__}: {e}")
    print(json.dumps(out), flush=True)


def main() -> None:
    """Prints exactly one JSON result line; exits non-zero after
    printing it when anything failed."""
    key = f"r{ROWS}_t{TREES}_l{NUM_LEAVES}_b{NUM_BINS}"
    out = {
        "metric": f"gbdt_train_sec_per_tree_higgslike_{ROWS//1000}k",
        "value": 0.0,
        "unit": "s/tree",
        "vs_baseline": 0.0,
        "platform": "none",
    }
    info: dict = {}
    try:
        # platform is stamped into the row the moment the backend
        # resolves, so a failure on the chip still says where it ran
        out["platform"] = _init_backend()
        if VROWS > 0:
            X, y, Xv, yv = make_data(ROWS, n_valid=VROWS)
        else:  # BENCH_VALID=0 disables the out-of-sample column
            (X, y), Xv, yv = make_data(ROWS), None, None
        ours, auc, valid_auc, info = ours_sec_per_tree(X, y, Xv, yv)
        out["value"] = round(ours, 4)
        # the trees are leaf-wise, the reference's growth
        out["growth"] = "leafwise"
        # self-description (VERDICT r5 item 4): the warm-up and compile
        # evidence ships INSIDE the BENCH row, so a number measured over
        # lazy compiles can be seen to be one
        out.update({k: info[k] for k in (
            "warmup_iters", "warm_trees_discarded", "compile_stable",
            "compiles_warmup", "compiles_timed", "timed_trees")})
        out["stop_lag"] = int(os.environ["LGBM_TPU_STOP_LAG"])
        out["train_auc"] = round(float(auc), 4)
        if Xv is not None:
            out["valid_auc"] = round(float(valid_auc), 4)
        if os.environ.get("BENCH_SKIP_REF", "0") != "0":
            # contract/CI mode: our own number without the reference
            # baseline — building the reference CLI (cmake+make) inside
            # a test would eat the whole tier-1 time budget
            _emit_result(out, info, key)
            return
        ref, ref_auc, ref_valid_auc = reference_sec_per_tree(X, y, key, Xv, yv)
        if ref and ours > 0:
            out["vs_baseline"] = round(ref / ours, 3)
        if ref_auc is not None:
            out["ref_auc"] = round(float(ref_auc), 4)
            # the north-star clause is "at identical AUC", i.e. NOT WORSE:
            # auc_gap is the deficit only (0 when we beat the reference);
            # auc_delta keeps the signed difference for the record
            delta = out["train_auc"] - float(ref_auc)
            out["auc_delta"] = round(delta, 4)
            # NaN must propagate (a missing AUC is a failure, not a pass)
            gap = float("nan") if delta != delta else max(0.0, -delta)
            out["auc_gap"] = round(gap, 4)
        if ref_valid_auc is not None and Xv is not None:
            out["ref_valid_auc"] = round(float(ref_valid_auc), 4)
            vdelta = out["valid_auc"] - float(ref_valid_auc)
            out["valid_auc_delta"] = round(vdelta, 4)
            vgap = float("nan") if vdelta != vdelta else max(0.0, -vdelta)
            out["valid_auc_gap"] = round(vgap, 4)
    except Exception as e:
        import traceback
        traceback.print_exc(file=sys.stderr)
        out["error"] = f"{type(e).__name__}: {str(e)[:200]}"
    _emit_result(out, info, key)
    if "error" in out:
        sys.exit(1)


if __name__ == "__main__":
    main()
