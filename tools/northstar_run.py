"""North-star run: HIGGS-10M shape, 255 leaves, 255 bins, 500 trees, on chip.

VERDICT r3 item 2: run BASELINE.json config 2 at FULL length and report
total wall (compile included), steady-state s/tree, train AND valid AUC,
and HBM peak.  Reference: /root/reference/README.md:15 (the 64-core
speed claim this build targets) and src/application/application.cpp:228-235
(per-iteration timing the reference CLI logs).

Writes progress to .bench/northstar_progress.jsonl (one line per eval
checkpoint) and the final row to .bench/northstar_r4.json.  Saves the
model every CHECKPOINT_EVERY trees so a run that dies midway still leaves
evidence (text model + partial timings).

Env: NS_ROWS (default 10M), NS_VALID (default 1M), NS_TREES (default 500),
NS_REF (default 1: also run the reference CLI at the same config).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
BENCH_DIR = os.path.join(REPO, ".bench")

import bench  # noqa: E402

os.environ.setdefault("LGBM_TPU_STOP_LAG", "4")

import numpy as np  # noqa: E402

ROWS = int(float(os.environ.get("NS_ROWS", 10_000_000)))
VALID = int(float(os.environ.get("NS_VALID", 1_000_000)))
TREES = int(os.environ.get("NS_TREES", 500))
CHECKPOINT_EVERY = int(os.environ.get("NS_CKPT", 100))
N_FEAT, NUM_BINS, NUM_LEAVES = 28, 255, 255


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def emit_progress(row: dict) -> None:
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "northstar_progress.jsonl"), "a") as fh:
        fh.write(json.dumps(row) + "\n")


def make_split_data():
    """Same-boundary train/valid split via bench.make_data(n_valid=...):
    the train rows stay bit-identical to a plain make_data(ROWS) call, so
    bench.py's cached reference baselines refer to the same data."""
    if VALID <= 0:
        X, y = bench.make_data(ROWS, seed=7)
        return X, y, None, None
    return bench.make_data(ROWS, seed=7, n_valid=VALID)


def hbm_stats() -> dict:
    """Shared device-memory reader (obs/memory.py) — same output keys
    as the old ad-hoc memory_stats() call; on backends without
    allocator stats the peak falls back to the census high-water mark
    so a CPU northstar run still reports a real number."""
    from lightgbm_tpu.obs import memory as obs_memory

    st = obs_memory.hbm_stats()
    if st.get("hbm_stats_error"):
        return {"hbm_stats_error": st["hbm_stats_error"]}
    return {
        "hbm_peak_bytes": int(st["hbm_peak_bytes"]
                              or obs_memory.peak_bytes()),
        "hbm_limit_bytes": int(st["hbm_limit_bytes"]),
    }


def run_ours(Xtr, ytr, Xva, yva) -> dict:
    import jax

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    platform = jax.devices()[0].platform
    log(f"platform: {platform}")
    if platform != "tpu" and os.environ.get("NS_REQUIRE_TPU", "1") != "0":
        raise RuntimeError(f"NS_REQUIRE_TPU set but backend is {platform!r}")

    cfg = Config(
        objective="binary", num_leaves=NUM_LEAVES, max_bin=NUM_BINS,
        learning_rate=0.1, min_data_in_leaf=100, metric=["auc"],
        tree_growth="leafwise",
    )
    t_wall0 = time.perf_counter()
    t0 = time.perf_counter()
    ds = BinnedDataset.from_matrix(Xtr, Metadata(label=ytr), config=cfg)
    t_bin = time.perf_counter() - t0
    log(f"binning train ({ROWS} rows): {t_bin:.1f}s")
    t_bin_va, va = 0.0, None
    if Xva is not None:
        t0 = time.perf_counter()
        va = ds.align_with(Xva, Metadata(label=yva))
        t_bin_va = time.perf_counter() - t0
        log(f"binning valid ({VALID} rows): {t_bin_va:.1f}s")

    obj = create_objective(cfg, ds.metadata, ds.num_data)
    booster = GBDT(cfg, ds, obj)
    # NOTE: the valid set is attached AFTER training (add_valid_dataset
    # replays the whole model onto the valid scores in one stacked
    # program).  Attaching it up front puts a per-tree binned ensemble
    # walk over the 1M valid rows INSIDE the training loop — measured
    # ~3x the tree-growth cost itself at the 10M/255-leaf shape (the
    # walk is depth x 1M indexed gathers per tree).

    from lightgbm_tpu.analysis.recompile import compile_counter

    cc = compile_counter()
    t0 = time.perf_counter()
    booster.train_one_iter()
    _ = np.asarray(booster._scores[0, :1])
    t_compile = time.perf_counter() - t0
    log(f"compile + first tree: {t_compile:.1f}s")
    compiles_first = cc.delta()
    cc.reset()

    done = 1
    seg_t0, seg_done, loop_s = time.perf_counter(), 1, 0.0
    steady_compiles = 0
    while done < TREES:
        booster.train_one_iter()
        done += 1
        if done % 10 == 0:
            _ = np.asarray(booster._scores[0, :1])  # light sync
        if done % CHECKPOINT_EVERY == 0 or done == TREES:
            _ = np.asarray(booster._scores[0, :1])
            now = time.perf_counter()
            # steady time EXCLUDES the eval/save blocks below: only the
            # training segments are summed (review r4 — the final steady
            # rate must agree with the per-segment progress rows)
            loop_s += now - seg_t0
            # compile accounting mirrors the timing exclusion: count
            # compiles of the TRAINING segment now, drop whatever the
            # eval/save block below compiles (a fresh process always
            # compiles the metric program at the first checkpoint —
            # that must not read as a dirty steady loop)
            steady_compiles += cc.delta()
            seg_spt = (now - seg_t0) / (done - seg_done)
            evals = {
                "trees": done,
                "seg_sec_per_tree": round(seg_spt, 4),
                "train_auc": round(booster.eval_at(0)["auc"], 6),
                "elapsed_s": round(now - t_wall0, 1),
            }
            evals.update(hbm_stats())
            emit_progress(evals)
            log(f"progress: {evals}")
            booster.save_model_to_file("/tmp/northstar_model.txt")
            cc.reset()
            seg_t0, seg_done = time.perf_counter(), done
    _ = np.asarray(booster._scores)
    loop_s += time.perf_counter() - seg_t0
    steady_compiles += cc.delta()
    booster.finish_lagged_stop()
    total_wall = time.perf_counter() - t_wall0

    out = {
        "platform": platform,
        "rows": ROWS, "valid_rows": VALID, "trees": done,
        "bin_s": round(t_bin, 1), "bin_valid_s": round(t_bin_va, 1),
        "compile_first_tree_s": round(t_compile, 1),
        "steady_sec_per_tree": round(loop_s / max(done - 1, 1), 4),
        "total_wall_s": round(total_wall, 1),
        "train_auc": round(booster.eval_at(0)["auc"], 6),
        # compile evidence (obs): a steady rate measured while the
        # steady-loop counter moved is not steady.  Counts TRAINING
        # segments only — eval/checkpoint compiles are excluded exactly
        # like their wall time is.
        "compiles_first_tree": compiles_first,
        "compiles_steady_loop": steady_compiles,
    }
    if va is not None:
        t0 = time.perf_counter()
        booster.add_valid_dataset(va, "valid")  # replays the full model
        out["valid_auc"] = round(booster.eval_at(1)["auc"], 6)
        out["valid_replay_s"] = round(time.perf_counter() - t0, 1)
    out.update(hbm_stats())
    booster.save_model_to_file("/tmp/northstar_model.txt")
    return out


def run_reference(Xtr, ytr, Xva, yva) -> dict:
    """Reference CLI at the identical config (1 CPU core on this box),
    timed via its own per-iteration log; valid AUC computed by loading
    its model through our (format-compatible) loader."""
    exe = bench.build_reference_cli()
    if exe is None:
        return {"ref_error": "reference CLI unavailable"}
    # "v2": the original run wrote this CSV from a sliced-draw variant of
    # the generator; the n_valid split draws different labels, so the two
    # data versions must never share a cache path.  bench.py CSVs hold
    # the SAME train rows (make_data keeps the train draw bit-identical
    # under n_valid) — reuse one if present instead of a multi-minute
    # 10M-row savetxt.
    import glob

    data_path = f"/tmp/ns_ref_{ROWS}_v2.csv"
    if not os.path.exists(data_path):
        for cand in sorted(glob.glob(f"/tmp/bench_r{ROWS}_t*_l255_b255.csv")):
            log(f"reusing bench CSV {cand}")
            os.link(cand, data_path)
            break
    if not os.path.exists(data_path):
        log("writing reference CSV ...")
        np.savetxt(data_path, np.column_stack([ytr, Xtr]), fmt="%.6g",
                   delimiter=",")
    model_path = "/tmp/ns_ref_model.txt"
    log(f"running reference CLI ({TREES} trees at {ROWS} rows) ...")
    spt, total, proc = bench.run_reference_cli(
        exe, data_path, model_path, TREES, timeout_s=4 * 3600)
    if spt is None:
        return {"ref_error": proc.stderr[-300:] or proc.stdout[-300:]}
    out = {
        "ref_total_wall_s": round(total, 1),
        "ref_sec_per_tree": round(spt, 4),
    }
    try:
        out["ref_train_auc"] = round(
            bench._model_train_auc(model_path, Xtr, ytr), 6)
        if Xva is not None:
            out["ref_valid_auc"] = round(
                bench._model_train_auc(model_path, Xva, yva), 6)
    except Exception as e:
        out["ref_auc_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def main() -> None:
    log(f"north-star run: {ROWS} rows + {VALID} valid, {TREES} trees")
    t0 = time.perf_counter()
    Xtr, ytr, Xva, yva = make_split_data()
    log(f"data gen: {time.perf_counter() - t0:.1f}s")
    result = {"config": "BASELINE.json #2 (HIGGS-10M shape)"}
    try:
        result.update(run_ours(Xtr, ytr, Xva, yva))
    except Exception as e:
        import traceback
        traceback.print_exc(file=sys.stderr)
        result["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    if os.environ.get("NS_REF", "1") != "0":
        try:
            result.update(run_reference(Xtr, ytr, Xva, yva))
        except Exception as e:
            result["ref_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    if result.get("ref_sec_per_tree") and result.get("steady_sec_per_tree"):
        result["vs_ref_1core"] = round(
            result["ref_sec_per_tree"] / result["steady_sec_per_tree"], 3)
    os.makedirs(BENCH_DIR, exist_ok=True)
    artifact = os.path.join(BENCH_DIR, "northstar_r4.json")
    from lightgbm_tpu.resilience.atomic import atomic_write_json

    atomic_write_json(artifact, result, sort_keys=False)
    try:  # self-describing evidence next to the artifact (obs)
        from lightgbm_tpu.obs import RunManifest, manifest_path

        manifest = RunManifest.collect(
            "northstar",
            config={"rows": ROWS, "valid_rows": VALID, "trees": TREES,
                    "num_leaves": NUM_LEAVES, "num_bins": NUM_BINS,
                    "checkpoint_every": CHECKPOINT_EVERY},
            result=result,
            warmup={"compiles_first_tree":
                        result.get("compiles_first_tree"),
                    "compiles_steady_loop":
                        result.get("compiles_steady_loop")},
            per_tree_reservoir="tree_dispatch_s",
        )
        log(f"manifest: {manifest.write(manifest_path(artifact))}")
    except Exception as e:
        log(f"manifest write failed: {type(e).__name__}: {e}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
