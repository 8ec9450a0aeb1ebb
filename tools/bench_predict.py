"""Batch-prediction benchmark (VERDICT r3 item 4).

The reference treats batch prediction as a first-class workload: a
threaded streaming file predictor (predictor.hpp:24-155) walking each
tree root-to-leaf per row (gbdt.cpp:621-655).  Ours is an ensemble
gather in one device program (models/tree.py ensemble_sum_raw).  This
tool measures, on the SAME trained model (our text format is
reference-compatible both ways):

  in-memory  — ours: predict normal / raw / leaf-index over N rows
               (includes host->device transfer), warm jit caches
  file-to-file — ours task=predict (in THIS process, through
               cli.main: the chip belongs to one process, and this one
               holds it) vs reference CLI task=predict on the same CSV
               (includes parse + write for both; ours excludes
               interpreter start-up, the reference's includes it)

Prints one JSON line; also appended (by hand) to BASELINE.md.

Env: PRED_ROWS (default 1e6), PRED_TREES (default 100),
PRED_PLATFORM=cpu pins CPU (default: real chip).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

os.environ.setdefault("LGBM_TPU_STOP_LAG", "4")

import numpy as np  # noqa: E402

ROWS = int(float(os.environ.get("PRED_ROWS", 1_000_000)))
TREES = int(os.environ.get("PRED_TREES", 100))
LEAVES, BINS = 255, 255


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    if os.environ.get("PRED_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["PRED_PLATFORM"])
    import lightgbm_tpu as lgb

    platform = jax.devices()[0].platform
    out = {"metric": f"predict_sec_per_{ROWS//1000}k_rows",
           "platform": platform, "trees": TREES}

    X, y = bench.make_data(ROWS)

    # one trained model shared by every path (train with our framework,
    # reference reads the text format)
    model_path = f"/tmp/predbench_model_{ROWS}_{TREES}.txt"
    if not os.path.exists(model_path):
        log(f"training {TREES}-tree model ...")
        params = {"objective": "binary", "num_leaves": LEAVES,
                  "max_bin": BINS, "learning_rate": 0.1,
                  "min_data_in_leaf": 100, "verbose": -1}
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(params, ds, num_boost_round=TREES)
        bst.save_model(model_path)
    bst = lgb.Booster(model_file=model_path)

    # ---- in-memory (ours): warm then measure, one device program
    for name, fn in (
        ("normal", lambda: bst.predict(X)),
        ("raw", lambda: bst.predict(X, raw_score=True)),
        ("leaf_index", lambda: bst.predict(X, pred_leaf=True)),
    ):
        fn()  # warm: compile + stack cache
        t0 = time.perf_counter()
        r = fn()
        dt = time.perf_counter() - t0
        out[f"ours_{name}_s"] = round(dt, 4)
        log(f"ours in-memory {name}: {dt:.3f}s for {ROWS} rows "
            f"({r.shape})")

    # ---- file-to-file: ours CLI vs reference CLI on the same CSV
    key = f"r{ROWS}_t{bench.TREES}_l{LEAVES}_b{BINS}"
    csv = f"/tmp/bench_{key}.csv"
    if not os.path.exists(csv):
        log("writing CSV ...")
        np.savetxt(csv, np.column_stack([y, X]), fmt="%.6g", delimiter=",")

    from lightgbm_tpu import cli

    t0 = time.perf_counter()
    rc = cli.main(["task=predict", f"data={csv}",
                   f"input_model={model_path}",
                   "output_result=/tmp/predbench_ours.tsv"])
    out["ours_file_s"] = round(time.perf_counter() - t0, 2)
    if rc != 0:
        out["ours_file_error"] = f"cli.main returned {rc}"
    log(f"ours file-to-file (in-process, warm jit caches): "
        f"{out['ours_file_s']}s")

    exe = bench.build_reference_cli()
    if exe is not None:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [exe, "task=predict", f"data={csv}",
             f"input_model={model_path}",
             "output_result=/tmp/predbench_ref.tsv"],
            capture_output=True, text=True, timeout=3600)
        out["ref_file_s"] = round(time.perf_counter() - t0, 2)
        if proc.returncode != 0:
            out["ref_file_error"] = proc.stderr[-300:]
        elif not out.get("ours_file_error"):
            # numeric parity between the two result files
            a = np.loadtxt("/tmp/predbench_ours.tsv")
            b = np.loadtxt("/tmp/predbench_ref.tsv")
            out["file_pred_max_abs_diff"] = float(np.abs(a - b).max())
        log(f"reference file-to-file: {out['ref_file_s']}s")
        if out.get("ours_normal_s"):
            out["vs_ref_inmem_vs_file"] = round(
                out["ref_file_s"] / out["ours_normal_s"], 2)
        if out.get("ours_file_s") and not out.get("ours_file_error"):
            out["vs_ref_file"] = round(
                out["ref_file_s"] / out["ours_file_s"], 2)

    os.makedirs(os.path.join(REPO, ".bench"), exist_ok=True)
    from lightgbm_tpu.resilience.atomic import atomic_write_json

    atomic_write_json(os.path.join(REPO, ".bench", "predict_bench.json"),
                      out, sort_keys=False)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
