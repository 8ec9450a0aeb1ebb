"""Reconstruct the north-star result row after a mid-eval crash.

The 500-tree run completed training (checkpointed model + per-segment
timings in .bench/northstar_progress.jsonl) but the TPU worker crashed
during the FINAL eval program.  This tool recomputes the missing
evidence from the saved artifacts:

  * train AUC  — from the last progress checkpoint (device-evaluated
    during the run);
  * valid AUC  — by loading /tmp/northstar_model.txt (the 500-tree
    checkpoint) and batch-predicting the held-out rows;
  * steady s/tree — tree-count-weighted mean of the per-segment rates,
    excluding the first segment (it carries ~12 lazy per-tier Mosaic
    compiles; reported separately);
  * merges the reference-CLI rows from northstar_r4.json if present.

Writes the merged row back to .bench/northstar_r4.json.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.join(REPO, ".bench")
ROWS = int(float(os.environ.get("NS_ROWS", 10_000_000)))
VALID = int(float(os.environ.get("NS_VALID", 1_000_000)))
MODEL = os.environ.get("NS_MODEL", "/tmp/northstar_model.txt")


def main() -> None:
    rows = [json.loads(l) for l in
            open(os.path.join(BENCH_DIR, "northstar_progress.jsonl"))]
    # keep the LAST run's monotone tail (the file appends across runs)
    tail = []
    for r in rows:
        if tail and r["trees"] <= tail[-1]["trees"]:
            tail = []
        tail.append(r)
    segs = tail
    total_trees = segs[-1]["trees"]
    steady = [s for s in segs if s["trees"] > segs[0]["trees"]]
    w = [s["trees"] for s in segs]
    w = np.diff([0] + w)
    spt_all = float(np.sum(
        [s["seg_sec_per_tree"] * dw for s, dw in zip(segs, w)]) / sum(w))
    spt_steady = float(np.sum(
        [s["seg_sec_per_tree"] * dw
         for s, dw in zip(segs[1:], w[1:])]) / sum(w[1:]))

    out_path = os.path.join(BENCH_DIR, "northstar_r4.json")
    result = {}
    if os.path.exists(out_path):
        result = json.load(open(out_path))
    result.update({
        "config": "BASELINE.json #2 (HIGGS-10M shape), 500 trees",
        "rows": ROWS, "valid_rows": VALID, "trees": total_trees,
        "steady_sec_per_tree": round(spt_steady, 4),
        "first_seg_sec_per_tree": segs[0]["seg_sec_per_tree"],
        "mean_sec_per_tree_incl_compiles": round(spt_all, 4),
        "total_train_wall_s": segs[-1]["elapsed_s"],
        "train_auc": segs[-1]["train_auc"],
        "note": ("final eval program crashed the TPU worker; train AUC "
                 "from the tree-500 device checkpoint, valid AUC "
                 "recomputed from the saved model"),
    })

    try:
        X, y, Xv, yv = bench.make_data(ROWS, seed=7, n_valid=VALID)
        result["valid_auc"] = round(
            bench._model_train_auc(MODEL, Xv, yv), 6)
        # the reference model's valid AUC, if its run finished
        ref_model = "/tmp/ns_ref_model.txt"
        if os.path.exists(ref_model) and "ref_valid_auc" not in result:
            result["ref_train_auc"] = round(
                bench._model_train_auc(ref_model, X, y), 6)
            result["ref_valid_auc"] = round(
                bench._model_train_auc(ref_model, Xv, yv), 6)
    except Exception as e:
        result["valid_auc_error"] = f"{type(e).__name__}: {str(e)[:200]}"

    if result.get("ref_sec_per_tree"):
        result["vs_ref_1core"] = round(
            result["ref_sec_per_tree"] / result["steady_sec_per_tree"], 3)
    from lightgbm_tpu.resilience.atomic import atomic_write_json

    atomic_write_json(out_path, result, sort_keys=False)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
