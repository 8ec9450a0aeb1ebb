"""Per-shard wall-clock of the data-parallel learner's split loop
(VERDICT r4 item 1 done-criterion: DP per-shard s/tree within ~15% of
the serial fast path at fixed local rows).

Runs on whatever devices exist: a 1-device mesh on the real chip times
the DP loop STRUCTURE (collectives degenerate but the program is the
per-shard program: record compaction kernel + window histogram via the
reduce-scatter hook + Pallas shard search + canonical buffer updates);
the serial fast path (the fused grower) on the same rows is the yardstick.

Env: DPB_ROWS (default 1M), DPB_TREES (default 12), DPB_MODES
(comma list from {serial,dp_record,dp_canonical}).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROWS = int(float(os.environ.get("DPB_ROWS", 1_000_000)))
TREES = max(3, int(os.environ.get("DPB_TREES", 12)))  # 2 warm + timed
LEAVES, BINS = 255, 255
MODES = os.environ.get(
    "DPB_MODES", "serial,dp_record,dp_canonical").split(",")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io import BinnedDataset, Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower

    platform = jax.devices()[0].platform
    out = {"metric": "dp_shard_sec_per_tree", "platform": platform,
           "rows": ROWS, "trees": TREES}
    X, y = bench.make_data(ROWS)
    cfg = Config(objective="binary", num_leaves=LEAVES, max_bin=BINS,
                 min_data_in_leaf=100, verbose=-1)
    ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
    obj = create_objective(cfg, ds.metadata, ds.num_data)

    def run(mode):
        gb = GBDT(cfg, ds, obj)
        if mode != "serial":
            mesh = data_mesh(num_devices=len(jax.devices()))
            gb._grow = make_data_parallel_grower(
                mesh, num_bins=gb._num_bins, max_leaves=gb.max_leaves,
                sorted_hist=gb._use_pallas_hist(),
                record=(mode == "dp_record"))
        t0 = time.perf_counter()
        # TWO warm iterations: the second train_one_iter triggers a
        # further trace (donated-score layout), measured ~14s at 200k —
        # warming once would leak that compile into the steady window
        gb.train_one_iter()
        gb.train_one_iter()
        jax.block_until_ready(gb._scores)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(TREES - 2):
            gb.train_one_iter()
        jax.block_until_ready(gb._scores)
        per_tree = (time.perf_counter() - t0) / (TREES - 2)
        auc = gb.eval_at(0).get("auc")
        return per_tree, compile_s, auc

    for mode in MODES:
        try:
            per_tree, compile_s, auc = run(mode)
            out[f"{mode}_s_per_tree"] = round(per_tree, 4)
            out[f"{mode}_compile_s"] = round(compile_s, 1)
            if auc is not None:
                out[f"{mode}_auc"] = round(float(auc), 5)
            log(f"{mode}: {per_tree:.4f} s/tree (compile+1st {compile_s:.1f}s)")
        except Exception as e:  # keep the sweep going
            out[f"{mode}_error"] = repr(e)[:300]
            log(f"{mode} FAILED: {e!r}")
    if "serial_s_per_tree" in out and "dp_record_s_per_tree" in out:
        out["dp_record_vs_serial"] = round(
            out["dp_record_s_per_tree"] / out["serial_s_per_tree"], 3)
    os.makedirs(os.path.join(REPO, ".bench"), exist_ok=True)
    from lightgbm_tpu.resilience.atomic import atomic_write_json

    atomic_write_json(os.path.join(REPO, ".bench", "dp_shard_bench.json"),
                      out, sort_keys=False)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
