"""LambdaRank benchmark at MSLR-like scale (BASELINE.json config #4).

MSLR-WEB10K-shaped synthetic workload: skewed query lengths (lognormal,
median ~100, long tail past 1000 — the distribution the bucketed
objective in objectives_rank.py exists for), 136 features, graded 0-4
relevance.  Trains ours and the reference CLI on the SAME csv + .query
side file and reports s/tree + train NDCG@10
(/root/reference/src/objective/rank_objective.hpp:19-227).

Env: RANKBENCH_QUERIES (default 1000), RANKBENCH_TREES (default 30),
RANKBENCH_PLATFORM (pin JAX platform), RANKBENCH_SKIP_REF=1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NQ = int(float(os.environ.get("RANKBENCH_QUERIES", 1000)))
TREES = int(os.environ.get("RANKBENCH_TREES", 30))
F, LEAVES, BINS, LR = 136, 31, 255, 0.1


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_data(nq, seed=29):
    rng = np.random.RandomState(seed)
    # skewed sizes: lognormal median ~100, clipped to [8, 1250] (MSLR-ish)
    sizes = np.clip(
        np.rint(np.exp(rng.normal(np.log(100), 0.8, nq))), 8, 1250
    ).astype(np.int64)
    n = int(sizes.sum())
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32) * (rng.rand(F) < 0.2)
    score = X @ w + 0.5 * rng.randn(n).astype(np.float32)
    # graded labels by within-query quantile of the latent score
    y = np.zeros(n, np.int32)
    start = 0
    for s in sizes:
        q = score[start:start + s]
        ranks = np.searchsorted(np.sort(q), q, side="left") / max(s - 1, 1)
        y[start:start + s] = np.clip((ranks * 5).astype(int), 0, 4)
        start += s
    return X, y.astype(np.float32), sizes


def ndcg_at_10(scores, y, sizes):
    from lightgbm_tpu.dcg import label_gains_from_config
    gains = np.asarray(label_gains_from_config(""), np.float64)
    total, used, start = 0.0, 0, 0
    for s in sizes:
        ys = y[start:start + s].astype(int)
        ss = scores[start:start + s]
        k = min(10, s)
        disc = 1.0 / np.log2(np.arange(2, k + 2))
        top = np.argsort(-ss, kind="stable")[:k]
        dcg = float((gains[ys[top]] * disc).sum())
        ideal = np.sort(ys)[::-1][:k]
        idcg = float((gains[ideal] * disc).sum())
        if idcg > 0:
            total += dcg / idcg
            used += 1
        start += s
    return total / max(used, 1)


def main():
    import jax

    plat = os.environ.get("RANKBENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    platform = jax.devices()[0].platform  # stamped BEFORE timing anything
    if platform != "tpu" and not plat:
        sys.exit(f"backend is {platform!r}, not tpu; set RANKBENCH_PLATFORM "
                 "to name another platform explicitly")

    X, y, sizes = make_data(NQ)
    n = len(y)
    log(f"{NQ} queries, {n} rows, sizes median={int(np.median(sizes))} "
        f"max={int(sizes.max())}")
    results = {"queries": NQ, "rows": n, "trees": TREES}

    import lightgbm_tpu as lgb

    params = {
        "objective": "lambdarank", "metric": "ndcg", "ndcg_eval_at": [10],
        "num_leaves": LEAVES, "max_bin": BINS, "learning_rate": LR,
        "min_data_in_leaf": 50, "verbose": -1,
    }
    os.environ.setdefault("LGBM_TPU_STOP_LAG", "4")
    ds = lgb.Dataset(X, label=y, group=sizes)
    # warm the jit caches: first-iteration compile must not ride s/tree.
    # Cold vs warm is printed explicitly (VERDICT r3 item 9).
    t0 = time.perf_counter()
    lgb.train(params, ds, num_boost_round=2)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=TREES)
    ours_s = (time.perf_counter() - t0) / TREES
    log(f"cold (2 trees + compile): {cold_s:.2f}s; warm: {ours_s:.4f}s/tree")
    pred = np.asarray(bst.predict(X, raw_score=True))
    ours_ndcg = ndcg_at_10(pred, y, sizes)
    results["ours"] = {"sec_per_tree": round(ours_s, 4),
                       "ndcg@10": round(ours_ndcg, 4)}
    log(f"ours: {ours_s:.3f}s/tree NDCG@10={ours_ndcg:.4f}")

    if os.environ.get("RANKBENCH_SKIP_REF", "0") == "0":
        import bench
        exe = bench.build_reference_cli()
        if exe:
            csv = "/tmp/rankbench.csv"
            np.savetxt(csv, np.column_stack([y, X]), fmt="%.6g",
                       delimiter=",")
            np.savetxt(csv + ".query", sizes, fmt="%d")
            model = "/tmp/rankbench_ref.txt"
            conf = [
                "task=train", f"data={csv}", "objective=lambdarank",
                f"num_trees={TREES}", f"num_leaves={LEAVES}",
                f"max_bin={BINS}", f"learning_rate={LR}",
                "min_data_in_leaf=50", f"output_model={model}",
                "is_save_binary_file=false", "verbosity=1",
            ]
            t0 = time.perf_counter()
            p = subprocess.run([exe] + conf, capture_output=True, text=True,
                               timeout=7200)
            total = time.perf_counter() - t0
            if p.returncode == 0:
                sec = None
                for line in p.stdout.splitlines():
                    if "seconds elapsed, finished iteration" in line:
                        sec = float(line.split("]")[-1].strip().split()[0])
                ref_pred = np.asarray(
                    lgb.Booster(model_file=model).predict(X, raw_score=True))
                ref_s = (sec or total) / TREES
                ref_ndcg = ndcg_at_10(ref_pred, y, sizes)
                results["ref"] = {"sec_per_tree": round(ref_s, 4),
                                  "ndcg@10": round(ref_ndcg, 4)}
                results["vs_ref"] = round(ref_s / ours_s, 3)
                results["ndcg_gap"] = round(abs(ref_ndcg - ours_ndcg), 4)
                log(f"ref: {ref_s:.3f}s/tree NDCG@10={ref_ndcg:.4f}")
            else:
                log(f"ref failed: {p.stdout[-300:]} {p.stderr[-300:]}")
    results["platform"] = platform
    print(json.dumps(results))


if __name__ == "__main__":
    main()
