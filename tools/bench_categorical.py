"""Categorical-speedup benchmark (BASELINE.json config #3).

Expo-2009-style workload: a few numeric columns plus high-cardinality
categorical columns whose per-category effects drive the label.  Trains
four ways — {ours, reference CLI} x {direct categorical, one-hot
expansion} — and reports s/tree + train AUC for each, reproducing the
reference's headline claim that direct categorical splits beat one-hot
encoding by ~8x at equal accuracy (/root/reference/README.md:19,
docs/Quick-Start.md:21).

Env: CATBENCH_ROWS (default 100_000), CATBENCH_TREES (default 30),
CATBENCH_PLATFORM (pin JAX platform, e.g. cpu), CATBENCH_SKIP_REF=1.

Usage: python tools/bench_categorical.py
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

ROWS = int(float(os.environ.get("CATBENCH_ROWS", 100_000)))
TREES = int(os.environ.get("CATBENCH_TREES", 30))
LEAVES, BINS, MIN_DATA, LR = 63, 255, 100, 0.1
CARDS = (12, 30, 100, 100)  # month / carrier / origin / dest
N_NUM = 4


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_data(n, seed=13):
    rng = np.random.RandomState(seed)
    Xn = rng.randn(n, N_NUM).astype(np.float32)
    cats = [rng.randint(0, c, n) for c in CARDS]
    z = Xn[:, 0] + 0.5 * Xn[:, 1] * Xn[:, 2]
    for c, col in zip(CARDS, cats):
        z = z + rng.randn(c)[col] * 0.8
    z = (z - z.mean()) / z.std()
    y = (z + 0.6 * rng.randn(n) > 0).astype(np.float32)
    # 3-class label from the same latent score (terciles): the
    # multiclass variant of config 3 — K per-class trees per round are
    # the forest-batching B-source the batched re-measure exercises
    zn = z + 0.6 * rng.randn(n)
    ymc = np.digitize(zn, np.quantile(zn, [1 / 3, 2 / 3])).astype(
        np.float32)
    Xc = np.column_stack(cats).astype(np.float32)
    return Xn, Xc, y, ymc


def one_hot(Xc):
    cols = []
    for j, c in enumerate(CARDS):
        eye = np.eye(c, dtype=np.float32)
        cols.append(eye[Xc[:, j].astype(int)])
    return np.concatenate(cols, axis=1)


def auc(y, s):
    order = np.argsort(s)
    r = np.empty(len(y))
    r[order] = np.arange(1, len(y) + 1)
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return (r[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg)


def train_ours(X, y, cat_idx, extra_params=None):
    import lightgbm_tpu as lgb

    os.environ.setdefault("LGBM_TPU_STOP_LAG", "4")
    params = {
        "objective": "binary", "num_leaves": LEAVES, "max_bin": BINS,
        "learning_rate": LR, "min_data_in_leaf": MIN_DATA, "verbose": -1,
    }
    params.update(extra_params or {})
    ds = lgb.Dataset(X, label=y, categorical_feature=cat_idx or None)
    # warm the jit caches (first-iteration compile must not ride the
    # steady-state s/tree; the lru-cached hist/search factories make the
    # second train compile-free at the same shapes).  Cold vs warm is
    # printed explicitly so a published row can never silently contain
    # compile time (VERDICT r3 item 9).
    t0 = time.perf_counter()
    lgb.train(params, ds, num_boost_round=2)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=TREES)
    elapsed = time.perf_counter() - t0
    log(f"  cold (2 trees + compile): {cold_s:.2f}s; "
        f"warm: {elapsed / TREES:.4f}s/tree x {TREES}")
    pred = np.asarray(bst.predict(X, raw_score=True))
    if pred.ndim == 2:  # multiclass: accuracy replaces AUC
        score = float((pred.argmax(axis=1) == y).mean())
    else:
        score = auc(y, pred)
    return elapsed / TREES, score, bst


def train_ref(exe, csv_path, n_cols, cat_idx, tag):
    model = f"/tmp/catbench_{tag}.txt"
    conf = [
        "task=train", f"data={csv_path}", "objective=binary",
        f"num_trees={TREES}", f"num_leaves={LEAVES}", f"max_bin={BINS}",
        f"learning_rate={LR}", f"min_data_in_leaf={MIN_DATA}",
        f"output_model={model}", "is_save_binary_file=false", "verbosity=1",
    ]
    if cat_idx:
        conf.append("categorical_column=" + ",".join(map(str, cat_idx)))
    t0 = time.perf_counter()
    p = subprocess.run([exe] + conf, capture_output=True, text=True,
                       timeout=7200)
    total = time.perf_counter() - t0
    if p.returncode != 0:
        log(f"ref {tag} failed: {p.stdout[-300:]} {p.stderr[-300:]}")
        return None, None
    sec = None
    for line in p.stdout.splitlines():
        if "seconds elapsed, finished iteration" in line:
            sec = float(line.split("]")[-1].strip().split()[0])
    import lightgbm_tpu as lgb

    data = np.loadtxt(csv_path, delimiter=",", dtype=np.float32)
    pred = lgb.Booster(model_file=model).predict(data[:, 1:], raw_score=True)
    return (sec or total) / TREES, auc(data[:, 0], np.asarray(pred))


def main():
    import jax

    plat = os.environ.get("CATBENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    platform = jax.devices()[0].platform  # stamped BEFORE timing anything
    if platform != "tpu" and not plat:
        sys.exit(f"backend is {platform!r}, not tpu; set CATBENCH_PLATFORM "
                 "to name another platform explicitly")

    Xn, Xc, y, ymc = make_data(ROWS)
    X_direct = np.column_stack([Xn, Xc])
    cat_idx = list(range(N_NUM, N_NUM + len(CARDS)))
    results = {}

    log("ours direct-categorical ...")
    s, a, _ = train_ours(X_direct, y, cat_idx)
    results["ours_direct"] = {"sec_per_tree": round(s, 4), "auc": round(a, 4)}
    log(f"  {s:.3f}s/tree AUC={a:.4f}")

    log("ours one-hot ...")
    X_oh = np.column_stack([Xn, one_hot(Xc)])
    s, a, _ = train_ours(X_oh, y, [])
    results["ours_onehot"] = {"sec_per_tree": round(s, 4), "auc": round(a, 4)}
    log(f"  {s:.3f}s/tree AUC={a:.4f}")

    if os.environ.get("CATBENCH_MULTICLASS", "1") != "0":
        # multiclass variant (3-class terciles of the same latent): the
        # K per-class trees per round route through the batched forest
        # dispatch (learners/forest.py) when forest_batching=on — one
        # launch per round instead of K — and must stay BITWISE equal
        # to the sequential per-class loop (forest_batching=off)
        import hashlib

        mc = {"objective": "multiclass", "num_class": 3}
        log("ours multiclass direct, batched per-class trees ...")
        s, a, bst_b = train_ours(X_direct, ymc, cat_idx,
                                 {**mc, "forest_batching": "on"})
        results["ours_mc_batched"] = {
            "sec_per_tree": round(s, 4), "accuracy": round(a, 4)}
        log(f"  {s:.3f}s/tree acc={a:.4f}")
        log("ours multiclass direct, sequential per-class trees ...")
        s, a, bst_s = train_ours(X_direct, ymc, cat_idx,
                                 {**mc, "forest_batching": "off"})
        results["ours_mc_sequential"] = {
            "sec_per_tree": round(s, 4), "accuracy": round(a, 4)}
        log(f"  {s:.3f}s/tree acc={a:.4f}")
        results["mc_batched_parity"] = (
            hashlib.sha256(bst_b.model_to_string().encode()).hexdigest()
            == hashlib.sha256(
                bst_s.model_to_string().encode()).hexdigest())
        results["mc_batched_speedup"] = round(
            results["ours_mc_sequential"]["sec_per_tree"]
            / results["ours_mc_batched"]["sec_per_tree"], 2)
        log(f"  batched vs sequential: "
            f"{results['mc_batched_speedup']}x, parity "
            f"{'OK' if results['mc_batched_parity'] else 'BROKEN'}")

    if os.environ.get("CATBENCH_SKIP_REF", "0") == "0":
        import bench

        exe = bench.build_reference_cli()
        if exe:
            csv_d = "/tmp/catbench_direct.csv"
            np.savetxt(csv_d, np.column_stack([y, X_direct]), fmt="%.6g",
                       delimiter=",")
            log("reference direct-categorical ...")
            s, a = train_ref(exe, csv_d, X_direct.shape[1], cat_idx, "direct")
            if s:
                results["ref_direct"] = {
                    "sec_per_tree": round(s, 4), "auc": round(a, 4)}
                log(f"  {s:.3f}s/tree AUC={a:.4f}")
            csv_o = "/tmp/catbench_onehot.csv"
            np.savetxt(csv_o, np.column_stack([y, X_oh]), fmt="%.6g",
                       delimiter=",")
            log("reference one-hot ...")
            s, a = train_ref(exe, csv_o, X_oh.shape[1], [], "onehot")
            if s:
                results["ref_onehot"] = {
                    "sec_per_tree": round(s, 4), "auc": round(a, 4)}
                log(f"  {s:.3f}s/tree AUC={a:.4f}")

    for k in ("ours", "ref"):
        d, o = results.get(f"{k}_direct"), results.get(f"{k}_onehot")
        if d and o:
            results[f"{k}_direct_speedup_vs_onehot"] = round(
                o["sec_per_tree"] / d["sec_per_tree"], 2)
    results["platform"] = platform
    print(json.dumps({"rows": ROWS, "trees": TREES, **results}))
    out = os.environ.get("CATBENCH_OUT")
    if out:
        # benchdiff-ready row (raw bench-row shape: metric/value/unit):
        # the headline stays ours-direct s/tree so the row diffs
        # cleanly against the committed config-3 baseline
        from lightgbm_tpu.resilience.atomic import atomic_write_json

        atomic_write_json(out, {
            "metric": "categorical_config3_ours_direct",
            "value": results["ours_direct"]["sec_per_tree"],
            "unit": "s/tree",
            "platform": platform,
            "train_auc": results["ours_direct"]["auc"],
            "rows": ROWS, "trees": TREES,
            "results": results,
        })
        log(f"wrote {out}")


if __name__ == "__main__":
    main()
