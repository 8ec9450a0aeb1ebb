"""Bisect the DP-vs-serial on-chip gap: time grow_tree variants that
add the data-parallel structure one piece at a time.

  serial_opt    — the fused grower (learners/fused.py)
  hooks_nomesh  — record partition + DP-style hooks (pallas search2 via
                  canonical layout, jnp root search) but NO shard_map:
                  isolates hook structure from SPMD
  dp_record     — the real 1-device-mesh DP grower

Env: DB_ROWS (default 200k), DB_TREES (default 4).
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROWS = int(float(os.environ.get("DB_ROWS", 200_000)))
TREES = int(os.environ.get("DB_TREES", 4))
L, B = 255, 255


def main():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learners.serial import TreeLearnerParams, grow_tree
    from lightgbm_tpu.ops.histogram import select_single_hist_fn
    from lightgbm_tpu.ops.split import find_best_split

    from lightgbm_tpu.io import BinnedDataset, Metadata

    # real structured data so trees actually grow to the leaf budget
    X, y = bench.make_data(ROWS)
    ds = BinnedDataset.from_matrix(
        X, Metadata(label=y.astype(np.float32)),
        config=Config(max_bin=B))
    bins_T = jnp.asarray(ds.dense_bins().T)
    F = int(bins_T.shape[0])
    p = jnp.float32(0.5)
    grad = jnp.asarray(p - y.astype(np.float32))
    hess = jnp.full(ROWS, p * (1 - p), jnp.float32)
    bag = jnp.ones(ROWS, jnp.float32)
    fmask = jnp.ones(F, bool)
    nbpf = jnp.full(F, B, jnp.int32)
    is_cat = jnp.zeros(F, bool)
    params = TreeLearnerParams.from_config(
        Config(min_data_in_leaf=100, min_sum_hessian_in_leaf=1e-3))

    hist_local = select_single_hist_fn(B, True)

    def search_fn(hist, sg, sh, c, can, fm, nb, ic, prm):
        return find_best_split(
            hist, sg, sh, c, fm, nb, ic,
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split, can)

    def search2_fn(hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                   fm, nb, ic, prm):
        from lightgbm_tpu.ops.pallas_search import search2_pallas

        return search2_pallas(
            hl, hr, lsg, lsh, lc, rsg, rsh, rc, can, fm, nb, ic,
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split)

    from lightgbm_tpu.models.gbdt import GBDT  # noqa: F401  (env parity)

    def timeit(name, fn):
        t0 = time.perf_counter()
        nl = int(np.asarray(fn()))  # host transfer = hard sync
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(TREES):
            out = fn()
        nl = int(np.asarray(out))
        per = (time.perf_counter() - t0) / TREES
        print(f"{name}: {per:.4f} s/tree (compile+1st {compile_s:.1f}s, "
              f"leaves {nl})", flush=True)

    modes = os.environ.get(
        "DB_MODES", "serial_opt,hooks_nomesh,dp_record").split(",")

    if "serial_opt" in modes:
        from lightgbm_tpu.learners import fused

        timeit("serial_opt", lambda: fused.grow_tree(
            bins_T, grad, hess, bag, fmask, nbpf, is_cat, params,
            num_bins=B, max_leaves=L)[0].num_leaves)

    if "hooks_nomesh" in modes:
        timeit("hooks_nomesh", lambda: grow_tree(
            bins_T, grad, hess, bag, fmask, nbpf, is_cat, params,
            num_bins=B, max_leaves=L, hist_fn=hist_local,
            search_fn=search_fn, search2_fn=search2_fn,
            record_mode=True)[0].num_leaves)

    if "dp_record" in modes:
        from lightgbm_tpu.parallel import (
            data_mesh, make_data_parallel_grower)

        grow = make_data_parallel_grower(
            data_mesh(num_devices=len(jax.devices())), num_bins=B,
            max_leaves=L, sorted_hist=True, record=True)
        timeit("dp_record", lambda: grow(
            bins_T, grad, hess, bag, fmask, nbpf, is_cat,
            params)[0].num_leaves)


if __name__ == "__main__":
    main()
