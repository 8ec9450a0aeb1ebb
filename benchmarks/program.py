"""The one module that touches the system under test.

It builds the program's objects through the calls ``engine.train`` makes
(``lightgbm_tpu.Dataset``, ``lightgbm_tpu.Booster``, ``Booster.update``)
and reads back what the program answered: trees, scores, bin bounds.
Nothing here computes a number that is compared.
"""

from __future__ import annotations

import numpy as np


def build_dataset(config: dict, data: dict):
    import lightgbm_tpu as lgb

    ds = lgb.Dataset(data["X"], label=data["y"], group=data.get("group"),
                     params=dict(config["params"]))
    ds.construct()
    return ds


def build_booster(config: dict, ds):
    import lightgbm_tpu as lgb

    return lgb.Booster(params=dict(config["params"]), train_set=ds)


def sync(booster) -> None:
    import jax

    jax.block_until_ready(booster._gbdt._scores)


def scores(booster) -> np.ndarray:
    """The training scores, copied to the host (a device sync)."""
    return np.asarray(booster._gbdt._scores, np.float32)[0].copy()


def num_trees(booster) -> int:
    return len(booster._gbdt.models)


def bin_bounds(ds) -> list:
    """Upper bound of every bin, for every column the program kept, with
    the column's index in the raw matrix."""
    inner = ds.construct()
    real = np.asarray(inner.real_feature_indices)
    return [(int(real[i]), np.asarray(m.bin_upper_bound, np.float64))
            for i, m in enumerate(inner.bin_mappers)]


TREE_FIELDS = ("split_feature_real", "threshold_real", "left_child",
               "right_child", "split_gain", "internal_value",
               "internal_count", "leaf_value", "leaf_count")


def trees(booster, first: int, count: int | None = None) -> list:
    """Trees from ``first`` on (``count`` of them, or all) as plain numpy,
    cut to their used nodes: what the program says it grew."""
    models = booster._gbdt.models[first:]
    out = []
    for t in models if count is None else models[:count]:
        nl = int(t.num_leaves)
        d = {"num_leaves": nl}
        for f in TREE_FIELDS:
            a = np.asarray(getattr(t, f))
            d[f] = a[:nl] if f.startswith("leaf_") else a[:max(nl - 1, 0)]
        out.append(d)
    return out


def tree_counts(booster, first: int) -> list:
    """(internal_count, leaf_count, left_child, right_child) of every tree
    from ``first`` on: the rows each node held, which is what the required
    work is counted from."""
    return [(t["internal_count"].astype(np.int64),
             t["leaf_count"].astype(np.int64),
             t["left_child"], t["right_child"])
            for t in trees(booster, first)]


def memory_model(config: dict) -> float | None:
    """obs/memmodel's predicted peak for this shape, in bytes (printed
    beside the measured peak; never reported as a metric)."""
    try:
        from lightgbm_tpu.obs import memmodel
    except ImportError:
        return None
    p, g = config["params"], config["generator"]["params"]
    return float(memmodel.predict(
        rows=g["rows"], features=g["features"], bins=p["max_bin"],
        leaves=p["num_leaves"], routing="order")["peak_bytes"])


def free(*objs) -> None:
    """Drop the program's device state before the reference runs: the
    booster and the dataset own every device array between them."""
    import gc

    for o in objs:
        o.__dict__.clear()
    gc.collect()
