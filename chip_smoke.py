"""Does the system still start on the chip?  train -> predict -> serve, once.

Drives the library's main path through the entry points a user calls
(``lgb.train``, ``Booster.predict``, ``serving.ServingEngine``) at the
HIGGS-like width the repo's only chip history is for — 1,000,000 x 28
float32, 255 bins, 255 leaves — with the depth cut to 6 trees, checks
every leg by the repo's own means, and prints as the last line of stdout
``{"ok": true, "device": {"platform", "kind", "count"}}`` — exactly
those keys; per-leg seconds and peak HBM are on the ``report:`` line
before it.  One process; library defaults; data from a seed.

    python chip_smoke.py                        # on the chip
    python chip_smoke.py --cpu-rehearsal 20000  # off it, explicitly

With no TPU it exits non-zero and prints no result: the rehearsal is an
explicit argument, never a detection.  A leg that raises ends the run.
Times printed here are wall clock around a whole leg, for reading a log;
they are not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np

FULL_ROWS, N_FEAT, TREES, MESH_TREES = 1_000_000, 28, 6, 3
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "min_data_in_leaf": 100, "learning_rate": 0.1, "verbose": 1}
# What the CPU path (segment-sum histograms, jax.numpy search) gives for
# the same seed at the full size.  Produced here, jax 0.9.0, by
#   JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal 1000000
# which prints them in its train and predict legs.
REF_ROOT_SPLIT = (4, 48)  # (inner feature, threshold bin) of tree 0's root
REF_VALID_AUC = 0.8289  # held-out AUC after TREES trees
AUC_BAND = 0.005
REQUEST_ROWS = (1, 37, 128, 1000)


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def make_data(n: int, n_valid: int, seed: int = 7):
    """HIGGS-like: 28 features, a nonlinear decision boundary, label
    noise; the held-out rows come from the same boundary."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, N_FEAT).astype(np.float32)
    w1, w2 = rng.randn(N_FEAT), rng.randn(N_FEAT)

    def label(X):
        z = X @ w1 + 0.5 * (X**2 - 1.0) @ w2 + 0.8 * X[:, 0] * X[:, 1]
        z = (z - z.mean()) / z.std()
        return (z + 0.5 * rng.randn(len(X)) > 0).astype(np.float32)

    y = label(X)
    Xv = rng.randn(n_valid, N_FEAT).astype(np.float32)
    return X, y, Xv, label(Xv)


def auc(y, score) -> float:
    order = np.argsort(score, kind="stable")
    rank = np.empty(len(y))
    rank[order] = np.arange(1, len(y) + 1)
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return float((rank[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


class IterClock:
    """lgb.train callback: wall time at the end of every iteration, so a
    leg can split its first (compiling) call from the steady ones."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.ends = []

    def __call__(self, env):
        self.ends.append(time.perf_counter())

    def split(self):
        first = self.ends[0] - self.t0
        steady = np.diff(self.ends)
        return first, float(steady.mean()) if len(steady) else 0.0


def check_trees(gbdt, rows: int, full: bool):
    """Every tree partitions every row exactly once."""
    for i, t in enumerate(gbdt.models):
        nl = int(t.num_leaves)
        require(nl == 255 if full else nl > 1, f"tree {i} has {nl} leaves")
        lc = int(np.asarray(t.leaf_count)[:nl].sum())
        ic = int(np.asarray(t.internal_count)[0])
        require(lc == rows and ic == rows,
                f"tree {i}: sum(leaf_count)={lc}, internal_count[0]={ic}, "
                f"rows={rows}")
    t0 = gbdt.models[0]
    return int(t0.split_feature[0]), int(t0.threshold_bin[0])


# ------------------------------------------------------------------- legs
def leg_device(rehearsal: bool):
    import jax
    import jaxlib

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" and not rehearsal:
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev['platform']!r}); nothing was run")
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    from lightgbm_tpu import device, native

    cache = device.enable_compile_cache()
    say(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    say(f"compile cache: {cache}")
    say("file parsing: " + ("native library" if native.available()
                            else "python fallback"))
    return dev


def leg_kernels(rehearsal: bool):
    from lightgbm_tpu.analysis import kernel_parity

    say("kernels against references"
        + (" (INTERPRETED: hardware branches not exercised)" if rehearsal
           else ""))
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = kernel_parity.run_all(say, interpret=rehearsal)
        times.append(time.perf_counter() - t0)
        require(all(res.values()), f"kernel parity: {res}")
    return {"first_s": times[0], "steady_s": times[1]}


def leg_train(X, y, full: bool, gate: bool):
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import memmodel

    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=PARAMS)
    ds.construct()
    bin_s = time.perf_counter() - t0
    clock = IterClock()
    booster = lgb.train(PARAMS, ds, num_boost_round=TREES,
                        verbose_eval=False, callbacks=[clock])
    gbdt = booster._gbdt
    jax.block_until_ready(gbdt._scores)
    first, steady = clock.split()
    require(len(gbdt.models) == TREES, f"{len(gbdt.models)} trees grown")
    root = check_trees(gbdt, len(y), full)
    say(f"train: tree 0 root split (feature, bin) = {root}")
    if gate:
        require(root == REF_ROOT_SPLIT,
                f"root split {root} != CPU path's {REF_ROOT_SPLIT}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    model = memmodel.predict(
        rows=len(y), features=N_FEAT, bins=PARAMS["max_bin"],
        leaves=PARAMS["num_leaves"])["peak_bytes"]
    say(f"train: peak_bytes_in_use = {peak} "
        f"({'n/a' if peak is None else f'{peak / 2**20:.0f} MiB'}); "
        f"obs/memmodel predicts {model / 2**20:.0f} MiB")
    return booster, ds, root, {"binning_s": bin_s, "first_s": first,
                               "steady_s": steady}


def leg_predict(booster, Xv, yv, gate: bool):
    import jax.numpy as jnp

    from lightgbm_tpu.models.gbdt import _use_matmul_predict
    from lightgbm_tpu.models.tree import ensemble_sum_raw

    times, raw = [], None
    for _ in range(2):
        t0 = time.perf_counter()
        raw = booster.predict(Xv, raw_score=True)  # returns host numpy
        times.append(time.perf_counter() - t0)
    gbdt = booster._gbdt
    walk = np.asarray(ensemble_sum_raw(
        gbdt._stacked_models(len(gbdt.models), grouped=True),
        jnp.asarray(Xv)))[0]
    diff = float(np.abs(raw - walk).max())
    say(f"predict: {'matmul' if _use_matmul_predict() else 'walk'} "
        f"predictor vs the walk on {len(Xv)} rows, "
        f"max abs diff {diff:.3g}")
    require(raw.shape == (len(Xv),) and np.isfinite(raw).all(),
            "raw scores not finite [rows]")
    require(diff <= 1e-6, f"predictor disagrees with the walk by {diff}")
    valid_auc = auc(yv, raw)
    say(f"predict: held-out AUC after {TREES} trees = {valid_auc:.4f}")
    if gate:
        require(abs(valid_auc - REF_VALID_AUC) <= AUC_BAND,
                f"AUC {valid_auc:.4f} vs CPU path's {REF_VALID_AUC}")
    return {"first_s": times[0], "steady_s": times[1]}


def leg_serve(booster, Xv):
    from lightgbm_tpu.analysis.recompile import compile_counter
    from lightgbm_tpu.serving import ServingEngine

    want = {n: booster.predict(Xv[:n]) for n in REQUEST_ROWS}
    t0 = time.perf_counter()
    engine = ServingEngine(booster)  # warms every bucket
    first = time.perf_counter() - t0
    cc = compile_counter()
    errors = []

    def client():
        for _ in range(3):
            for n in REQUEST_ROWS:
                got = engine.predict(Xv[:n])
                if not np.array_equal(got, want[n]):
                    errors.append(
                        f"{n} rows: max abs diff "
                        f"{float(np.abs(got - want[n]).max()):.3g}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    steady = (time.perf_counter() - t0) / (2 * 3 * len(REQUEST_ROWS))
    compiles = cc.delta()
    say(f"serve: {2 * 3 * len(REQUEST_ROWS)} requests of {REQUEST_ROWS} "
        f"rows from 2 threads, {compiles} compiles after warm-up")
    require(not errors, f"served != Booster.predict: {errors[:3]}")
    require(compiles == 0, f"{compiles} backend compiles after warm-up")
    return {"first_s": first, "steady_s": steady}


def leg_mesh(ds, serial_booster, serial_root, Xv, yv, full: bool):
    """tree_learner=data over every device the process sees."""
    import jax

    import lightgbm_tpu as lgb

    devs = jax.devices()
    clock = IterClock()
    booster = lgb.train({**PARAMS, "tree_learner": "data"}, ds,
                        num_boost_round=MESH_TREES, verbose_eval=False,
                        callbacks=[clock])
    gbdt = booster._gbdt
    jax.block_until_ready(gbdt._scores)
    first, steady = clock.split()
    rows = gbdt.num_data
    root = check_trees(gbdt, rows, full)
    require(root == serial_root,
            f"root split {root} != serial leg's {serial_root}")
    a_mesh = auc(yv, booster.predict(Xv))
    a_serial = auc(yv, serial_booster.predict(Xv, num_iteration=MESH_TREES))
    say(f"mesh: held-out AUC after {MESH_TREES} trees {a_mesh:.4f}, "
        f"serial learner {a_serial:.4f}")
    require(abs(a_mesh - a_serial) <= AUC_BAND, "AUC outside the band")
    # every device holds its shard, and the training state stayed there
    shard = (N_FEAT, rows // len(devs))
    for name, arr in (("_bins_T", gbdt._bins_T), ("_scores", gbdt._scores)):
        held = {s.device for s in arr.addressable_shards}
        require(held == set(devs) and not arr.sharding.is_fully_replicated,
                f"{name} sharding {arr.sharding}")
    require(all(s.data.shape == shard
                for s in gbdt._bins_T.addressable_shards),
            f"bins shards {[s.data.shape for s in gbdt._bins_T.addressable_shards]}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    say(f"mesh: {gbdt._bins_T.sharding}; bytes_in_use per device {in_use}")
    require(all(b is None or b >= shard[0] * shard[1] for b in in_use),
            "a device holds less than its shard of the binned matrix")
    return {"first_s": first, "steady_s": steady}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", type=int, metavar="ROWS", default=0,
        help="run every leg off the chip at ROWS training rows (kernels "
             "interpreted); never a chip result")
    args = ap.parse_args()
    rehearsal = args.cpu_rehearsal > 0
    rows = args.cpu_rehearsal or FULL_ROWS
    full = rows == FULL_ROWS
    gate = full and not rehearsal  # the constants ARE the CPU path's
    t_start = time.perf_counter()

    legs = {}
    dev = leg_device(rehearsal)
    legs["kernels"] = leg_kernels(rehearsal)
    X, y, Xv, yv = make_data(rows, rows // 5)
    booster, ds, root, legs["train"] = leg_train(X, y, full, gate)
    legs["predict"] = leg_predict(booster, Xv, yv, gate)
    legs["serve"] = leg_serve(booster, Xv)
    if dev["count"] >= 4:
        legs["mesh"] = leg_mesh(ds, booster, root, Xv, yv, full)
    else:
        say(f"mesh: skipped: {dev['count']} device")
    for name, t in legs.items():
        say(f"{name}: " + ", ".join(f"{k[:-2]} {v:.2f}s"
                                    for k, v in t.items()))

    import jax

    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in jax.devices())
    report = {"legs_s": {k: {kk: round(vv, 3) for kk, vv in v.items()}
                         for k, v in legs.items()},
              "wall_s": round(time.perf_counter() - t_start, 1),
              "peak_bytes_in_use": peak or None}
    if rehearsal:
        # a rehearsal ends on its report and never prints the result
        # line: nothing read off the last line can take it for a chip run
        say("report: " + json.dumps({"rehearsal": True, **report}))
        return
    say("report: " + json.dumps(report))
    # the result line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
