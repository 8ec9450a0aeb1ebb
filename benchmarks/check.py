"""The comparison that decides ``correct``.

One side stands in the program's place (the program itself, or the
reference computed in a lower precision, or with a fault planted); the
other is the plain reference in the precision the configuration states.
Every number has a limit of its own in ``limits/<config>.json``, set from
readings (PERF.md gives them); a run is correct when no number passes its
limit.
"""

from __future__ import annotations

import numpy as np

import cells
from references import gbdt_replay


def _gaps(a, b) -> np.ndarray:
    """Every entry's gap, against the reference's own size or the median
    entry's, whichever is larger: some entries are all but zero."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if len(a) != len(b):
        return np.array([np.inf])
    if len(b) == 0:
        return np.array([0.0])
    scale = np.maximum(np.abs(b), np.median(np.abs(b)))
    return np.abs(a - b) / np.maximum(scale, 1e-300)


def side_of_program(trees: list, snapshots: list, loss_of) -> list:
    """The program's answers in the shape ``compare`` takes: ``snapshots``
    are its scores before the first tree and after each."""
    out = []
    for t, tree in enumerate(trees):
        n_int = tree["num_leaves"] - 1
        out.append({
            "leaf_value": tree["leaf_value"],
            "leaf_count": tree["leaf_count"],
            "internal_count": tree["internal_count"],
            "split_gain": tree["split_gain"],
            "chosen": {i: (int(tree["split_feature_real"][i]),
                           float(tree["threshold_real"][i]))
                       for i in range(n_int)},
            "scores": snapshots[t + 1],
            "loss": loss_of(snapshots[t + 1]),
        })
    return [{"scores": snapshots[0]}] + out


def side_of_replay(replayed: dict) -> list:
    """A replay (the control, or a planted fault) in the same shape."""
    out = []
    for tree in replayed["trees"]:
        out.append({
            "leaf_value": tree["leaf_value"],
            "leaf_count": tree["leaf_count"],
            "internal_count": tree["internal_count"],
            "split_gain": tree["split_gain"],
            "chosen": {s["node"]: (s["best_col"], s["best_threshold"])
                       for s in tree["searched"]},
            "scores": tree["scores"], "loss": tree["loss"],
        })
    return [{"scores": replayed["trees"][0]["start"]}] + out


def compare(side: list, reference: dict, X: np.ndarray,
            detail: list | None = None) -> dict:
    """Every number that can be compared, over the trees the reference
    followed: the worst tree's reading of each.  ``limits/<config>.json``
    says which of them decide ``correct``.

    ``*_gap`` is the worst leaf or node; ``*_median_gap`` the median one;
    ``step_gap`` is the tree's whole step over the rows, ``|step -
    reference's step|`` summed over ``|reference's step|`` summed: a leaf
    weighs as many rows as it holds.  ``*_argmax_gap`` is how far the gain
    of the chosen split lies under the best the reference finds: at the
    first tree's root (the root search), and at the sampled nodes below a
    root (the fused split step's search).  ``detail`` collects every
    searched node's reading (tools/limits.py)."""
    ref = reference["trees"]
    lam2 = reference["lambda_l2"]
    n = X.shape[0]
    names = ("count_mismatch", "leaf_value_gap", "leaf_value_median_gap",
             "gain_gap", "gain_median_gap", "root_argmax_gap",
             "node_argmax_gap", "step_gap", "loss_gap")
    num = {k: 0.0 for k in names}
    if len(side) - 1 != len(ref):
        return {k: float("inf") for k in names}

    def worse(name, value):
        num[name] = max(num[name], float(value))

    for t, (got, want) in enumerate(zip(side[1:], ref)):
        if len(got["leaf_count"]) != len(want["leaf_count"]):
            return {k: float("inf") for k in names}
        num["count_mismatch"] += float(
            np.sum(np.rint(got["leaf_count"]) != want["leaf_count"])
            + np.sum(np.rint(got["internal_count"]) != want["internal_count"])
            + (np.rint(got["leaf_count"]).sum() != n))
        for name, key in (("leaf_value", "leaf_value"),
                          ("gain", "split_gain")):
            gaps = _gaps(got[key], want[key])
            worse(name + "_gap", gaps.max())
            worse(name + "_median_gap", np.median(gaps))
        for s in want["searched"]:
            col, thr = got["chosen"][s["node"]]
            chosen = gbdt_replay.gain_of(
                X, want["node_rows"][s["node"]], want["grad"], want["hess"],
                col, thr, lam2)
            worse("node_argmax_gap" if s["node"] else "root_argmax_gap",
                  (s["best_gain"] - chosen) / max(s["best_gain"], 1e-300))
            if detail is not None:
                detail.append({"tree": t, "node": s["node"], "rows": s["rows"],
                               "best_gain": s["best_gain"], "chosen": chosen})
        diff = np.abs(got["scores"] - want["scores"])
        worse("step_gap", diff.sum(dtype=np.float64) / max(float(np.abs(
            want["scores"] - want["start"]).sum(dtype=np.float64)), 1e-300))
        worse("loss_gap", abs(got["loss"] - want["loss"])
              / max(abs(want["loss"]), 1e-300))
    return num


def limits_of(config_name: str) -> dict:
    return cells.read_json(cells.HERE, "limits", config_name + ".json")


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result line, each number beside its limit."""
    table = {k: {"value": float(numbers[k]), "limit": float(v["limit"])}
             for k, v in limits.items()}
    ok = all(np.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
