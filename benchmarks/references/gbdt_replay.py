"""A plain leaf-wise GBDT step, replayed over the trees the program grew.

The program's answers are its trees: which column and threshold split each
node, what each leaf adds.  The reference follows the first trees with its
own arithmetic on the raw arrays:

* its own gradients from its own scores (``references/<objective>.py``);
* rows routed by ``x <= threshold`` on the raw values, so the program's
  binning, partition and placement have to agree with the raw data;
* leaf sums, leaf values ``-G / (H + lambda_l2) * learning_rate``, split
  gains ``G_L^2/H_L + G_R^2/H_R - G_P^2/H_P`` in float64 from float32
  gradients;
* at a seeded sample of nodes, the full histogram over every column and
  bin bound, and the best split a search could have found there;
* its own scores, advanced by its own leaf values.

``precision="bfloat16"`` is the control: the same replay with every array
it stores (gradients, hessians, leaf values, scores) rounded to bfloat16,
sums kept in float32 or better, as the MXU would.  ``fault`` plants one of
the faults a training path can have: ``state_unchanged`` (the scores stay
as they were), ``half_batch`` (every other row left out of the sums),
``altered_split`` (the search answers with its second-best column),
``altered_leaf`` (the smallest leaf answers with the wrong sign, in the
tree and in the scores).  Both stand in the program's place when the
comparison is proved to fail.
"""

from __future__ import annotations

import importlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8
FEASIBLE_SLACK = 1e-4  # a candidate counts only if clearly allowed


def bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def route(X: np.ndarray, tree: dict):
    """Rows of every node and the leaf of every row, by the raw values."""
    n = X.shape[0]
    nl = tree["num_leaves"]
    rows = {0: np.arange(n, dtype=np.int32)} if nl > 1 else {}
    leaf_of = np.zeros(n, np.int32)
    node_rows = {}
    for i in range(nl - 1):
        r = rows.pop(i)
        node_rows[i] = r
        f = int(tree["split_feature_real"][i])
        left = X[r, f] <= tree["threshold_real"][i]
        for child, part in ((int(tree["left_child"][i]), r[left]),
                            (int(tree["right_child"][i]), r[~left])):
            if child >= 0:
                rows[child] = part
            else:
                leaf_of[part] = ~child
    return node_rows, leaf_of


def node_sums(tree: dict, leaf_sums: np.ndarray) -> np.ndarray:
    """[nodes, k] sums of every internal node from its leaves' sums."""
    nl = tree["num_leaves"]
    out = np.zeros((max(nl - 1, 0), leaf_sums.shape[1]), np.float64)
    for i in range(nl - 2, -1, -1):
        for child in (int(tree["left_child"][i]), int(tree["right_child"][i])):
            out[i] += out[child] if child >= 0 else leaf_sums[~child]
    return out


def child_sums(tree: dict, i: int, sums: np.ndarray, leaf_sums: np.ndarray):
    def of(child):
        return sums[child] if child >= 0 else leaf_sums[~child]
    return of(int(tree["left_child"][i])), of(int(tree["right_child"][i]))


def split_gain(left, right, lam2: float) -> float:
    """Gain of a split from the (G, H, ...) sums of its two sides."""
    def term(g, h):
        return g * g / (h + lam2)
    return (term(left[0], left[1]) + term(right[0], right[1])
            - term(left[0] + right[0], left[1] + right[1]))


def best_split(X, rows, g, h, bounds, p, runner_up: bool = False):
    """Best gain over every column and every bin bound for the rows of one
    node, with the column and bound that give it.  Candidates keep
    ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` on each side,
    the hessian with a little slack so that a borderline candidate the
    program had to refuse is not held against it.  ``runner_up`` gives
    the best split of the second-best column instead: the search's answer,
    altered as little as an answer can be."""
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    G, H, C = gr.sum(), hr.sum(), len(rows)
    lam2, min_c = p["lambda_l2"], p["min_data_in_leaf"]
    min_h = p["min_sum_hessian_in_leaf"] * (1 + FEASIBLE_SLACK)
    parent = G * G / (H + lam2)

    def one(entry):
        col, ub = entry
        if len(ub) < 2:
            return (-np.inf, col, 0.0)
        b = np.searchsorted(ub, X[rows, col].astype(np.float64), side="left")
        nb = len(ub)
        gl = np.cumsum(np.bincount(b, gr, nb))[:-1]
        hl = np.cumsum(np.bincount(b, hr, nb))[:-1]
        cl = np.cumsum(np.bincount(b, minlength=nb))[:-1]
        ok = (cl >= max(min_c, 1)) & (C - cl >= max(min_c, 1)) \
            & (hl >= min_h) & (H - hl >= min_h)
        if not ok.any():
            return (-np.inf, col, 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, gl * gl / (hl + lam2)
                            + (G - gl) ** 2 / (H - hl + lam2) - parent,
                            -np.inf)
        k = int(np.argmax(gain))
        return (float(gain[k]), col, float(ub[k]))

    with ThreadPoolExecutor(THREADS) as pool:
        ranked = sorted(pool.map(one, bounds), key=lambda t: -t[0])
    return ranked[1] if runner_up else ranked[0]


def gain_of(X, rows, g, h, col: int, threshold: float, lam2: float) -> float:
    left = X[rows, col] <= np.float32(threshold)
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    return split_gain((gr[left].sum(), hr[left].sum()),
                      (gr[~left].sum(), hr[~left].sum()), lam2)


MID, TOP = 64, 8  # a middle node holds n/64 .. n/8 rows


def sample_nodes(tree_index: int, counts: np.ndarray, n: int, seed: int,
                 how_many: int) -> list:
    """The root of the first tree (the root search's program), and seeded
    draws of nodes below a root (the fused split step's search): half of
    them middle nodes, n/64 to n/8 rows, where a wrong choice costs most,
    half of them smaller ones.  The nodes above n/8 are left out only for
    the time a search over them takes (a microsecond a row: ten seconds
    at the root of 8.9M rows)."""
    picks = [0] if tree_index == 0 else []
    rng = np.random.default_rng([int(seed), tree_index, 7])
    below = np.arange(len(counts)) > 0
    mid = np.flatnonzero(below & (counts >= n // MID) & (counts <= n // TOP))
    small = np.flatnonzero(below & (counts < n // MID) & (counts >= 2))
    for pool, k in ((mid, how_many - how_many // 2), (small, how_many // 2)):
        if len(pool):
            picks += list(rng.choice(pool, size=min(k, len(pool)),
                                     replace=False))
    return [int(i) for i in picks]


def replay(data: dict, trees: list, bounds: list, config: dict, seed: int,
           precision: str = "float32", fault: str | None = None,
           sampled: int = 6, picks: list | None = None,
           starts: list | None = None, keep_rows: bool = False) -> dict:
    """Follow ``trees`` (structure only) from zero scores.  Returns, for
    each tree, leaf values, node counts, split gains, the sampled nodes'
    best gains, and the scores and loss after it.  ``picks`` fixes the
    sampled nodes of every tree (a control searches where the reference
    did).  ``starts`` gives the scores every tree starts from (the
    program's own, before that tree): each step is then held on its own,
    as a served model's reference is run over the tokens that were served,
    and an early step's error is not counted again in the later ones.
    ``keep_rows`` keeps every node's rows (tools/explain.py), not only the
    sampled nodes'."""
    p = {**config["defaults_relied_on"], **config["params"]}
    p.setdefault("min_sum_hessian_in_leaf", 10.0)
    p.setdefault("min_data_in_leaf", 100)
    lam2, rate = float(p["lambda_l2"]), float(p["learning_rate"])
    store = bf16 if precision == "bfloat16" else (lambda a: a)
    X = data["X"]
    n = X.shape[0]
    objective = importlib.import_module(
        "references." + config["objective"]).Objective(data, p)
    keep = slice(None, None, 2) if fault == "half_batch" else slice(None)
    scores = np.zeros(n, np.float32)
    out = []
    for t, tree in enumerate(trees):
        if starts is not None:
            scores = store(np.asarray(starts[t], np.float32))
        start = scores
        g, h = objective.gradients(scores)
        g, h = store(g), store(h)
        node_rows, leaf_of = route(X, tree)
        nl = tree["num_leaves"]
        w = np.zeros(n)
        w[keep] = 1.0  # the rows a faulty step would count
        leaf_sums = np.stack([
            np.bincount(leaf_of, g * w, nl), np.bincount(leaf_of, h * w, nl),
            np.bincount(leaf_of, w, nl)], axis=1)
        sums = node_sums(tree, leaf_sums)
        value = store((-leaf_sums[:, 0] / (leaf_sums[:, 1] + lam2) * rate)
                      .astype(np.float32))
        if fault == "altered_leaf":
            value[np.argmin(leaf_sums[:, 2])] *= -1
        gains = np.array([
            split_gain(*child_sums(tree, i, sums, leaf_sums), lam2)
            for i in range(nl - 1)])
        nodes = picks[t] if picks is not None else sample_nodes(
            t, sums[:, 2], n, seed, sampled)
        searched = []
        for i in nodes:
            rows = node_rows[i]
            best, col, thr = best_split(X, rows, g, h, bounds, p,
                                        runner_up=fault == "altered_split")
            searched.append({"node": i, "best_gain": best, "best_col": col,
                             "best_threshold": thr, "rows": len(rows)})
        if fault != "state_unchanged":
            scores = store(scores + value[leaf_of])
        out.append({
            "leaf_value": value, "leaf_count": leaf_sums[:, 2].copy(),
            "leaf_sums": leaf_sums, "leaf_of": leaf_of,
            "abs_grad": float(np.abs(g * w).sum(dtype=np.float64)),
            "internal_count": sums[:, 2].copy(), "split_gain": gains,
            "searched": searched,
            "node_rows": node_rows if keep_rows else {
                i: node_rows[i] for i in nodes},
            "grad": g, "hess": h, "start": start,
            "scores": scores.copy(), "loss": objective.loss(scores),
        })
    return {"trees": out, "objective": objective, "rate": rate,
            "lambda_l2": lam2}
