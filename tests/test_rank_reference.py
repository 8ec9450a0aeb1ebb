"""The program's pair gradients against the plain reference.

``lightgbm_tpu/objectives_rank.py`` (bucketed, padded ``[C, Q, Q]`` tensors
under ``lax.map``, float32) is held to
``benchmarks/references/lambdarank.py`` (numpy, a query at a time, float64
sums) on seeded scores WITH TIES, over queries that sit on both sides of a
bucket's edge (16 / 17 rows), a single row, a pair, 130 rows (a second
bucket) and 1,000 rows (the longest bucket, several ``lax.map`` chunks
wide), a query of equal labels and a query of equal scores.  The same
comparison has to fail for three planted departures, or it holds nothing.

The program moves scores, labels and gains from slot order to rank order
as payload operands of ONE ``lax.sort``, and the row sums back by a second
(PR 33; five XLA gathers by the permutation were 212 of its 267 ms a tree
on the chip).  ``EDGE_CASES`` hold that formulation where it could part from
``argsort`` + ``take_along_axis``: ties everywhere, full and one-row
queries, chunks of queries of no rows; and one test asks for ``==`` with
the gather formulation, restated here.

Tolerances, each with its reason:

* a row's gradient against ``GRAD_TOL`` = 1e-5 of the query's largest
  |gradient| (hessian alike).  The program sums a row's up to 999 pair
  terms in float32, the reference in float64: each term is off by a few
  float32 roundings (6e-8 relative: the products, ``exp``, the division),
  and the sum of n of them by about sqrt(n) roundings of the partial sums;
  a mid-grade row's terms cancel (it is h in some pairs and l in others),
  so its error is measured against the query's largest gradient and not
  its own.  Read on the CPU over five seeds: 3.7e-7 at worst; the limit
  leaves 27 times that for a backend whose ``exp`` and division round
  otherwise.  The planted departures read 6.3e-3 (scores in bfloat16),
  5.4 (ties reversed) and 6.7 (no score-distance term), and are asked
  for 1e-3.
* rows the reference gives exactly 0 (a query of equal labels, a single
  row) must be exactly 0 in the program too: no tolerance.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

SIZES = (1, 2, 16, 17, 130, 1000, 40, 40, 9, 64)
EQUAL_LABELS, EQUAL_SCORES = 6, 7  # the two queries of 40 rows
GRAD_TOL = 1e-5
PARAMS = {"sigmoid": 1.0, "max_position": 20, "label_gain": [0, 1, 3, 7, 15]}


@pytest.fixture(scope="module")
def lambdarank():
    sys.path.append(BENCH)
    from references import lambdarank as module

    yield module
    sys.path.remove(BENCH)


def table(seed: int):
    """Labels 0-4, most of them 0, and scores on a grid of eleven values
    (leaf values after a tree or two: most rows of a query tie)."""
    rng = np.random.default_rng(seed)
    n = sum(SIZES)
    label = np.minimum(rng.geometric(0.55, n) - 1, 4).astype(np.float32)
    scores = (rng.integers(-5, 6, n) * np.float32(0.125)).astype(np.float32)
    bounds = np.concatenate([[0], np.cumsum(SIZES)])
    a, b = bounds[EQUAL_LABELS], bounds[EQUAL_LABELS + 1]
    label[a:b] = 2
    a, b = bounds[EQUAL_SCORES], bounds[EQUAL_SCORES + 1]
    scores[a:b] = np.float32(0.375)
    label[a] = 4  # and it has pairs to weigh
    return label, scores, bounds


def program_objective(label, bounds):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io import Metadata
    from lightgbm_tpu.objectives import create_objective

    cfg = Config.from_dict({"objective": "lambdarank"})
    assert (cfg.sigmoid, cfg.max_position) == (1.0, 20)  # PARAMS' values
    return create_objective(
        cfg, Metadata(label=label, query_boundaries=bounds), len(label))


def program_gradients(label, scores, bounds, chunk=None):
    """``chunk`` in place of each bucket's own (all of a small bucket's
    queries): with one that does not divide a bucket's query count the
    program fills the last chunk with queries of no rows."""
    obj = program_objective(label, bounds)
    if chunk is not None:
        obj._buckets = [(*bucket, chunk) for *bucket, _ in obj._buckets]
    g, h = obj.get_gradients(np.asarray(scores, np.float32))
    return np.asarray(g), np.asarray(h)


def reference_gradients(lambdarank, label, scores, bounds):
    obj = lambdarank.Objective(
        {"y": label, "group": np.diff(bounds)}, PARAMS)
    return obj.gradients(scores)


def worst_gap(got, want, bounds) -> float:
    """The largest |got - want| of any row over its query's largest
    |want|; inf where the reference's exact zeros are not zero."""
    worst = 0.0
    for a, b in zip(bounds[:-1], bounds[1:]):
        top = np.abs(want[a:b]).max()
        if top == 0:
            if np.any(got[a:b] != 0):
                return float("inf")
            continue
        worst = max(worst, float(np.abs(
            got[a:b].astype(np.float64) - want[a:b]).max() / top))
    return worst


@pytest.mark.parametrize("seed", [11, 2_147_483_659])
def test_pair_gradients_agree_with_the_plain_reference(lambdarank, seed):
    label, scores, bounds = table(seed)
    g, h = program_gradients(label, scores, bounds)
    rg, rh = reference_gradients(lambdarank, label, scores, bounds)
    assert worst_gap(g, rg, bounds) < GRAD_TOL
    assert worst_gap(h, rh, bounds) < GRAD_TOL
    # what the cases are there for
    a, b = bounds[EQUAL_LABELS], bounds[EQUAL_LABELS + 1]
    assert not g[a:b].any() and not h[a:b].any() and not rg[a:b].any()
    assert g[0] == 0 and h[0] == 0  # the query of one row
    a, b = bounds[EQUAL_SCORES], bounds[EQUAL_SCORES + 1]
    assert np.abs(rg[a:b]).max() > 0
    for a, b in zip(bounds[:-1], bounds[1:]):  # lambdas cancel in a query
        assert abs(g[a:b].sum(dtype=np.float64)) <= 1e-5 * max(
            np.abs(g[a:b]).sum(dtype=np.float64), 1e-30)
    assert np.all(h >= 0)


def _labels(rng, n):
    return np.minimum(rng.geometric(0.55, n) - 1, 4).astype(np.float32)


def _bounds(sizes):
    return np.concatenate([[0], np.cumsum(sizes)])


def _every_score_equal(rng):
    """Tree 0 of every run: the sort's key is one value, so ``order`` has
    to come out as the slots themselves and the discounts go by row."""
    label, _, bounds = table(3)
    return label, np.zeros(len(label), np.float32), bounds, None


def _tied_blocks_across_labels(rng):
    """Runs of four rows of one score whose labels differ, falling scores
    first and then rising ones: which row of a run ranks first decides
    its discount, and only slot order says."""
    sizes = (37, 64, 128, 5, 129)
    n = sum(sizes)
    run = np.arange(n) // 4
    scores = np.where(run % 2 == 0, -run, run).astype(np.float32) * 0.25
    return _labels(rng, n), scores, _bounds(sizes), None


def _one_row_full_buckets_and_no_grades(rng):
    """Queries of one row at both ends, queries of exactly Q rows (no
    padding slot: rank < cnt is every rank) at four bucket lengths, and a
    20-row query of grade 0 throughout, whose gradients are exact zeros."""
    sizes = (1, 16, 32, 64, 128, 20, 1)
    n = sum(sizes)
    bounds = _bounds(sizes)
    label = _labels(rng, n)
    label[bounds[5]:bounds[6]] = 0
    scores = (rng.integers(-3, 4, n) * np.float32(0.5)).astype(np.float32)
    return label, scores, bounds, None


def _chunks_that_do_not_divide_a_bucket(rng):
    """Seven queries in the 16-wide bucket, five in the 32-wide and one in
    the 64-wide, three to a chunk: the last chunks hold two, one and two
    queries of no rows (best -inf, worst inf, NaN differences), which
    must add exact zeros and no NaN."""
    sizes = (3, 17, 9, 30, 16, 25, 12, 18, 40, 7, 32, 15, 11)
    n = sum(sizes)
    scores = (rng.integers(-5, 6, n) * np.float32(0.125)).astype(np.float32)
    return _labels(rng, n), scores, _bounds(sizes), 3


def _last_query_fills_its_bucket_to_row_n_minus_1(rng):
    """The table's last query has exactly Q rows, so its last slot is row
    n - 1 and nothing of it points at the sentinel behind the scores."""
    sizes = (20, 33, 64)
    n = sum(sizes)
    scores = rng.standard_normal(n).astype(np.float32)
    scores[-7:] = scores[-8]  # and its tail ties
    return _labels(rng, n), scores, _bounds(sizes), None


def _a_single_bucket(rng):
    """Every query in the 16-wide bucket: the tree's one launch is its
    last, and takes its own sums to rows with none before it."""
    sizes = (5, 9, 16, 2)
    n = sum(sizes)
    scores = (rng.integers(-2, 3, n) * np.float32(0.25)).astype(np.float32)
    return _labels(rng, n), scores, _bounds(sizes), None


EDGE_CASES = (_every_score_equal, _tied_blocks_across_labels, _a_single_bucket,
              _one_row_full_buckets_and_no_grades,
              _chunks_that_do_not_divide_a_bucket,
              _last_query_fills_its_bucket_to_row_n_minus_1)


@pytest.mark.parametrize("case", EDGE_CASES, ids=lambda f: f.__name__[1:])
def test_the_sort_formulation_agrees_with_the_reference_on(lambdarank, case):
    label, scores, bounds, chunk = case(np.random.default_rng(33))
    g, h = program_gradients(label, scores, bounds, chunk)
    rg, rh = reference_gradients(lambdarank, label, scores, bounds)
    assert np.abs(rg).max() > 0  # the case has pairs to weigh
    assert not np.isnan(g).any() and not np.isnan(h).any()
    assert worst_gap(g, rg, bounds) < GRAD_TOL
    assert worst_gap(h, rh, bounds) < GRAD_TOL


def gather_rank_order(s, slot, lab, gain):
    """The reordering the program had before PR 33: ``argsort`` and a
    gather of every array by its ``order`` (the gains by their labels)."""
    import jax.numpy as jnp

    gains = jnp.asarray(PARAMS["label_gain"] + [0.0] * 26, jnp.float32)
    order = jnp.argsort(-s, axis=1, stable=True)
    s_r = jnp.take_along_axis(s, order, axis=1)
    l_r = jnp.take_along_axis(lab, order, axis=1)
    return s_r, order, l_r, gains[jnp.clip(l_r, 0, gains.shape[0] - 1)]


def gather_slot_order(order, *payload):
    import jax.numpy as jnp

    unsort = jnp.argsort(order, axis=1, stable=True)
    return tuple(jnp.take_along_axis(a, unsort, axis=1) for a in payload)


def ragged_ties(rng):
    """Ragged queries over three buckets whose scores tie in plenty, both
    zeros among them, with a chunk that leaves queries of no rows."""
    sizes = (3, 17, 9, 30, 16, 25, 12, 1, 18, 40, 7, 32, 64, 15, 11)
    n = sum(sizes)
    scores = (rng.integers(-3, 4, n) * np.float32(0.125)).astype(np.float32)
    scores[rng.random(n) < 0.1] = np.float32(-0.0)
    return _labels(rng, n), scores, _bounds(sizes)


def test_both_sorts_move_their_payload_as_the_gathers_did():
    """Bit for bit, compiled: scores (``-0.0`` and the padding's ``-inf``
    among them), slots, labels and gains out, the sums back; and the two
    things the program no longer reorders at all: the valid mask in rank
    order is ``rank < cnt``, the worst score the minimum over valid slots."""
    import jax

    from lightgbm_tpu import objectives_rank

    rng = np.random.default_rng(7)
    C, Q = 24, 32
    cnt = rng.integers(0, Q + 1, C).astype(np.int32)
    cnt[:3] = 0, 1, Q
    slot = np.broadcast_to(np.arange(Q, dtype=np.int32), (C, Q))
    vld = slot < cnt[:, None]
    s = rng.integers(-2, 3, (C, Q)) * np.float32(0.5)
    s = np.where(rng.random((C, Q)) < 0.2, np.float32(-0.0), s)
    s = np.where(vld, s, -np.inf).astype(np.float32)
    lab = np.where(vld, rng.integers(0, 5, (C, Q)), 0).astype(np.int32)
    gain = np.asarray(PARAMS["label_gain"], np.float32)[lab]
    new = jax.jit(objectives_rank._to_rank_order)(s, slot, lab, gain)
    old = jax.jit(gather_rank_order)(s, slot, lab, gain)
    for a, b in zip(new, old):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(  # bits: 0.0 == -0.0 would pass
            np.asarray(a).view(np.int32), np.asarray(b).view(np.int32))
    s_r, order = np.asarray(old[0]), old[1]
    np.testing.assert_array_equal(
        np.take_along_axis(vld, np.asarray(order), axis=1), vld)
    has = cnt > 0
    np.testing.assert_array_equal(
        s_r[has, cnt[has] - 1], np.where(vld, s, np.inf).min(axis=1)[has])
    sums = rng.standard_normal((2, C, Q)).astype(np.float32)
    back = jax.jit(objectives_rank._to_slot_order)(order, *sums)
    for a, b in zip(back, jax.jit(gather_slot_order)(order, *sums)):
        np.testing.assert_array_equal(
            np.asarray(a).view(np.int32), np.asarray(b).view(np.int32))


def test_gradients_equal_the_gather_formulations(monkeypatch):
    """``==``, every row, on ragged queries with ties: the program as it is
    and with both reorderings put back as gathers.  Op by op
    (``disable_jit``), because equality is the FORMULATION's: compiled
    whole, XLA:CPU rounds the 32-wide bucket's row sums otherwise when
    their inputs come out of a sort and not a gather (a few rows by one
    ulp, inputs bit-equal as the test above holds them; PERF.md, PR 33)."""
    import jax

    from lightgbm_tpu import objectives_rank

    label, scores, bounds = ragged_ties(np.random.default_rng(33))
    with jax.disable_jit():
        g, h = program_gradients(label, scores, bounds, chunk=4)
        monkeypatch.setattr(
            objectives_rank, "_to_rank_order", gather_rank_order)
        monkeypatch.setattr(
            objectives_rank, "_to_slot_order", gather_slot_order)
        og, oh = program_gradients(label, scores, bounds, chunk=4)
    assert np.abs(g).max() > 0
    np.testing.assert_array_equal(g, og)
    np.testing.assert_array_equal(h, oh)


def test_the_equal_score_query_has_no_score_distance_term():
    """With every score of a query equal, ``delta`` is NOT divided by
    0.01 + |s_h - s_l|: the gradients are a hundredth of what the division
    would make them, and the same whatever the common score is."""
    label, scores, bounds = table(5)
    a, b = bounds[EQUAL_SCORES], bounds[EQUAL_SCORES + 1]
    g, _ = program_gradients(label, scores, bounds)
    moved = scores.copy()
    moved[a:b] += np.float32(1.5)
    g2, _ = program_gradients(label, moved, bounds)
    np.testing.assert_array_equal(g[a:b], g2[a:b])
    nudged = scores.copy()
    nudged[b - 1] -= np.float32(1e-3)  # best != worst now
    g3, _ = program_gradients(label, nudged, bounds)
    assert np.abs(g3[a:b]).max() > 50 * np.abs(g[a:b]).max()


def without_distance_term(lambdarank, label, scores, bounds):
    """The reference with the ``/ (0.01 + |s_h - s_l|)`` left out: every
    query treated as the equal-score one is."""
    obj = lambdarank.Objective({"y": label, "group": np.diff(bounds)}, PARAMS)
    g = np.zeros(len(label), np.float32)
    h = np.zeros(len(label), np.float32)
    for q, a, b in obj.queries():
        lab = obj.label[a:b]
        order = np.argsort(-scores[a:b], kind="stable")
        s, lab = scores[a:b][order].astype(np.float64), lab[order]
        d = obj.discount64[:b - a]
        pair = lab[:, None] > lab[None, :]
        diff = s[:, None] - s[None, :]
        delta = ((obj.gain64[lab][:, None] - obj.gain64[lab][None, :])
                 * np.abs(d[:, None] - d[None, :]) * obj.inv_max_dcg[q])
        p = 2.0 / (1.0 + np.exp(2.0 * diff))
        lam = np.where(pair, delta * p, 0.0)
        hes = np.where(pair, 2 * delta * p * (2 - p), 0.0)
        g[a + order] = lam.sum(axis=0) - lam.sum(axis=1)
        h[a + order] = hes.sum(axis=0) + hes.sum(axis=1)
    return g, h


def ties_reversed(lambdarank, label, scores, bounds):
    """The reference on every query's rows in reverse: a stable sort then
    puts tied rows in the opposite order."""
    sizes = np.diff(bounds)
    back = np.concatenate([np.arange(a, b)[::-1]
                           for a, b in zip(bounds[:-1], bounds[1:])])
    g, h = lambdarank.Objective(
        {"y": label[back], "group": sizes}, PARAMS).gradients(scores[back])
    out_g, out_h = np.empty_like(g), np.empty_like(h)
    out_g[back], out_h[back] = g, h
    return out_g, out_h


def scores_in_bfloat16(lambdarank, label, scores, bounds):
    from references import gbdt_replay

    return reference_gradients(
        lambdarank, label, gbdt_replay.bf16(scores), bounds)


@pytest.mark.parametrize("departure", [
    without_distance_term, ties_reversed, scores_in_bfloat16])
def test_a_planted_departure_fails_the_same_comparison(lambdarank, departure):
    label, scores, bounds = table(11)
    if departure is scores_in_bfloat16:
        # off the 2**-3 grid, which bfloat16 holds exactly
        scores = (scores * np.float32(1.003)).astype(np.float32)
    g, h = program_gradients(label, scores, bounds)
    rg, rh = reference_gradients(lambdarank, label, scores, bounds)
    assert worst_gap(g, rg, bounds) < GRAD_TOL  # the sound one still holds
    fg, fh = departure(lambdarank, label, scores, bounds)
    assert worst_gap(g, fg, bounds) > 100 * GRAD_TOL
    assert worst_gap(h, fh, bounds) > 100 * GRAD_TOL


def test_init_counts_what_a_trees_gradients_cost():
    """``rank.*`` counters (docs/observability.md), added once by
    ``init``: held to the table's own arithmetic."""
    from lightgbm_tpu.obs import telemetry

    label, _, bounds = table(11)
    names = ("rank.queries", "rank.buckets", "rank.launches_per_tree",
             "rank.label_pairs", "rank.pair_slots", "rank.row_slots")
    tel = telemetry.get_telemetry()
    before = {k: tel.counter(k) for k in names}
    obj = program_objective(label, bounds)
    added = {k: tel.counter(k) - before[k] for k in names}
    pairs = sum(int(np.sum(label[a:b, None] > label[None, a:b]))
                for a, b in zip(bounds[:-1], bounds[1:]))
    # buckets 16 (1, 2, 16, 9 rows), 32 (17), 64 (40, 40, 64), 256, 1024
    slots = 4 * 16**2 + 32**2 + 3 * 64**2 + 256**2 + 1024**2
    # what a sort operand, the gather in and a scatter back each move
    row_slots = 4 * 16 + 32 + 3 * 64 + 256 + 1024
    assert added == {"rank.queries": len(SIZES), "rank.buckets": 5,
                     "rank.launches_per_tree": len(obj._buckets),
                     "rank.label_pairs": pairs, "rank.pair_slots": slots,
                     "rank.row_slots": row_slots}


def test_every_op_of_the_pair_program_lies_under_a_rank_scope():
    """``lgbm.rank.sort``, ``.pairs`` and ``.scatter`` are in
    ``obs/device_time.SCOPES`` and between them name every op the program
    writes, so that a trace of it is attributed by scope."""
    import re

    import jax.numpy as jnp

    from lightgbm_tpu import objectives_rank
    from lightgbm_tpu.obs import device_time

    scopes = {"lgbm.rank.sort", "lgbm.rank.pairs", "lgbm.rank.scatter"}
    assert scopes <= set(device_time.SCOPE_NAMES)
    nq, Q, n = 5, 16, 70
    text = objectives_rank._lambdarank_grads.lower(  # a tree's last launch
        jnp.zeros(n), jnp.zeros((nq, Q), jnp.int32),
        jnp.full(nq, Q, jnp.int32), jnp.zeros((nq, Q), jnp.int32),
        jnp.ones((nq, Q)), jnp.ones(nq), jnp.ones(Q), jnp.float32(1.0),
        chunk=2, before=((jnp.zeros(32), jnp.zeros(32)),),
        row_slot=jnp.zeros(n, jnp.int32),
    ).compile().as_text()
    # an argument's op_name is its name, and a comparator's or combiner's
    # body (of the sort, a sum, the scatter) carries a bare primitive's:
    # the ops themselves carry a path from ``jit(_lambdarank_grads)`` down
    names = [name for name in re.findall(r'op_name="([^"]+)"', text)
             if "/" in name]
    assert len(names) > 20
    found = {device_time.scope_of(name)[0] for name in names}
    assert found == scopes, found
