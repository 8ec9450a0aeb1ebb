"""Data-parallel training on the fused grower (learners/fused.py under
``jax.shard_map``, parallel/data_parallel.py
``make_fused_data_parallel_grower``): rows dealt to chips as contiguous
shares, one ``psum`` of a ``[Fp, 4, Bp]`` histogram block a split.

CPU, four of the conftest's eight host devices, kernels interpreted, on a
seeded table shaped like the Airline cell's (13 columns, six of them
categorical, two of those with more categories than bins), small enough
to interpret.  The sharded trees are held to the one-device fused
grower's and to a plain one-vs-rest routing of the raw matrix.
"""

import functools
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.learners import fused, serial
from lightgbm_tpu.learners.serial import TreeLearnerParams
from lightgbm_tpu.ops import record
from lightgbm_tpu.ops.pallas_histogram import make_single_hist_fn_raw
from lightgbm_tpu.parallel import data_mesh
from lightgbm_tpu.parallel.data_parallel import make_fused_data_parallel_grower

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from references import onevsrest_replay  # noqa: E402  (benchmarks/)

SHARDS, SHARD_ROWS, LEAVES = 4, 2048, 31
# (values, categorical) of the 13 columns, in the Airline table's order
COLUMNS = [(22, False), (12, True), (31, True), (7, True), (0, False),
           (0, False), (29, True), (300, False), (0, False), (40, True),
           (300, True), (0, False), (2, False)]
CATEGORICAL = [j for j, (_, cat) in enumerate(COLUMNS) if cat]
MAX_BIN = 31  # fewer bins than two columns' categories: the others' bin


def make_table(seed: int, n: int = SHARDS * SHARD_ROWS):
    rng = np.random.default_rng(seed)
    X = np.empty((n, len(COLUMNS)))
    for j, (values, _) in enumerate(COLUMNS):
        if values:  # heavy-tailed identifiers, codes in a fixed shuffle
            p = 1.0 / (np.arange(values) + 2.0) ** 1.2
            X[:, j] = rng.permutation(values)[
                rng.choice(values, n, p=p / p.sum())]
        else:
            X[:, j] = np.round(rng.gamma(2.0, 300.0, n))
    score = (0.02 * X[:, 0] + 0.3 * (X[:, 1] % 3) - 0.002 * X[:, 4]
             + 0.5 * (X[:, 9] % 5 == 1) + rng.standard_normal(n))
    y = (score > np.median(score)).astype(np.float32)
    return X, y


@functools.lru_cache(maxsize=None)
def case(seed: int, n: int = SHARDS * SHARD_ROWS):
    """The binned table and one tree's operands (gradients of a binary
    log-loss at seeded scores), as numpy."""
    X, y = make_table(seed, n)
    cfg = Config(objective="binary", num_leaves=LEAVES, max_bin=MAX_BIN,
                 min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)
    ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg,
                                   categorical_features=CATEGORICAL)
    rng = np.random.default_rng(seed + 1)
    p = 1.0 / (1.0 + np.exp(-0.3 * rng.standard_normal(len(y))))
    grad = (p - y).astype(np.float32)
    hess = (p * (1.0 - p)).astype(np.float32)
    return X, ds, cfg, grad, hess


def operands(ds, grad, hess):
    F = ds.num_features
    return (jnp.asarray(np.ascontiguousarray(ds.dense_bins().T)),
            jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(len(grad), jnp.float32), jnp.ones(F, bool),
            jnp.asarray(ds.num_bins_per_feature),
            jnp.asarray(ds.is_categorical))


def grow_both(seed: int, n: int = SHARDS * SHARD_ROWS):
    """(one-device tree, four-shard tree, leaf ids of each) on the seed."""
    X, ds, cfg, grad, hess = case(seed, n)
    args = operands(ds, grad, hess) + (TreeLearnerParams.from_config(cfg),)
    nb = max(int(ds.max_num_bin), 2)
    one = fused.grow_tree(*args, num_bins=nb, max_leaves=LEAVES)
    mesh = data_mesh(num_devices=SHARDS)
    grow = make_fused_data_parallel_grower(mesh, num_bins=nb,
                                           max_leaves=LEAVES)
    many = grow(*args)
    return jax.tree.map(np.asarray, (one, many))


SEEDS = (2, 40, 401)
# rows that do not divide the four chips: padded with bag mask 0
UNEVEN = SHARDS * SHARD_ROWS - 3


@pytest.fixture(scope="module", params=SEEDS + ((SEEDS[0], UNEVEN),),
                ids=[str(s) for s in SEEDS] + ["uneven"])
def grown(request):
    seed, n = (request.param if isinstance(request.param, tuple)
               else (request.param, SHARDS * SHARD_ROWS))
    return (seed, n), grow_both(seed, n)


def _used(tree):
    nl = int(tree.num_leaves)
    return nl, slice(0, nl - 1), slice(0, nl)


def test_the_sharded_trees_are_the_one_device_trees(grown):
    """Split features, thresholds and kinds exact; counts exact (the
    sharded tree's are int32, summed over the chips); leaf values and
    gains to float32 reduction order: the chips' histograms are summed
    in another order than one chip sums its rows, so a bin may differ in
    its last bit; a leaf's value is a ratio of two such sums, and a gain
    the difference of three ``G^2 / H`` terms of its parent's size, so a
    small gain keeps the parent term's rounding (3e-5 of 8.1 read)."""
    _, ((one, lid1), (many, lidD)) = grown
    nl, nodes, leaves = _used(one)
    assert int(many.num_leaves) == nl > LEAVES // 2
    for f in ("split_feature", "threshold_bin", "decision_type",
              "left_child", "right_child"):
        np.testing.assert_array_equal(getattr(many, f)[nodes],
                                      getattr(one, f)[nodes], err_msg=f)
    assert many.internal_count.dtype == np.int32
    assert many.leaf_count.dtype == np.int32
    np.testing.assert_array_equal(many.internal_count[nodes],
                                  one.internal_count[nodes])
    np.testing.assert_array_equal(many.leaf_count[leaves],
                                  one.leaf_count[leaves])
    np.testing.assert_allclose(many.leaf_value[leaves],
                               one.leaf_value[leaves], rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(many.split_gain[nodes],
                               one.split_gain[nodes], rtol=1e-4)
    np.testing.assert_array_equal(lidD, lid1)


def test_the_sharded_trees_follow_the_plain_one_vs_rest_routing(grown):
    """The raw matrix routed through the sharded tree by the plain
    reference (benchmarks/references/onevsrest_replay.py: a categorical
    node sends a row left iff its category IS the node's, a numerical one
    iff ``x <= bound``) puts in every node and leaf the rows the tree
    counted, and each leaf's float64 ``-G / H`` is the tree's value to
    float32 accuracy (the grower sums in float32)."""
    (seed, n), (_, (many, leaf_id)) = grown
    X, ds, _, grad, hess = case(seed, n)
    nl, nodes, leaves = _used(many)
    real = ds.real_feature_indices
    feat = many.split_feature[nodes]
    thr = [(m.bin_to_category if cat else m.bin_upper_bound)[t]
           for m, cat, t in zip([ds.bin_mappers[f] for f in feat],
                                ds.is_categorical[feat],
                                many.threshold_bin[nodes])]
    tree = {"num_leaves": nl, "split_feature_real": real[feat],
            "threshold_real": np.asarray(thr, np.float64),
            "decision_type": many.decision_type[nodes],
            "left_child": many.left_child[nodes],
            "right_child": many.right_child[nodes]}
    node_rows, leaf_of = onevsrest_replay.route(X, tree)
    np.testing.assert_array_equal(leaf_of, leaf_id)
    np.testing.assert_array_equal(
        np.bincount(leaf_of, minlength=nl), many.leaf_count[leaves])
    np.testing.assert_array_equal(
        [len(node_rows[i]) for i in range(nl - 1)],
        many.internal_count[nodes])
    G = np.bincount(leaf_of, grad.astype(np.float64), nl)
    H = np.bincount(leaf_of, hess.astype(np.float64), nl)
    np.testing.assert_allclose(many.leaf_value[leaves], -G / H,
                               rtol=1e-4, atol=1e-6)


def _shards(a, axis=-1):
    return np.split(np.asarray(a), SHARDS, axis=axis)


def _child_hist(bins_T, grad, hess, split, counts):
    """The smaller child's histogram of the root split ``split`` over the
    rows given, as the data-parallel grower's first launch sums it
    (ops/record.py split_hist_counted, interpreted), the child chosen by
    the global ``counts`` (left, right)."""
    from lightgbm_tpu.ops.pallas_search import _pack_scal

    F, n = bins_T.shape
    k = record.bins_per_word(bins_T.dtype)
    cap = record.round_up(n, record.TILE)
    rec = record.build_record(jnp.asarray(bins_T), jnp.asarray(grad),
                              jnp.asarray(hess), jnp.ones(n, jnp.float32),
                              2 * cap)
    f, thr, is_cat = split
    scal_f = _pack_scal(1.0, 0.0, 0.0, counts[0], 0.0, 0.0, counts[1],
                        20.0, 1e-3, 0.0, 0.0, 0.0)
    h, *_ = record.split_hist_counted(
        rec, 0, n, True, f, thr, is_cat, scal_f, F=F, cap=cap, k=k,
        Fp=record.round_up(F, 8), Bp=256 if MAX_BIN > 128 else 128,
        interpret=True)
    return np.asarray(h)


@pytest.mark.parametrize("which", ["root", "child"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_shards_histograms_sum_to_the_tables(seed, which):
    """What each chip sums of its own rows, summed over the chips, is the
    histogram of the whole table: the root's (the root kernel on each
    shard) and the root split's smaller child's (the split step's first
    launch on each shard's record).  Counts exact, sums of gradients and
    hessians to float32 accumulation order."""
    _, ds, _, grad, hess = case(seed)
    bins_T = np.ascontiguousarray(ds.dense_bins().T)
    nb = max(int(ds.max_num_bin), 2)
    if which == "root":
        hist = make_single_hist_fn_raw(nb)

        def of(b, g, h):
            return np.asarray(hist(jnp.asarray(b), jnp.asarray(g),
                                   jnp.asarray(h),
                                   jnp.ones(len(g), jnp.float32)))
    else:
        (one, _), _ = grow_both(seed)
        split = (int(one.split_feature[0]), int(one.threshold_bin[0]),
                 int(one.decision_type[0]))
        left = int(one.left_child[0])
        lc = one.internal_count[left] if left >= 0 else one.leaf_count[~left]
        counts = (float(lc), float(len(grad) - lc))

        def of(b, g, h):
            return _child_hist(b, g, h, split, counts)
    whole = of(bins_T, grad, hess)
    parts = [of(b, g, h) for b, g, h in zip(
        _shards(bins_T), _shards(grad), _shards(hess))]
    summed = np.sum(parts, axis=0)
    np.testing.assert_array_equal(summed[:, 2], whole[:, 2])  # counts
    assert whole[0, 2].sum() > len(grad) // 8  # a child of some size
    np.testing.assert_allclose(summed[:, :2], whole[:, :2],
                               rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("shard_counts", [
    (2 ** 24 - 1, 2 ** 24 - 1, 1, 2),  # 2**25 + 1: no float32 holds it
    (2 ** 24, 2 ** 24, 2 ** 24, 2 ** 24 - 3),
    (0, 7, 0, 4096),
])
def test_the_exchange_sums_counts_exactly_past_float32(shard_counts):
    """learners/fused.py ``exchange``: the histogram block is summed over
    the chips in one ``psum``, and the chips' row counts (each one
    exact, at most 2**24) come back summed exactly in int32, where the
    float32 count channel rounds them; channel 3 is left as zero as it
    came."""
    from jax.sharding import PartitionSpec as P

    mesh = data_mesh(num_devices=SHARDS)
    blocks = np.zeros((SHARDS, 16, 4, 128), np.float32)
    blocks[:, 0, 2, 5] = shard_counts  # feature 0's count channel
    blocks[:, 3, 0, :] = 0.25  # a gradient sum, summed as it is

    def body(h):
        out, total = fused.exchange(h[0], "row")
        return out[None], total[None]

    out, total = jax.shard_map(
        body, mesh=mesh, in_specs=P("row"), out_specs=(P("row"), P("row")),
        check_vma=False)(jnp.asarray(blocks))
    out, total = np.asarray(out), np.asarray(total)
    assert (total == sum(shard_counts)).all(), (total, sum(shard_counts))
    assert total.dtype == np.int32
    assert not out[:, :, 3].any()
    np.testing.assert_array_equal(out[:, 3, 0], 0.25 * SHARDS)


@pytest.mark.parametrize("rows,shards,fits", [
    (4 * 2 ** 24, 4, True),  # the Airline cell: 2**24 a chip
    (2 ** 24 + 1, 1, False),
    (2 ** 24, 1, True),
    (4 * 2 ** 24 + 1, 4, False),  # one chip holds 2**24 + 1
])
def test_the_count_envelope_is_a_shards(rows, shards, fits):
    """learners/serial.py check_count_envelope bounds the rows ONE
    histogram sums: a shard's."""
    if fits:
        serial.check_count_envelope(rows, "float32", shards)
        return
    with pytest.raises(ValueError, match="float32 integer-exact") as e:
        serial.check_count_envelope(rows, "float32", shards)
    assert str(rows) in str(e.value)
    assert ("rows a shard" in str(e.value)) == (shards > 1)


def _collectives(jaxpr, in_loop=False):
    """``(collective, inside a loop)`` of every equation of ``jaxpr`` and
    of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("psum", "pmax", "all_gather", "psum_scatter",
                    "reduce_scatter", "all_to_all", "ppermute"):
            yield name, in_loop
        inner = in_loop or name in ("while", "scan")  # a fori_loop
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _collectives(sub, inner)


def test_one_device_traces_no_collective_and_shards_one_a_split():
    """With no mesh axis the grower traces no collective at all (the
    one-chip program is the parent's: tests/test_chip_compile.py holds its
    compiled form to a digest); over four shards the split loop holds ONE
    (the smaller child's histogram), and the root's exchange and exact
    totals stand before it."""
    _, ds, cfg, grad, hess = case(SEEDS[0])
    args = operands(ds, grad, hess) + (TreeLearnerParams.from_config(cfg),)
    nb = max(int(ds.max_num_bin), 2)
    one = jax.make_jaxpr(functools.partial(
        fused.grow_tree, num_bins=nb, max_leaves=LEAVES))(*args)
    assert list(_collectives(one.jaxpr)) == []
    grow = make_fused_data_parallel_grower(
        data_mesh(num_devices=SHARDS), num_bins=nb, max_leaves=LEAVES)
    many = list(_collectives(jax.make_jaxpr(grow)(*args).jaxpr))
    assert [c for c in many if c[1]] == [("psum", True)]
    # the root histogram, the six digit sums and the grid of its totals
    assert sorted(c for c, loop in many if not loop) == ["pmax"] + [
        "psum"] * 7


@pytest.fixture(scope="module")
def boosters():
    """Three iterations of ``lgb.train`` through a Booster whose selector
    is told it stands on a chip (the fused grower; its kernels interpreted
    here), on one device and with ``tree_learner=data`` over four: the
    booster's own placement of rows, scores and counts end to end."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import gbdt as gbdt_mod

    X, y = make_table(SEEDS[0])
    params = {"objective": "binary", "num_leaves": LEAVES,
              "max_bin": MAX_BIN, "min_data_in_leaf": 20,
              "categorical_column": ",".join(map(str, CATEGORICAL)),
              "verbose": -1}
    picked = gbdt_mod.GBDT.select_grower
    out = {}
    try:
        gbdt_mod.GBDT.select_grower = lambda self, row_mask=False: (
            "fused", "")
        for learner, extra in (("serial", {}),
                               ("data", {"num_machines": SHARDS})):
            out[learner] = lgb.train(
                {**params, "tree_learner": learner, **extra},
                lgb.Dataset(X, label=y), num_boost_round=3)
    finally:
        gbdt_mod.GBDT.select_grower = picked
    return X, out


def test_a_booster_trains_data_parallel_on_the_fused_grower(boosters):
    """The data-parallel booster keeps its rows on four devices, grows the
    one-device booster's trees, scores alike, and writes its int32 counts
    into the model text as the one-device booster writes its float32
    ones."""
    X, b = boosters
    one, many = b["serial"]._gbdt, b["data"]._gbdt
    assert many._learner_devices == SHARDS and one._learner_devices == 1
    assert len(many._bins_T.sharding.device_set) == SHARDS
    assert len(many.models) == len(one.models) == 3
    for t1, tD in zip(one.models, many.models):
        nl = int(t1.num_leaves)
        assert int(tD.num_leaves) == nl
        for f in ("split_feature", "threshold_bin", "decision_type",
                  "internal_count"):
            np.testing.assert_array_equal(
                np.asarray(getattr(tD, f))[:nl - 1],
                np.asarray(getattr(t1, f))[:nl - 1], err_msg=f)
    np.testing.assert_allclose(b["data"].predict(X), b["serial"].predict(X),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(many._scores),
                               np.asarray(one._scores), rtol=1e-5, atol=1e-6)
    text1, textD = (b[k].model_to_string() for k in ("serial", "data"))
    counts = re.compile(r"^(?:leaf|internal)_count=.*$", re.M)
    assert counts.findall(textD) == counts.findall(text1)


def test_the_sharded_grower_walks_feature_chunks():
    """A table wider than one ``[Fc, 4, Bp]`` block (chunks made short,
    as tests/test_feature_chunks.py makes them, so that 40 features are
    two chunks and the second short): the exchanged block is every
    chunk's, the search launch walks them, and the sharded trees are the
    one-device trees."""
    from lightgbm_tpu.ops import pallas_histogram as PH

    F, B, n, leaves = 40, 16, SHARDS * 1024, 15
    rng = np.random.RandomState(7)
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    grad = (rng.randn(n) + 0.3 * (bins[3] > 7)).astype(np.float32)
    hess = (0.5 + 0.1 * rng.rand(n)).astype(np.float32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, jnp.float32), jnp.ones(F, bool),
            jnp.full(F, B, jnp.int32), jnp.zeros(F, bool),
            TreeLearnerParams(*map(jnp.float32, (20, 1e-3, 0, 0, 0)),
                              jnp.int32(-1)))
    whole = PH.CHUNK_BLOCK_BYTES
    PH.CHUNK_BLOCK_BYTES = 32 * 16 * 128  # chunks of 32 features
    jax.clear_caches()
    try:
        assert PH.feature_chunk(40, 128) == (32, 2)
        one = fused.grow_tree(*args, num_bins=B, max_leaves=leaves)
        many = make_fused_data_parallel_grower(
            data_mesh(num_devices=SHARDS), num_bins=B,
            max_leaves=leaves)(*args)
        one, many = jax.tree.map(np.asarray, (one, many))
    finally:
        PH.CHUNK_BLOCK_BYTES = whole
        jax.clear_caches()
    (t1, lid1), (tD, lidD) = one, many
    nl, nodes, leaves_ = _used(t1)
    assert int(tD.num_leaves) == nl == leaves
    assert (t1.split_feature[nodes] >= 32).any()  # a split in chunk two
    for f in ("split_feature", "threshold_bin", "left_child",
              "right_child", "internal_count"):
        np.testing.assert_array_equal(getattr(tD, f)[nodes],
                                      getattr(t1, f)[nodes], err_msg=f)
    np.testing.assert_array_equal(tD.leaf_count[leaves_],
                                  t1.leaf_count[leaves_])
    np.testing.assert_allclose(tD.leaf_value[leaves_], t1.leaf_value[leaves_],
                               rtol=2e-5, atol=1e-7)
    np.testing.assert_array_equal(lidD, lid1)
