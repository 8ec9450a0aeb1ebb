"""The score update's leaf-value lookup (models/tree.py ``leaf_lookup``).

``_post_grow_step`` adds each row's leaf value to its score once a tree.
Up to ``LEAF_SELECT_MAX_LEAVES`` entries the value is read by a mux tree
of selects on the leaf id's bits, one fused pass over the rows; past it
by XLA's element gather.  Either way every row gets exactly
``table[leaf_id]``, bit for bit: compared here as ``uint32`` views.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.models import tree as tree_mod
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.obs import telemetry

PAST = tree_mod.LEAF_SELECT_MAX_LEAVES + 1
N = 4099  # no multiple of a vector's lanes

# NaN payloads of both signs, a signalling NaN, +-inf, -0.0, subnormals
SPECIAL = np.concatenate([
    np.float32([np.inf, -np.inf, -0.0, 0.0, 1e-40, -1e-42, 1.4e-45]),
    np.uint32([0x7FC00000, 0xFFC00000, 0x7FC12345, 0xFFA00001,
               0x7F800001]).view(np.float32)])


def _table(L, rng, special):
    t = rng.standard_normal(L).astype(np.float32)
    if special:
        t[:min(L, len(SPECIAL))] = SPECIAL[:L]
        rng.shuffle(t)
    return t


def _ids(case, L, rng):
    if case == "every id":
        return rng.permutation(np.arange(N) % L).astype(np.int32)
    if case == "one id":
        return np.full(N, L // 2, np.int32)
    return rng.integers(0, L, N).astype(np.int32)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("case", ["random ids", "every id", "one id",
                                  "special values"])
@pytest.mark.parametrize("L", [2, 31, 255, PAST])
def test_the_lookup_is_the_table_read_bit_for_bit(L, case):
    rng = np.random.default_rng(L)
    table = _table(L, rng, case == "special values")
    ids = _ids(case, L, rng)
    got = jax.jit(tree_mod.leaf_lookup)(jnp.asarray(table), jnp.asarray(ids))
    assert got.shape == ids.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(got), _bits(table[ids]))
    # and through the gather formula it replaces
    ref = jax.jit(lambda t, i: t[i])(jnp.asarray(table), jnp.asarray(ids))
    np.testing.assert_array_equal(_bits(got), _bits(ref))


@pytest.mark.parametrize("L,path", [(1, "select"), (255, "select"),
                                    (tree_mod.LEAF_SELECT_MAX_LEAVES, "select"),
                                    (PAST, "gather")])
def test_the_path_follows_the_table_length_alone(L, path):
    """Selects up to the crossover, the gather past it: a compiled
    lookup holds a gather only on the gather's side, and every id
    outside ``[0, L)`` reads an entry of the table, as clamped."""
    assert tree_mod.leaf_lookup_path(L) == path
    table = np.arange(1, L + 1, dtype=np.float32)
    ids = np.array([-5, -1, 0, L - 1, L, L + 7, 1 << 30], np.int32)
    lookup = jax.jit(tree_mod.leaf_lookup)
    got = lookup(jnp.asarray(table), jnp.asarray(ids))
    np.testing.assert_array_equal(
        np.asarray(got), table[np.clip(ids, 0, L - 1)])
    hlo = lookup.lower(jnp.asarray(table), jnp.asarray(ids)).compile(
    ).as_text()
    assert (" gather(" in hlo) == (path == "gather")


def _bounds(F=3):
    return tree_mod.pack_threshold_bounds(
        [[0.5, 1.0] for _ in range(F)], list(range(F)))


@pytest.mark.parametrize("k", [0, 1, 2])
def test_the_update_adds_to_row_k_alone(k):
    """``[3, n]`` scores: row ``k`` gains the shrunk tree's leaf value of
    each row, as float32 adds it; the other rows keep their bits."""
    L = 255
    rng = np.random.default_rng(k)
    lv = _table(L, rng, False)
    ids = rng.integers(0, L, N).astype(np.int32)
    s0 = rng.standard_normal((3, N)).astype(np.float32)
    tree = tree_mod.empty_tree(L)._replace(leaf_value=jnp.asarray(lv))
    bounds_mat, real_feat = _bounds()
    shrunk, scores = gbdt_mod._post_grow_step(
        tree, jnp.asarray(s0), jnp.int32(k), jnp.asarray(ids),
        jnp.float32(0.1), bounds_mat, real_feat)
    table = lv * np.float32(0.1)
    np.testing.assert_array_equal(_bits(shrunk.leaf_value), _bits(table))
    want = s0.copy()
    want[k] = s0[k] + table[ids]
    np.testing.assert_array_equal(_bits(scores), _bits(want))


@functools.partial(jax.jit, donate_argnums=(1,))
def _gather_step(tree, scores, k, leaf_id, rate, bounds_mat, real_feat):
    """The score update by XLA's element gather of the shrunk tree's
    table.  The barrier keeps the shrunk table one array: without it
    XLA:CPU folds the shrinkage into each row's add and contracts
    ``s + v[id] * rate`` into one rounding, a different sum."""
    tree = tree.shrink(rate)
    table = jax.lax.optimization_barrier(tree.leaf_value)
    scores = scores.at[k].add(table[leaf_id])
    return tree_mod.finalize_thresholds_device(
        tree, bounds_mat, real_feat), scores


def _train(grower, monkeypatch, gather):
    if grower == "fused":
        monkeypatch.setattr(
            GBDT, "select_grower", lambda self, row_mask=False: ("fused", ""))
    if gather:
        monkeypatch.setattr(gbdt_mod, "_post_grow_step", _gather_step)
    rng = np.random.default_rng(5)
    X = rng.standard_normal((1500, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "verbose": -1}
    booster = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=5)
    return booster.model_to_string(), _bits(booster._gbdt._scores)


@pytest.mark.parametrize("grower", ["canonical", "fused"])
def test_a_booster_trains_as_through_the_gather(grower, monkeypatch):
    """Five trees: the models and the scores after them equal, bit for
    bit, the same training whose score update reads the shrunk table by
    the gather.  ``fused``: the grower a chip runs, kernels interpreted."""
    model, scores = _train(grower, monkeypatch, gather=False)
    ref_model, ref_scores = _train(grower, monkeypatch, gather=True)
    assert model.count("Tree=") == 5
    assert model == ref_model
    np.testing.assert_array_equal(scores, ref_scores)


@pytest.mark.parametrize("num_leaves,path", [(15, "select"), (PAST, "gather")])
def test_the_booster_says_the_lookup(num_leaves, path):
    """``score.leaf_lookup.<path>`` gains the table's length once a
    booster, and the booster's log line names the path."""
    tel = telemetry.get_telemetry()
    before = {p: tel.counter(f"score.leaf_lookup.{p}")
              for p in ("select", "gather")}
    rng = np.random.default_rng(0)
    X = rng.standard_normal((300, 3)).astype(np.float32)
    params = {"objective": "regression", "num_leaves": num_leaves,
              "verbose": -1}
    lgb.Booster(params, lgb.Dataset(X, label=X[:, 0], params=params))
    after = {p: tel.counter(f"score.leaf_lookup.{p}") - before[p]
             for p in before}
    assert after == {p: num_leaves if p == path else 0 for p in before}
    said = f"score update by {path} over {num_leaves} leaves"
    assert any(said in m for m in gbdt_mod._LOGGED_PATHS), \
        gbdt_mod._LOGGED_PATHS


def test_the_update_partitions_by_rows_without_a_collective():
    """Under a row mesh (``tree_learner=data``: scores and leaf ids
    sharded by rows, the tree on every device) the compiled update holds
    no collective and gives the single-device scores."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs four devices")
    mesh = Mesh(np.array(devices), ("rows",))
    L, n = 255, 4096
    rng = np.random.default_rng(3)
    lv = _table(L, rng, True)
    ids = rng.integers(0, L, n).astype(np.int32)
    s0 = rng.standard_normal((1, n)).astype(np.float32)
    tree = tree_mod.empty_tree(L)._replace(leaf_value=jnp.asarray(lv))
    bounds_mat, real_feat = _bounds()
    args = (jnp.int32(0), jax.device_put(ids, NamedSharding(mesh, P("rows"))),
            jnp.float32(0.1), bounds_mat, real_feat)
    scores = jax.device_put(s0, NamedSharding(mesh, P(None, "rows")))
    hlo = gbdt_mod._post_grow_step.lower(tree, scores, *args).compile(
    ).as_text()
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in hlo, op
    _, got = gbdt_mod._post_grow_step(tree, scores, *args)
    assert got.sharding.spec == P(None, "rows")
    _, want = gbdt_mod._post_grow_step(
        tree, jnp.asarray(s0), jnp.int32(0), jnp.asarray(ids),
        jnp.float32(0.1), bounds_mat, real_feat)
    np.testing.assert_array_equal(_bits(got), _bits(want))
