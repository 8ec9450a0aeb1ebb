"""The one place that chooses the leaf-wise grower
(``GBDT.select_grower``, models/gbdt.py): one case a row of its table.

The fused grower (learners/fused.py) is what a TPU chip runs for serial
float32 leaf-wise training; everything else gets the canonical grower
(learners/serial.py), and the booster says why.  The TPU rows run under
``device.assume_platform("tpu")``: the selection reads what it can
observe, and no kernel is compiled here.
"""

import numpy as np
import pytest

from lightgbm_tpu import device
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.learners import fused, serial
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import record as R


def _booster(platform, vmem=None, monkeypatch=None, F=5, n=600, csr=False,
             **params):
    """A booster on an ``n`` x ``F`` table; ``csr``: nine cells in ten
    zero, ingested as CSR and kept sparse (io/sparse.py)."""
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    if csr:
        X[rng.rand(n, F) < 0.9] = 0.0
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=7, min_data_in_leaf=5,
                 **params)
    if vmem is not None:
        monkeypatch.setattr(device, "vmem_bytes", lambda: vmem)
    with device.assume_platform(platform):
        if csr:
            rows, cols = np.nonzero(X)
            indptr = np.concatenate(
                [[0], np.cumsum(np.bincount(rows, minlength=n))])
            ds = BinnedDataset.from_csr(indptr, cols, X[rows, cols], F,
                                        Metadata(label=y), cfg)
            assert ds.is_sparse
        else:
            ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
        return gbdt_mod.GBDT(cfg, ds, create_objective(cfg, ds.metadata, n))


# (id, platform, config, which, a piece of the reason, the grow callable)
_TABLE = [
    ("tpu-serial-f32", "tpu", {}, "fused", "", fused.grow_tree),
    ("cpu", "cpu", {}, "canonical", "platform=cpu", serial.grow_tree),
    ("float64", "tpu", {"hist_dtype": "float64"}, "canonical",
     "hist_dtype=float64", serial.grow_tree),
    ("pool", "tpu", {"histogram_pool_size": 0.01}, "canonical",
     "histogram_pool_size=0.01", serial.grow_tree),
    ("hist_impl=segment", "tpu", {"hist_impl": "segment"}, "canonical",
     "hist_impl=segment", serial.grow_tree),
    # sparse ingest reaches the growers through dense bins
    ("csr-ingested", "tpu", {"csr": True}, "fused", "", fused.grow_tree),
    ("tree_learner=data", "tpu", {"tree_learner": "data"}, "fused", "",
     None),
    ("tree_learner=feature", "tpu", {"tree_learner": "feature"},
     "canonical", "tree_learner=feature over 8 devices", None),
    ("tree_learner=voting", "tpu", {"tree_learner": "voting"}, "canonical",
     "tree_learner=voting over 8 devices", None),
    ("vmem-too-small", "tpu", {}, "canonical",
     "split step VMEM 3 of 1 MiB", serial.grow_tree),
]

# (features, chunks of 256 features, record words, MiB the step keeps)
# (one parent tile a split step's grid step past 64 words)
_WIDE = [(264, 2, 72, 22), (1000, 4, 256, 34), (2000, 8, 512, 54)]


@pytest.mark.parametrize(
    "platform,params,which,why,grow", [row[1:] for row in _TABLE],
    ids=[row[0] for row in _TABLE])
def test_select_grower(platform, params, which, why, grow, monkeypatch):
    vmem = 2 << 20 if "of 1 MiB" in why else None
    g = _booster(platform, vmem, monkeypatch, **params)
    assert g._grower[0] == which and why in g._grower[1], g._grower
    with device.assume_platform(platform):
        assert g.select_grower() == g._grower  # asked again, same answer
    if grow is not None:
        assert g._grow.func is grow
        assert "hist_fn_raw" not in g._grow.keywords
    else:  # another learner's callable holds the canonical grower
        assert getattr(g._grow, "func", None) not in (
            fused.grow_tree, serial.grow_tree)
    if which == "canonical" and "pool" in why:
        assert g._grow.keywords["hist_pool"] >= 2
    # the booster said which grower and why, once, at INFO
    said = [m for m in gbdt_mod._LOGGED_PATHS if f"grower={which}" in m]
    assert any(why in m for m in said), gbdt_mod._LOGGED_PATHS


@pytest.mark.parametrize("devices", [2, 4, 8])
def test_data_parallel_on_one_process_takes_the_fused_grower(devices):
    """``tree_learner=data`` over the chips of one process: the fused
    grower with the rows dealt to ``devices`` shards
    (parallel/data_parallel.py make_fused_data_parallel_grower), and the
    booster's log line and ``dp.*`` counters say the shards, the rows of
    the fullest and the one block a split sums over the chips."""
    from lightgbm_tpu.obs import telemetry

    tel = telemetry.get_telemetry()
    names = ("dp.shards", "dp.rows_per_shard", "dp.exchange_bytes_per_split",
             "dp.collectives_per_split")
    before = {name: tel.counter(name) for name in names}
    n = 600
    g = _booster("tpu", tree_learner="data", num_machines=devices, n=n)
    assert g._grower == ("fused", "")
    assert g._learner_devices == devices and g._count_shards() == devices
    assert g._grow.__name__ == "grow_tree"  # the program jit_grow_tree
    rows, block = -(-n // devices), 8 * 4 * 256 * 4  # [Fp, 4, Bp] f32
    assert {name: tel.counter(name) - before[name] for name in names} == {
        "dp.shards": devices, "dp.rows_per_shard": rows,
        "dp.exchange_bytes_per_split": block, "dp.collectives_per_split": 1}
    assert any(f"devices={devices} of 8 tree_learner=data" in m
               and "grower=fused" in m
               and f"rows over {devices} devices ({rows} a shard), one "
               f"all-reduce of {block} B a split" in m
               for m in gbdt_mod._LOGGED_PATHS), gbdt_mod._LOGGED_PATHS
    # cv's row mask is the one-device learners' alone
    with device.assume_platform("tpu"), pytest.raises(ValueError):
        g.set_base_row_mask(np.arange(n) % 3 > 0)


def test_more_than_one_process_keeps_the_canonical_grower(monkeypatch):
    """Across processes the rows are each process's own ingest and the
    fused grower has no path: the selector says so."""
    g = _booster("tpu", tree_learner="data")
    monkeypatch.setattr(gbdt_mod.jax, "process_count", lambda: 2)
    with device.assume_platform("tpu"):
        which, why = g.select_grower()
    assert which == "canonical" and "of 2 processes" in why, why


def test_a_row_mask_selects_the_canonical_grower():
    """cv's bin-once path: the booster the chip would give the fused
    grower re-selects when a base row mask arrives."""
    g = _booster("tpu")
    assert g._grower[0] == "fused" and g._grow.func is fused.grow_tree
    with device.assume_platform("tpu"):
        g.set_base_row_mask(np.arange(600) % 3 > 0)
    assert g._grower == ("canonical", "base row mask")
    assert g._grow.func is serial.grow_tree
    assert g._grow.keywords["choice_by_mask_counts"] is True


@pytest.mark.parametrize("F,chunks,words,mib", _WIDE)
def test_wide_tables_get_the_fused_grower_and_the_bound_is_named(
        F, chunks, words, mib, monkeypatch):
    """Past one ``[Fc, 4, Bp]`` block the kernels walk feature chunks
    (learners/fused.py ``chunking``): the selector offers the fused
    grower at every width the split step's VMEM takes, says the
    chunking in the booster's log line and in the ``grow.*`` counters,
    and on a chip with less VMEM names the bound instead of leaving the
    table to Mosaic."""
    from lightgbm_tpu.obs import telemetry

    said = (f"{chunks} chunks of 256 features, record of {words} words, "
            f"split step VMEM {mib} of ")
    counters = {"grow.feature_chunks": chunks, "grow.chunk_features": 256,
                "grow.hist_block_bytes": 1 << 20, "grow.record_words": words,
                "grow.onehot_planes": 2, "grow.place_steps_per_tile": 1,
                "grow.place_launches_per_split": 1,
                "grow.split_tiles_per_step": R.split_tiles(words)}
    tel = telemetry.get_telemetry()
    before = {name: tel.counter(name) for name in counters}
    g = _booster("tpu", F=F)
    assert g._grower == ("fused", "") and g._grow.func is fused.grow_tree
    assert any(said + "96 MiB, one-hot of 2 x 128 bins, placement 1 step "
               "a tile in 1 launch a split, split step 1 tile a grid step"
               in m
               and "grower=fused" in m
               for m in gbdt_mod._LOGGED_PATHS), gbdt_mod._LOGGED_PATHS
    assert {name: tel.counter(name) - before[name]
            for name in counters} == counters  # once a booster
    small = (mib - 1) * 4 // 3 << 20  # the gate admits three quarters
    g = _booster("tpu", small, monkeypatch, F=F)
    assert g._grower[0] == "canonical", g._grower
    assert said in g._grower[1] and "past the chip's VMEM" in g._grower[1]


# (features, the record's words, parent tiles a grid step of the split
# step): the narrow cells' heights take four, a taller record one
@pytest.mark.parametrize("F,words,tiles", [
    (13, 16, 4), (220, 64, 4), (300, 80, 1), (600, 160, 1)])
def test_the_booster_says_the_split_tiles_a_step(F, words, tiles):
    """``grow.split_tiles_per_step`` is ``split_tiles`` of the record's
    height alone, counted once a booster and named in the log line."""
    from lightgbm_tpu.obs import telemetry

    tel = telemetry.get_telemetry()
    before = tel.counter("grow.split_tiles_per_step")
    g = _booster("tpu", F=F)
    assert g._grower == ("fused", "")
    assert g._chunking.record_words == words
    assert tel.counter("grow.split_tiles_per_step") - before == tiles
    said = f"split step {tiles} tile{'s' if tiles > 1 else ''} a grid step"
    assert any(said in m and "grower=fused" in m
               for m in gbdt_mod._LOGGED_PATHS), gbdt_mod._LOGGED_PATHS


# (max_bin, rows, the planes the kernels' one-hot body splits Bp into)
@pytest.mark.parametrize("max_bin,n,planes", [
    (63, 600, 1), (127, 600, 1), (255, 3000, 2), (511, 6000, 4)])
def test_the_booster_says_the_onehot_planes(max_bin, n, planes):
    """``grow.onehot_planes`` is ``Bp // 128`` of the table's widest
    column (ops/pallas_histogram.py bin_sums: one plane is the unsplit
    body), in the fused grower's counters and in its log line."""
    from lightgbm_tpu.obs import telemetry

    tel = telemetry.get_telemetry()
    before = tel.counter("grow.onehot_planes")
    g = _booster("tpu", F=3, n=n, max_bin=max_bin)
    assert g._grower == ("fused", "")
    assert -(-g._num_bins // 128) == planes, g._num_bins
    assert tel.counter("grow.onehot_planes") - before == planes
    assert any(f"one-hot of {planes} x 128 bins" in m and "grower=fused" in m
               for m in gbdt_mod._LOGGED_PATHS), gbdt_mod._LOGGED_PATHS
