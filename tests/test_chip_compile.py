"""Compile the CHIP program with no chip.

Tier-1 runs on the CPU, where the library picks segment-sum histograms
and the jax.numpy search; on a TPU it picks the raw-layout Pallas path
— a different program, which no CPU test executes.  libtpu can still
compile it: select the TPU paths (device.assume_platform), lower the
serial grower for a described v5e topology, and let Mosaic accept or
refuse every kernel.  A refusal then fails here, not in a chip call.
This says nothing about what the kernels compute (chip_smoke.py does).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import device
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"libtpu gives no v5e topology here: {e}")


@pytest.fixture(scope="module")
def grower(topo):
    """``(lowered, compiled)`` of the serial grower for one v5e chip."""
    n, F = 100_000, 28
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=63, max_bin=255,
                 min_data_in_leaf=100)
    with device.assume_platform("tpu"):
        ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
        gbdt = GBDT(cfg, ds, create_objective(cfg, ds.metadata, n))
        grow = gbdt._grow  # functools.partial over the jitted grow_tree
        assert grow.keywords["hist_fn_raw"] is not None  # the chip path
        on_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=on_chip),
            (gbdt._bins_T, jnp.zeros(n, jnp.float32),
             jnp.zeros(n, jnp.float32), gbdt._bag_mask, jnp.ones(F, bool),
             gbdt._nbpf, gbdt._is_cat, gbdt._learner_params))
        lowered = grow.func.lower(*args, **grow.keywords)
    return lowered, lowered.compile()  # Mosaic refuses a kernel here, or not


def test_serial_grower_compiles_for_v5e(grower):
    lowered, compiled = grower
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    assert mosaic_calls >= 10, mosaic_calls  # 19 at this shape
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_scopes_and_kernel_names_in_the_compiled_grower(grower):
    """What obs/device_time reads back, and the harness's layer files
    match, as the chip's compiler leaves it: every scope the grower
    writes is in some ``op_name``, none is outside the table, and every
    Mosaic call's instruction is named after its kernel and capacity
    (an instruction takes the name of its innermost scope: read off
    compiled programs, not documented, hence this test)."""
    from lightgbm_tpu.obs import device_time as dt

    text = grower[1].as_text()
    found = {dt.scope_of(name) for name in
             set(re.findall(r'op_name="([^"]*)"', text))} - {None}
    scopes = {scope for scope, _ in found}
    assert scopes <= set(dt.SCOPE_NAMES), scopes - set(dt.SCOPE_NAMES)
    assert scopes >= {s for s in dt.SCOPE_NAMES if ".grow." in s} | {
        "lgbm.histogram", "lgbm.split_step", "lgbm.partition",
        "lgbm.split_search"}
    assert {tail for scope, tail in found if scope == "lgbm.grow.tier"} == {
        "split"}  # the fused path has the one chain
    calls = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        text, re.M)
    assert len(calls) >= 10, calls
    for name in calls:
        assert re.fullmatch(
            r"lgbm\.(split_step|partition\.place|histogram)"
            r"\.cap\d+(\.\d+)?", name), name
