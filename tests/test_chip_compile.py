"""Compile the CHIP program with no chip.

Tier-1 runs on the CPU, where the library picks segment-sum histograms
and the jax.numpy search; on a TPU it picks the raw-layout Pallas path
— a different program, which no CPU test executes.  libtpu can still
compile it: select the TPU paths (device.assume_platform), lower the
serial grower for a described v5e topology, and let Mosaic accept or
refuse every kernel.  A refusal then fails here, not in a chip call.
This says nothing about what the kernels compute (chip_smoke.py does).
"""

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


def _v5e_topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"libtpu gives no v5e topology here: {e}")


def test_serial_grower_compiles_for_v5e():
    topo = _v5e_topology()
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
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    assert mosaic_calls >= 10, mosaic_calls  # 19 at this shape
    compiled = lowered.compile()  # Mosaic refuses a kernel here, or not
    assert compiled.memory_analysis().temp_size_in_bytes > 0
