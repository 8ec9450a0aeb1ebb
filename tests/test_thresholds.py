"""The model's float32 thresholds send a raw value where training binned it.

A numerical bin bound is the float64 midpoint of two neighbouring values
of the bin sample.  A value the sample missed can lie at that midpoint:
on a 0.001 grid, 0.123 between a sample's 0.122 and 0.124.  Rounded to the
NEAREST float32 the bound can be that value itself, which training binned
right of the bound (it is a hair above the float64 midpoint) and a raw
``<=`` then sends left.  The model's thresholds are the largest float32 at
most each bound (models/tree.py ``float32_thresholds``), on the host path
and on the device path alike.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.io.binner import BinMapper
from lightgbm_tpu.models import tree as tree_mod


def thousandths(n: int) -> np.ndarray:
    return (np.arange(n) / 1000).astype(np.float32)


def model_thresholds(path: str, bounds: np.ndarray, nv: int) -> np.ndarray:
    """The float32 threshold of every value bin but the last, as a
    finished tree stores it (``host``: finalize_thresholds) or as the
    device finaliser reads it (``device``: pack_threshold_bounds)."""
    if path == "device":
        mat, _ = tree_mod.pack_threshold_bounds([bounds], [0])
        return np.asarray(mat)[0, :nv - 1]
    L = nv
    tree = tree_mod.empty_tree(L)._replace(
        num_leaves=jnp.int32(L),
        split_feature=jnp.zeros(L - 1, jnp.int32),
        threshold_bin=jnp.arange(L - 1, dtype=jnp.int32))
    done = tree_mod.finalize_thresholds(tree, [bounds], np.array([0]))
    return np.asarray(done.threshold_real)


@pytest.mark.parametrize("path", ["host", "device"])
def test_a_value_the_bin_sample_missed_goes_where_it_was_binned(path):
    data = thousandths(4000)
    sample = data[::2]  # the even thousandths: every odd one is a midpoint
    m = BinMapper.find(sample.astype(np.float64), max_bin=255)
    nv = m.num_bin
    thr = model_thresholds(path, m.bin_upper_bound, nv)
    binned = m.value_to_bin(data.astype(np.float64))
    for b in range(nv - 1):
        np.testing.assert_array_equal(data <= thr[b], binned <= b,
                                      err_msg=f"bound {b}")
