"""``build_record`` / ``pack_bins``: the packed training record's layout.

Feature ``w*k + j`` rides byte (u8 bins, k=4) or half (u16, k=2) ``j`` of
word-row ``w``; columns past ``n`` are zero.  ``pack_bins`` widens eight
word-rows at a time (PR 27: widening the whole matrix at ``n_pad`` held
11.4 GiB of scratch at 7.5M x 100), so the group boundaries are the
cases: fewer features than a word, exactly one group, one group and a
ragged second."""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu.ops.record as R


def _pack_numpy(bins, n_pad):
    F, n = bins.shape
    k = 4 if bins.dtype.itemsize == 1 else 2
    Wb = -(-F // k)
    x = np.zeros((Wb * k, n_pad), np.int64)
    x[:F, :n] = bins
    x = x.reshape(Wb, k, n_pad)
    out = np.zeros((Wb, n_pad), np.int64)
    for j in range(k):
        out |= x[:, j, :] << ((32 // k) * j)
    return out.astype(np.uint32).view(np.int32)


@pytest.mark.parametrize("dtype,F", [
    (np.uint8, 1), (np.uint8, 7), (np.uint8, 32), (np.uint8, 33),
    (np.uint8, 100), (np.uint16, 5), (np.uint16, 16), (np.uint16, 17)])
@pytest.mark.parametrize("n_pad", [1000, 1536])
def test_pack_bins_layout(dtype, F, n_pad):
    rng = np.random.RandomState(F)
    bins = rng.randint(0, np.iinfo(dtype).max + 1, (F, 1000)).astype(dtype)
    got = np.asarray(R.pack_bins(jnp.asarray(bins), n_pad))
    want = _pack_numpy(bins, n_pad)
    assert got.shape == want.shape and got.dtype == np.int32
    assert got.tobytes() == want.tobytes()


def test_build_record_rows_and_round_trip():
    """Word rows, then grad / hess / mask (float32 bits), row id (``n``
    past the rows), leaf id 0; ``unpack_window`` gives the inputs back."""
    F, n, n_pad = 11, 700, 1024
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 200, (F, n)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = (rng.rand(n) + 0.5).astype(np.float32)
    m = (rng.rand(n) < 0.7).astype(np.float32)
    rec = R.build_record(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                         jnp.asarray(m), n_pad)
    Wb = R.num_words(F, 4)
    assert rec.shape == (R.rec_height(F, 4), n_pad)
    r = np.asarray(rec)
    np.testing.assert_array_equal(r[:Wb], _pack_numpy(bins, n_pad))
    np.testing.assert_array_equal(r[Wb + 3, :n], np.arange(n))
    assert (r[Wb + 3, n:] == n).all() and not r[Wb + 4:].any()
    b2, g2, h2, m2 = R.unpack_window(rec[:, :n], F, 4, jnp.uint8)
    for a, b in ((bins, b2), (g, g2), (h, h2), (m, m2)):
        np.testing.assert_array_equal(a, np.asarray(b))
