import numpy as np
import pytest

from lightgbm_tpu.io.binner import BinMapper, NUMERICAL, CATEGORICAL


def test_distinct_values_fit_in_max_bin():
    # few distinct values -> one bin per value, midpoint bounds
    vals = np.array([1.0, 2.0, 2.0, 3.0, 1.0, 3.0, 3.0])
    m = BinMapper.find(vals, max_bin=256)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound[:-1], [1.5, 2.5])
    assert m.bin_upper_bound[-1] == np.inf
    assert not m.is_trivial
    # mapping: value <= upper bound
    bins = m.value_to_bin(np.array([0.5, 1.0, 1.6, 2.0, 2.51, 99.0]))
    np.testing.assert_array_equal(bins, [0, 0, 1, 1, 2, 2])


def test_trivial_feature():
    m = BinMapper.find(np.full(10, 7.0), max_bin=256)
    assert m.num_bin == 1
    assert m.is_trivial


def test_elided_zeros_are_counted():
    # sample holds only non-zeros; total_sample_cnt implies 5 zeros
    vals = np.array([1.0, 2.0])
    m = BinMapper.find(vals, total_sample_cnt=7, max_bin=256)
    assert m.num_bin == 3  # 0, 1, 2
    assert m.value_to_bin(np.array([0.0]))[0] == 0
    assert m.default_bin == 0


def test_greedy_equal_frequency_binning():
    rng = np.random.RandomState(0)
    vals = rng.randn(10000)
    m = BinMapper.find(vals, max_bin=16)
    assert m.num_bin <= 16
    assert m.num_bin >= 14  # roughly equal-frequency
    bins = m.value_to_bin(vals)
    counts = np.bincount(bins, minlength=m.num_bin)
    # equal-frequency: no empty bins, roughly balanced
    assert counts.min() > 0
    assert counts.max() < 3 * 10000 / 16


def test_big_count_value_gets_own_bin():
    # a value holding >1/max_bin of mass must isolate into its own bin
    vals = np.concatenate([np.zeros(5000), np.linspace(1, 2, 5000)])
    m = BinMapper.find(vals, max_bin=8)
    zb = m.value_to_bin(np.array([0.0]))[0]
    # bin of zero contains only zeros
    other = m.value_to_bin(np.array([1.0]))[0]
    assert other != zb


def test_monotone_mapping():
    rng = np.random.RandomState(1)
    vals = rng.exponential(size=5000)
    m = BinMapper.find(vals, max_bin=32)
    xs = np.sort(rng.exponential(size=100))
    bins = m.value_to_bin(xs)
    assert np.all(np.diff(bins) >= 0)


def test_categorical_binning():
    vals = np.array([3.0] * 50 + [7.0] * 30 + [1.0] * 20 + [9.0] * 5)
    m = BinMapper.find(vals, max_bin=3, bin_type=CATEGORICAL)
    assert m.bin_type == CATEGORICAL
    assert m.num_bin == 3
    # sorted by count desc: 3, 7 kept; 1, 9 and values never met share
    # the column's last bin, the others' (io/binner.py), within max_bin
    assert m.bin_to_category == [3, 7]
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([3.0, 7.0, 1.0, 9.0, 42.0, -5.0])),
        [0, 1, 2, 2, 2, 2]
    )
    # fewer categories than bins: all kept, the others' bin still last
    m = BinMapper.find(vals, max_bin=255, bin_type=CATEGORICAL)
    assert m.bin_to_category == [3, 7, 1, 9] and m.num_bin == 5
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([9.0, 2.0, 1e7])), [3, 4, 4])


def test_categorical_ids_may_be_wide_and_sparse():
    """One encoder whatever the ids' span: hashes or keys of a large
    table are looked up as label encodings are."""
    ids = np.array([7, 1 << 40, -(1 << 33), 12345678901], np.float64)
    vals = np.repeat(ids, [40, 30, 20, 10])
    m = BinMapper.find(vals, max_bin=255, bin_type=CATEGORICAL)
    assert m.bin_to_category == [int(v) for v in ids] and m.num_bin == 5
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([ids[2], ids[0], 8.0, 1e15, ids[1],
                                 np.nan])),
        [2, 0, 4, 4, 1, 4])


def test_one_kept_category_splits_against_overflow_rows_only():
    """``max_bin`` 2 over several categories keeps one, and that one
    against the others' bin is a split; a column of a single category
    has nothing to split and is dropped as before."""
    vals = np.array([3.0] * 50 + [7.0] * 30 + [1.0] * 20)
    m = BinMapper.find(vals, max_bin=2, bin_type=CATEGORICAL)
    assert m.bin_to_category == [3] and m.num_bin == 2
    assert not m.is_trivial
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([3.0, 7.0, 1.0])), [0, 1, 1])
    assert BinMapper.find(np.full(100, 3.0), max_bin=2,
                          bin_type=CATEGORICAL).is_trivial
    assert BinMapper.find(np.full(100, 3.0), max_bin=255,
                          bin_type=CATEGORICAL).is_trivial


def test_a_mapper_saved_before_the_others_bin_gains_one():
    """A dict from before PR 38 has ``num_bin == len(bin_to_category)``:
    loaded, its unknown values must not share the last kept category's
    bin, which the search never offers."""
    legacy = {"bin_type": CATEGORICAL, "num_bin": 3,
              "bin_upper_bound": [float("inf")],
              "bin_to_category": [3, 7, 1], "is_trivial": False}
    m = BinMapper.from_dict(legacy)
    assert m.num_bin == 4
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([3.0, 7.0, 1.0, 9.0])), [0, 1, 2, 3])
    again = BinMapper.from_dict(m.to_dict())  # today's dicts: unchanged
    assert again.num_bin == 4 and again.bin_to_category == [3, 7, 1]


def test_serialization_roundtrip():
    vals = np.random.RandomState(2).randn(1000)
    m = BinMapper.find(vals, max_bin=64)
    m2 = BinMapper.from_dict(m.to_dict())
    np.testing.assert_array_equal(
        m.value_to_bin(vals), m2.value_to_bin(vals)
    )


def test_nan_maps_to_zero_bin():
    m = BinMapper.find(np.array([-1.0, 0.0, 1.0, 2.0]), max_bin=8)
    assert (
        m.value_to_bin(np.array([np.nan]))[0] == m.value_to_bin(np.array([0.0]))[0]
    )


def test_greedy_equal_freq_matches_spec_fuzz():
    """The closure-jumping _greedy_equal_freq must be bit-identical to
    the reference's value-by-value loop (kept as _greedy_equal_freq_spec)
    across count distributions: uniform, zipf-heavy (big-count bins),
    few-distinct, constant-heavy, and tiny max_bin."""
    import numpy as np
    from lightgbm_tpu.io.binner import (
        _greedy_equal_freq, _greedy_equal_freq_spec)

    rng = np.random.RandomState(0)
    cases = []
    for trial in range(60):
        kind = trial % 5
        if kind == 0:
            nv = rng.randint(2, 400)
            counts = rng.randint(1, 20, nv)
        elif kind == 1:
            nv = rng.randint(2, 400)
            counts = rng.zipf(1.5, nv).clip(1, 10_000)
        elif kind == 2:
            nv = rng.randint(2, 8)
            counts = rng.randint(1, 2000, nv)
        elif kind == 3:
            nv = rng.randint(10, 100)
            counts = np.ones(nv, np.int64)
            counts[rng.randint(nv)] = 5000  # one dominant value
        else:
            nv = rng.randint(2, 3000)
            counts = rng.randint(1, 5, nv)
        max_bin = int(rng.choice([2, 3, 16, 255]))
        distinct = np.sort(rng.randn(nv)).astype(np.float64)
        cases.append((distinct, counts.astype(np.int64), max_bin))

    for distinct, counts, max_bin in cases:
        size = int(counts.sum())
        ub_f, c0_f = _greedy_equal_freq(distinct, counts, size, max_bin)
        ub_s, c0_s = _greedy_equal_freq_spec(distinct, counts, size, max_bin)
        np.testing.assert_array_equal(ub_f, ub_s)
        assert c0_f == c0_s, (c0_f, c0_s, max_bin, len(distinct))


def test_greedy_equal_freq_spec_parity_with_elided_mass():
    """sample_size may exceed counts.sum() (elided rows accounted at the
    caller); the fast path must still track the spec's running mean."""
    import numpy as np
    from lightgbm_tpu.io.binner import (
        _greedy_equal_freq, _greedy_equal_freq_spec)

    rng = np.random.RandomState(7)
    for _ in range(200):
        nv = rng.randint(2, 300)
        counts = rng.randint(1, 50, nv).astype(np.int64)
        extra = int(rng.randint(0, 500))
        size = int(counts.sum()) + extra
        max_bin = int(rng.choice([2, 16, 255]))
        distinct = np.sort(rng.randn(nv)).astype(np.float64)
        ub_f, c0_f = _greedy_equal_freq(distinct, counts, size, max_bin)
        ub_s, c0_s = _greedy_equal_freq_spec(distinct, counts, size, max_bin)
        np.testing.assert_array_equal(ub_f, ub_s)
        assert c0_f == c0_s
