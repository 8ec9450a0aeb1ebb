"""Native IO runtime tests: the C++ parser/encoder must agree exactly
with the pure-Python path on the reference example files and synthetic
edge cases (src/native/lgbm_native.cpp vs io/parser.py + BinMapper)."""

import os

import numpy as np
import pytest

from lightgbm_tpu import native
from lightgbm_tpu.io.binner import BinMapper, find_bin_mappers
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.parser import parse_file, detect_format, _read_head


pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def _python_parse(path, has_header=False):
    """Force the pure-python pandas/libsvm path."""
    os.environ["LIGHTGBM_TPU_NO_NATIVE"] = "1"
    try:
        import importlib

        import lightgbm_tpu.native as nat

        # reset the module cache so the env var is honored
        nat._lib, nat._tried = None, False
        out = parse_file(path, has_header=has_header)
    finally:
        del os.environ["LIGHTGBM_TPU_NO_NATIVE"]
        nat._lib, nat._tried = None, False
    return out


@pytest.mark.parametrize(
    "rel",
    [
        "binary_classification/binary.train",
        "binary_classification/binary.test",
        "regression/regression.train",
        "multiclass_classification/multiclass.train",
        "lambdarank/rank.train",
    ],
)
def test_native_python_parse_parity(reference_examples, rel):
    path = os.path.join(reference_examples, rel)
    mat_native, _ = parse_file(path)
    mat_python, _ = _python_parse(path)
    assert mat_native.shape == mat_python.shape
    np.testing.assert_allclose(mat_native, mat_python, rtol=1e-12, atol=0)


def test_native_csv_with_header_and_missing(tmp_path):
    p = str(tmp_path / "t.csv")
    with open(p, "w") as fh:
        fh.write("label,a,b\n1,2.5,3\n0,,7.25\n1,nan,-2e-3\n")
    mat, names = parse_file(p, has_header=True)
    assert names == ["label", "a", "b"]
    assert mat.shape == (3, 3)
    assert np.isnan(mat[1, 1]) and np.isnan(mat[2, 1])
    np.testing.assert_allclose(mat[2, 2], -2e-3)


def test_native_format_detection(reference_examples):
    for rel, want in [
        ("binary_classification/binary.train", "tsv"),
        ("lambdarank/rank.train", "libsvm"),
    ]:
        path = os.path.join(reference_examples, rel)
        assert native.detect_format(path, False) == want
        assert detect_format(_read_head(path, 2)) == want


def test_native_encode_parity():
    rng = np.random.RandomState(0)
    X = rng.randn(5000, 12) * rng.gamma(1, 1, 12)
    X[rng.rand(5000, 12) < 0.05] = np.nan
    mappers = find_bin_mappers(X, total_sample_cnt=5000, max_bin=63)
    bounds = [np.asarray(m.bin_upper_bound, np.float64) for m in mappers]
    out = np.empty((5000, 12), np.uint8)
    ok = native.value_to_bin_numerical(
        np.ascontiguousarray(X), np.arange(12, dtype=np.int64), bounds, out
    )
    assert ok
    for j, m in enumerate(mappers):
        np.testing.assert_array_equal(out[:, j], m.value_to_bin(X[:, j]))


def test_dataset_uses_native_encode():
    """End-to-end: BinnedDataset built with the native encoder equals the
    python-only build."""
    rng = np.random.RandomState(1)
    X = rng.randn(2000, 6)
    from lightgbm_tpu.io.metadata import Metadata

    meta = Metadata(label=(X[:, 0] > 0).astype(np.float32))
    ds1 = BinnedDataset.from_matrix(X, meta)
    os.environ["LIGHTGBM_TPU_NO_NATIVE"] = "1"
    try:
        import lightgbm_tpu.native as nat

        nat._lib, nat._tried = None, False
        ds2 = BinnedDataset.from_matrix(X, meta)
    finally:
        del os.environ["LIGHTGBM_TPU_NO_NATIVE"]
        nat._lib, nat._tried = None, False
    np.testing.assert_array_equal(ds1.X_bin, ds2.X_bin)


def test_native_rejects_malformed_rows(tmp_path):
    """Ragged/garbage rows must NOT parse silently: the native parser
    refuses (review fix); the python reader skips them as a counted,
    logged ``bad_rows`` event — or raises under strict_data=true
    (docs/resilience.md input hardening)."""
    from lightgbm_tpu.io.parser import ParseError
    from lightgbm_tpu.obs import telemetry

    p = str(tmp_path / "ragged.csv")
    with open(p, "w") as fh:
        fh.write("1,2\n1,2,3\n")
    assert native.parse_file(p, "csv", False) is None
    p2 = str(tmp_path / "garbage.csv")
    with open(p2, "w") as fh:
        fh.write("1,2.5\n1,1.5abc\n")
    assert native.parse_file(p2, "csv", False) is None
    before = telemetry.get_telemetry().counter("bad_rows")
    mat, _ = parse_file(p2)
    assert mat.shape[0] == 1  # the garbage row is gone, not crashed on
    assert telemetry.get_telemetry().counter("bad_rows") == before + 1
    with pytest.raises(ParseError):
        parse_file(p2, strict=True)


def test_native_rejects_qid_libsvm(tmp_path):
    """'qid:' tokens must not silently corrupt feature 0 (review fix)."""
    p = str(tmp_path / "rank.svm")
    with open(p, "w") as fh:
        fh.write("2 qid:1 1:0.5 2:0.3\n1 qid:1 1:0.1\n")
    assert native.parse_file(p, "libsvm", False) is None


def test_native_csv_with_stray_tab(tmp_path):
    """A tab inside a CSV must not flip the separator (review fix)."""
    p = str(tmp_path / "tab.csv")
    with open(p, "w") as fh:
        fh.write("1,2.5,3\n0,1.5,4\n")
    m = native.parse_file(p, "csv", False)
    assert m.shape == (2, 3)
    np.testing.assert_allclose(m[0], [1, 2.5, 3])


def test_native_short_rows_pad_nan(tmp_path):
    p = str(tmp_path / "short.csv")
    with open(p, "w") as fh:
        fh.write("1,2,3\n4,5\n")
    m = native.parse_file(p, "csv", False)
    assert m.shape == (2, 3)
    assert np.isnan(m[1, 2])


def test_concurrent_builds_leave_one_whole_library(tmp_path, monkeypatch):
    """Workers of a test run in a fresh checkout all find the library
    missing at once.  They take turns on a lock, the first builds into a
    file of its own and renames it into place, and the rest find it
    there: every one of them loads a whole library (a worker that loaded
    one half-written fell back to Python and recorded the ingest span
    ``lgbm.setup.ingest.encode.python`` no other worker had)."""
    import ctypes
    import threading

    import lightgbm_tpu.native as nat

    monkeypatch.setattr(nat, "_LIB_DIR", str(tmp_path))
    monkeypatch.setattr(nat, "_LIB_PATH", str(tmp_path / "liblgbm_native.so"))
    built = []
    workers = [threading.Thread(target=lambda: built.append(nat._build()))
               for _ in range(4)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    assert built == [True] * 4
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        ".build.lock", "liblgbm_native.so"]
    assert ctypes.CDLL(nat._LIB_PATH).lgbm_value_to_bin is not None
