"""The reduction held against a trace recorded on the chip.

``testdata/small.xplane.pb.gz`` is the profiler's own file from a
``--rehearsal --trace 1`` run of ``istella-220.train`` on one TPU v5 lite
(60,000 rows, 31 leaves, two traced trees; PR 25's first chip call).  The
numbers below are what the reduction read from it on that day: a change to
the reduction that moves them has changed what every later PR measures.
"""

import gzip
import os
import shutil

import pytest

import readers
import run as entry
import xplane

from conftest import BENCH

SPANS = ("bench.window", "Booster.update", "final sync")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(BENCH, "testdata", "small.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    raw = xplane.read(str(path), SPANS)
    out = {"trace": raw, "traced": {"trees": 2, "seconds": 0.0997,
                                    "tree_counts": []}}
    return raw, entry.reduce_trace(out)


def test_planes_lines_and_spans(reduced):
    raw, _ = reduced
    assert list(raw["devices"]) == ["/device:TPU:0"]
    chip = raw["devices"]["/device:TPU:0"]
    assert (len(chip["ops"]), len(chip["modules"])) == (7482, 60)
    assert [h[2] for h in raw["host"]] == [
        "bench.window", "Booster.update", "Booster.update", "final sync"]


def test_busy_and_window(reduced):
    _, tr = reduced
    assert tr["window_ns"] == 99769016.0
    assert tr["busy_ns"] == 82366992.0
    assert len(tr["leaf_ops"]) == 7234 and len(tr["modules"]) == 59


@pytest.mark.parametrize("metric,value", [
    ("split_step_ms_per_tree.train", 12.592357),
    ("hist_ms_per_tree.train", 7.824819),
    ("place_ms_per_tree.train", 0.5846125),
    ("grad_update_ms_per_tree.train", 10.065158),
    ("grow_glue_ms_per_tree.train", 7.6767025),
    ("host_gap_ms_per_tree.train", 8.701012),
    ("device_idle_pct.train", 17.442312952149393),
])
def test_layer_metrics(reduced, metric, value):
    _, tr = reduced
    ctx = {"trace": tr, "readings": {"features": 220}, "peak": None}
    assert readers.value(metric, ctx) == pytest.approx(value, rel=1e-9)


def test_the_parts_of_a_tree_add_up_to_its_busy_time(reduced):
    _, tr = reduced
    ctx = {"trace": tr, "readings": {}, "peak": None}
    parts = sum(readers.value(m + "_ms_per_tree.train", ctx) for m in (
        "split_step", "hist", "place", "grad_update", "grow_glue"))
    assert parts == pytest.approx(tr["busy_ns"] / 1e6 / 2, rel=0.1)


def test_shares_are_left_out_without_peaks(reduced):
    _, tr = reduced
    ctx = {"trace": tr, "readings": {"features": 220}, "peak": None}
    assert readers.value("split_step_roofline.train", ctx) is None
    assert readers.value("step_mfu.train", ctx) is None


def test_breakdown_names_the_kernels(reduced):
    _, tr = reduced
    top = xplane.top_ops(tr["leaf_ops"], 3)
    assert [name for name, _ in top] == [
        "%lgbm.histogram.1", "%lgbm.split_step.13", "%lgbm.split_step.15"]
