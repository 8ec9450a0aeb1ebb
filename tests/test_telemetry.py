"""Tier-1 gate for the obs subsystem: telemetry counters/spans pinned on
synthetic workloads, manifest schema round-trip, trace bucketing,
collective stats, and benchdiff catching a doctored regression.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from lightgbm_tpu.obs import manifest as manifest_mod
from lightgbm_tpu.obs.manifest import RunManifest, manifest_path, validate
from lightgbm_tpu.obs.telemetry import (
    Reservoir,
    Telemetry,
    collective_stats,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- telemetry

def test_spans_and_counters_pinned():
    tel = Telemetry()
    with tel.span("phase.a"):
        time.sleep(0.01)
    with tel.span("phase.a"):
        pass
    tel.count("widgets", 2)
    tel.count("widgets")
    snap = tel.snapshot(include_compiles=False)
    a = snap["spans"]["phase.a"]
    assert a["count"] == 2
    assert a["total_s"] >= 0.01
    assert a["min_s"] <= a["max_s"] <= a["total_s"]
    assert snap["counters"]["widgets"] == 3


def test_disabled_telemetry_records_nothing():
    tel = Telemetry(enabled=False)
    with tel.span("x"):
        pass
    tel.count("c")
    tel.record_value("r", 1.0)
    tel.observe("h", 0.5)
    tel.record_samples({"s": 1.0})
    snap = tel.snapshot(include_compiles=False)
    assert snap == {"counters": {}, "spans": {}, "reservoirs": {},
                    "histograms": {}}


def test_reservoir_percentiles_and_window():
    r = Reservoir(cap=100)
    for v in range(1, 101):  # 0.01 .. 1.00
        r.add(v / 100.0)
    assert r.percentile(50) == pytest.approx(0.50, abs=0.015)
    assert r.percentile(99) == pytest.approx(0.99, abs=0.015)
    d = r.as_dict()
    assert d["count"] == 100 and d["window"] == 100
    # overflow: the window slides, total count keeps the truth
    for _ in range(50):
        r.add(5.0)
    d = r.as_dict()
    assert d["count"] == 150 and d["window"] == 100
    assert d["max_s"] == 5.0


def test_train_loop_feeds_telemetry():
    """The library's own counters move when a model trains: iteration
    count, per-tree dispatch reservoir, and the grow-program trace
    counter (exactly one trace for a warm same-shape loop)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.obs import telemetry

    tel = telemetry.get_telemetry()
    base = tel.snapshot(include_compiles=False)["counters"]
    rng = np.random.RandomState(0)
    X = rng.randn(256, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=4, max_bin=16,
                 min_data_in_leaf=5)
    ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
    booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))
    for _ in range(3):
        booster.train_one_iter()
    np.asarray(booster._scores)
    snap = tel.snapshot(include_compiles=False)
    iters = snap["counters"]["train_iters"] - base.get("train_iters", 0)
    traces = snap["counters"]["grow_traces"] - base.get("grow_traces", 0)
    assert iters == 3
    assert traces >= 1  # compiled once (or resumed a cached trace)
    res = tel.reservoir("tree_dispatch_s")
    assert res is not None and len(res) >= 3


@pytest.mark.parametrize("scope", ["lgbm.split_search", "lgbm.gradients"])
def test_phase_scope_lands_in_compiled_hlo(scope):
    """Static proof of attribution: op metadata in the COMPILED program
    carries the lgbm scope that obs/device_time reads back from a trace
    (the grower's own scopes: tests/test_chip_compile.py)."""
    import jax.numpy as jnp

    from lightgbm_tpu.objectives import _l2_grads
    from lightgbm_tpu.ops.split import find_best_split

    F, B = 4, 8
    hist = jnp.zeros((F, B, 3), jnp.float32)
    fn, args = {
        "lgbm.split_search": (find_best_split, (
            hist, jnp.float32(0), jnp.float32(1), jnp.float32(8),
            jnp.ones(F, bool), jnp.full(F, B, jnp.int32),
            jnp.zeros(F, bool), jnp.float32(1), jnp.float32(1e-3),
            jnp.float32(0), jnp.float32(0), jnp.float32(0),
            jnp.bool_(True))),
        "lgbm.gradients": (_l2_grads, (
            jnp.zeros(8, jnp.float32), jnp.ones(8, jnp.float32), None)),
    }[scope]
    assert scope in fn.lower(*args).compile().as_text()


def test_emit_json_line_shape(capsys):
    tel = Telemetry()
    tel.count("c")
    tel.emit(stream=sys.stdout)
    line = capsys.readouterr().out.strip()
    data = json.loads(line)
    assert "lgbm_tpu_telemetry" in data
    assert data["lgbm_tpu_telemetry"]["counters"]["c"] == 1


# ----------------------------------------------------------- collectives

def test_collective_stats_on_synthetic_hlo():
    hlo = """\
ENTRY %main (p0: f32[64,32]) -> f32[64,32] {
  %p0 = f32[64,32] parameter(0)
  %ar = f32[64,32] all-reduce(%p0), replica_groups={}
}
%body (p: (f32[16], s32[4])) -> (f32[16], s32[4]) {
  %t = (f32[16], s32[4]) all-reduce(%p), replica_groups={}
  %ag = f32[128] all-gather(%x), dimensions={0}
  %done = f32[16] all-reduce-done(%t)
}
"""
    stats = collective_stats(hlo)
    assert stats["total"] == 3
    assert stats["by_op"] == {"all-reduce": 2, "all-gather": 1}
    ent = stats["by_computation"]["ENTRY"]
    assert ent["payload_bytes"] == 64 * 32 * 4
    body = stats["by_computation"]["%body"]
    # variadic result: both tuple components count toward payload
    assert body["payload_bytes"] == (16 * 4 + 4 * 4) + 128 * 4


# --------------------------------------------------------------- manifest

def test_manifest_roundtrip_and_validate(tmp_path):
    m = RunManifest.collect(
        "test", config={"rows": 10, "leaves": 3},
        result={"value": 1.25, "unit": "s/tree"},
        phases={"histogram": 0.5},
        warmup={"warmup_iters": 2, "compile_stable": True},
    )
    d = m.to_dict()
    validate(d)  # schema contract
    assert d["schema"] == "lightgbm-tpu/run-manifest/v1"
    assert d["config_fingerprint"] == manifest_mod.config_fingerprint(
        {"rows": 10, "leaves": 3})
    path = tmp_path / "run.manifest.json"
    m.write(str(path))
    m2 = RunManifest.load(str(path))
    assert m2.to_dict() == d
    # a gutted manifest must not validate
    bad = dict(d)
    bad.pop("git")
    with pytest.raises(ValueError, match="git"):
        validate(bad)
    with pytest.raises(ValueError, match="schema"):
        validate({**d, "schema": "nope/v0"})


def test_manifest_path_pairing():
    assert manifest_path("/a/BENCH_r05.json") == "/a/BENCH_r05.manifest.json"
    assert manifest_path("/a/model.txt") == "/a/model.txt.manifest.json"


def test_config_fingerprint_stability():
    fp = manifest_mod.config_fingerprint
    assert fp({"a": 1, "b": 2}) == fp({"b": 2, "a": 1})
    assert fp({"a": 1}) != fp({"a": 2})
    assert fp(None) is None


# -------------------------------------------------------------- benchdiff

def _benchdiff(*argv):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "benchdiff.py"),
         *argv],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    return r


def test_benchdiff_flags_doctored_regression(tmp_path):
    """A +20% doctored headline (and a phase blow-up) must be flagged;
    the reverse direction must exit clean."""
    base_row = {"metric": "m", "value": 0.40, "unit": "s/tree",
                "vs_baseline": 1.0, "platform": "tpu",
                "train_auc": 0.85, "compiles_timed": 0,
                "phases": {"histogram": 0.10, "partition": 0.20}}
    doctored = dict(base_row)
    doctored.update(value=0.48, vs_baseline=0.83,
                    phases={"histogram": 0.10, "partition": 0.29})
    old_p, new_p = tmp_path / "old.json", tmp_path / "new.json"
    old_p.write_text(json.dumps(base_row))
    new_p.write_text(json.dumps(doctored))

    r = _benchdiff(str(old_p), str(new_p), "--json",
                   str(tmp_path / "rep.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout
    assert "headline" in r.stdout
    assert "phase 'partition'" in r.stdout
    rep = json.loads((tmp_path / "rep.json").read_text())
    assert rep["report"]["regressions"]

    r = _benchdiff(str(new_p), str(old_p))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "REGRESSION" not in r.stdout


def test_benchdiff_flags_driver_artifact_regression():
    """Two driver-artifact rows of one cell (tests/fixtures), the second
    20% slower: benchdiff must flag it."""
    fx = os.path.join(ROOT, "tests", "fixtures")
    r = _benchdiff(os.path.join(fx, "bench_driver_row_a.json"),
                   os.path.join(fx, "bench_driver_row_b.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout
    assert "driver-config row" in r.stdout


def test_benchdiff_diffs_cli_train_manifests(tmp_path):
    """README promises ANY two run manifests are diffable: cli.train
    manifests carry train_wall_s + num_trees, not 'value' (review
    finding) — the headline is synthesized as wall/trees."""
    m_old = RunManifest.collect(
        "cli.train", result={"num_trees": 10, "train_wall_s": 2.0,
                             "output_model": "/tmp/m.txt"})
    m_new = RunManifest.collect(
        "cli.train", result={"num_trees": 10, "train_wall_s": 3.0,
                             "output_model": "/tmp/m.txt"})
    po, pn = tmp_path / "o.manifest.json", tmp_path / "n.manifest.json"
    m_old.write(str(po))
    m_new.write(str(pn))
    r = _benchdiff(str(po), str(pn))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "0.2000 -> 0.3000" in r.stdout


def test_benchdiff_reports_one_sided_phase(tmp_path):
    """A phase present on only one side signals lost attribution and
    must never be silently dropped (review finding)."""
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(
        {"metric": "m", "value": 0.40, "unit": "s/tree",
         "phases": {"histogram": 0.10, "partition": 0.20}}))
    pn.write_text(json.dumps(
        {"metric": "m", "value": 0.42, "unit": "s/tree",
         "phases": {"histogram": 0.10, "unattributed": 0.30}}))
    r = _benchdiff(str(po), str(pn))
    assert "present only in the old run" in r.stdout
    assert "present only in the new run" in r.stdout


def test_benchdiff_reads_manifests(tmp_path):
    m_old = RunManifest.collect(
        "bench.py", result={"metric": "m", "value": 0.30,
                            "unit": "s/tree"},
        phases={"histogram": 0.1})
    m_new = RunManifest.collect(
        "bench.py", result={"metric": "m", "value": 0.60,
                            "unit": "s/tree"},
        phases={"histogram": 0.25})
    po, pn = tmp_path / "a.manifest.json", tmp_path / "b.manifest.json"
    m_old.write(str(po))
    m_new.write(str(pn))
    r = _benchdiff(str(po), str(pn))
    assert r.returncode == 1
    assert "REGRESSION" in r.stdout
    assert "phase 'histogram'" in r.stdout


def test_benchdiff_rejects_unusable_input(tmp_path):
    p = tmp_path / "junk.json"
    p.write_text(json.dumps({"value": 0}))
    r = _benchdiff(str(p), str(p))
    assert r.returncode == 2


def test_benchdiff_flags_crashed_new_run(tmp_path):
    """bench.py's crash path emits value 0.0 + error: that is the worst
    regression, never a -100% improvement (review finding)."""
    good = tmp_path / "good.json"
    crashed = tmp_path / "crashed.json"
    good.write_text(json.dumps(
        {"metric": "m", "value": 0.40, "unit": "s/tree"}))
    crashed.write_text(json.dumps(
        {"metric": "m", "value": 0.0, "unit": "s/tree",
         "vs_baseline": 0.0, "error": "RuntimeError: boom"}))
    r = _benchdiff(str(good), str(crashed))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "NEW run errored" in r.stdout
    assert "improvement" not in r.stdout
