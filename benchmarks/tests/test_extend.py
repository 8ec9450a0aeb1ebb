"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell by adding files and appending entries to BENCHMARK.json, editing no
file that is there.  Rehearsed in a temporary copy: a regression
configuration with its own generator and reference objective, a shorter
mix, a metric of the score update, and the cell that uses them."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

GENERATOR = '''
import numpy as np


def generate(p, seed):
    rng = np.random.default_rng(int(seed))
    X = np.round(rng.standard_normal((p["rows"], p["features"])), 3)
    y = X[:, 0] * X[:, 1] + np.abs(X[:, 2]) + 0.1 * rng.standard_normal(
        p["rows"])
    return {"X": X.astype(np.float32), "y": y.astype(np.float32),
            "group": None}
'''

REFERENCE = '''
import numpy as np


class Objective:
    """L2: gradient s - y, hessian 1."""

    def __init__(self, data, params):
        self.y = data["y"].astype(np.float32)

    def gradients(self, scores):
        return scores - self.y, np.ones_like(scores)

    def loss(self, scores):
        return float(np.mean((scores.astype(np.float64) - self.y) ** 2))
'''


def test_new_files_and_appended_entries_are_enough(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "lightgbm_tpu"), tmp_path / "lightgbm_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file()}
    b = tmp_path / "benchmarks"
    (b / "generators" / "toy_surface.py").write_text(GENERATOR)
    (b / "references" / "toy_l2.py").write_text(REFERENCE)
    (b / "configs" / "toy-12.json").write_text(json.dumps({
        "name": "toy-12", "objective": "toy_l2",
        "params": {"objective": "regression", "num_leaves": 15,
                   "max_bin": 63, "learning_rate": 0.1,
                   "min_data_in_leaf": 20, "verbose": -1,
                   "hist_dtype": "float64"},
        "defaults_relied_on": {"lambda_l2": 0.0,
                               "min_sum_hessian_in_leaf": 10.0},
        "generator": {"name": "toy_surface",
                      "params": {"rows": 20000, "features": 12}},
        "rehearsal": {"rows": 20000, "num_leaves": 15}}))
    shutil.copy(b / "limits" / "synthetic-100.json",
                b / "limits" / "toy-12.json")
    mix = json.loads((b / "traffic" / "train_steady.json").read_text())
    (b / "traffic" / "train_short.json").write_text(json.dumps(
        {**mix, "name": "train_short", "min_warmup_trees": 3}))
    (b / "layers" / "leaf_update_ms_per_tree.train.json").write_text(
        json.dumps({"name": "leaf_update_ms_per_tree.train",
                    "reader": "scope_ms_per_tree",
                    "scopes": ["lgbm.leaf_update"]}))
    (b / "layers" / "warmup_trees.train.json").write_text(json.dumps(
        {"name": "warmup_trees.train", "reader": "reading",
         "reading": "warmup_trees"}))

    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    bench["configs"].append({
        "name": "toy-12", "source": "none: a rehearsal",
        "file": "benchmarks/configs/toy-12.json", "reduced": [],
        "why": "a regression surface"})
    bench["workloads"].append({
        "name": "toy-12.train", "config": "toy-12",
        "traffic": "train_short", "chips": 1, "why": "rehearsal"})
    for name in ("leaf_update_ms_per_tree.train", "warmup_trees.train"):
        bench["per_layer"].append({
            "name": name, "unit": "ms/tree", "better": "lower",
            "source": "device_trace", "layer": "objective + score update",
            "moves": "train_s_per_tree", "workloads": ["toy-12.train"]})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy-12.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "toy-12.train",
         "--seed", "77", "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stderr.strip().splitlines()[-1][len("rehearsal: "):])
    assert result["correct"] is True, result["checked"]
    # the new counter is read; the new trace metric finds no device trace on
    # the CPU and is left out, not reported as 0
    assert result["metrics"]["warmup_trees.train"]["value"] == 3
    assert "leaf_update_ms_per_tree.train" not in result["metrics"]
    # the old cells' metrics are not reported in the new cell
    assert "binning_s.train" not in result["metrics"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
