"""The whole command, as the driver starts it, at the rehearsal size."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}


def start(args, cwd=ROOT, env=ENV):
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_to_its_end_and_prints_no_result(trace):
    done = start(["--workload", "synthetic-100.train", "--seed", "4294967311",
                  "--seconds", "1", "--trace", trace, "--rehearsal"])
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    last = done.stderr.strip().splitlines()[-1]
    assert last.startswith("rehearsal: ")
    result = json.loads(last[len("rehearsal: "):])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert "checked count_mismatch" in done.stderr
    wanted = {"0": {"train_s_per_tree", "setup_s"},
              "1": {"binning_s.train", "compile_s.train"}}[trace]
    assert wanted <= set(result["metrics"])
    # a share of a roofline or of a peak is never reported without a chip
    assert not [m for m in result["metrics"] if "roofline" in m or "mfu" in m]


def test_without_a_chip_it_fails_and_prints_nothing():
    done = start(["--workload", "synthetic-100.train", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert done.returncode != 0
    assert done.stdout == ""
    assert "TPU" in done.stderr


def test_alone_in_a_directory_it_fails_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    done = start(["--workload", "synthetic-100.train", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--rehearsal"],
                 cwd=tmp_path, env=env)
    assert done.returncode != 0
    assert done.stdout == ""


def test_an_unknown_workload_is_refused():
    done = start(["--workload", "nothing.train", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--rehearsal"])
    assert done.returncode != 0 and done.stdout == ""
