"""Set-up from the inside (obs/telemetry.py ``setup_timeline``): the
``lgbm.setup.*`` spans through ingest, booster construction and the
first ``update()``, the ``compile.*`` seconds by jitted program
(analysis/recompile.py), the two host spans that close an iteration's
coverage, and the one JSON line at process exit.

CPU only, and no wall-clock comparison between runs: a span is checked
against its own parent, a counter against the table's shape.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import RunManifest, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# case -> rows: the three objectives the benchmark's cells run, and the
# binary one with its rows dealt to the host's devices (tree_learner=data,
# a row count that divides them), each at a shape of its own (and one no
# other test file uses), so the grow program really traces and compiles
# in this process for each
ROWS = {"regression": 1531, "binary": 1543, "lambdarank": 1549,
        "binary-data": 1552}
COLUMNS, DEAD_COLUMN = 9, 4
PARAMS = {"num_leaves": 11, "max_bin": 37, "min_data_in_leaf": 5,
          "verbose": -1}

INGEST = "lgbm.setup.ingest"
BOOSTER = "lgbm.setup.booster"
FIRST_ITER = "lgbm.setup.first_iter"
SETUP_SPANS = (
    INGEST, INGEST + ".metadata", INGEST + ".float64",
    INGEST + ".find_bins", INGEST + ".encode",
    BOOSTER, BOOSTER + ".objective", BOOSTER + ".learner",
    BOOSTER + ".upload", BOOSTER + ".metrics", FIRST_ITER)
# where the rows go to the shards of a mesh, the upload is named so
SHARDED = {BOOSTER + ".upload": BOOSTER + ".shard"}
EPS = 2e-6  # a snapshot rounds seconds to the microsecond


def spans_of(case: str) -> tuple:
    """The set-up spans a case records."""
    if case.endswith("-data"):
        return tuple(SHARDED.get(name, name) for name in SETUP_SPANS)
    return SETUP_SPANS


def make_table(case: str, n: int = 0):
    n = n or ROWS[case]
    objective = case.partition("-")[0]
    rng = np.random.default_rng(n)
    X = rng.standard_normal((n, COLUMNS)).astype(np.float32)
    X[:, DEAD_COLUMN] = 1.0  # a trivial column: binned away
    score = X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
    kwargs = {}
    if objective == "regression":
        y = score
    elif objective == "binary":
        y = (score > 0).astype(np.float64)
    else:
        y = np.clip(np.round(score + 1.5), 0, 4)
        sizes = [7] * (n // 7)
        kwargs["group"] = sizes + [n - sum(sizes)]
    return X, y, kwargs


def set_up_and_update(case: str):
    """``Dataset`` + ``Booster`` + two ``update()`` calls, the way
    ``engine.train`` and the benchmark make them; the snapshot after
    each ``update()``."""
    X, y, kwargs = make_table(case)
    params = {**PARAMS, "objective": case.partition("-")[0]}
    if case.endswith("-data"):
        params["tree_learner"] = "data"
    ds = lgb.Dataset(X, label=y, params=params, **kwargs)
    booster = lgb.Booster(params=params, train_set=ds)
    booster.update()
    first = telemetry.get_telemetry().snapshot()
    booster.update()
    return X, first, telemetry.get_telemetry().snapshot()


@pytest.fixture(scope="module", params=sorted(ROWS))
def run(request):
    tel = telemetry.get_telemetry()
    was = tel.enabled
    telemetry.set_enabled(True)
    tel.reset()
    try:
        X, first, second = set_up_and_update(request.param)
    finally:
        telemetry.set_enabled(was)
    return {"X": X, "first": first, "second": second,
            "spans": spans_of(request.param)}


@pytest.mark.parametrize("name", SETUP_SPANS)
def test_setup_span_once_and_inside_its_parent(run, name):
    name = run["spans"][SETUP_SPANS.index(name)]
    spans = run["second"]["spans"]
    assert name in spans, sorted(spans)
    st = spans[name]
    assert st["count"] == 1
    assert st["first_start_s"] > 0 and st["total_s"] >= 0
    parent = name.rpartition(".")[0]
    if parent in spans:
        up = spans[parent]
        assert up["first_start_s"] <= st["first_start_s"] + EPS
        assert (st["first_start_s"] + st["total_s"]
                <= up["first_start_s"] + up["total_s"] + EPS)


def test_timeline_orders_spans_and_reports_uncovered(run):
    rows = telemetry.setup_timeline(run["second"])
    assert sorted(r["name"] for r in rows) == sorted(run["spans"])
    starts = [r["start_s"] for r in rows]
    assert starts == sorted(starts)
    by_name = {r["name"]: r for r in rows}
    # the three phases in the order the program runs them
    assert (by_name[INGEST]["start_s"] < by_name[BOOSTER]["start_s"]
            < by_name[FIRST_ITER]["start_s"])
    for r in rows:
        assert r["uncovered_s"] >= 0
        want = r["name"].rpartition(".")[0]
        assert r["parent"] == (want if want in by_name else None)
        kids = sum(k["seconds"] for k in rows if k["parent"] == r["name"])
        assert abs(r["seconds"] - kids - r["uncovered_s"]) <= 6 * EPS
    top = sum(r["seconds"] for r in rows if r["parent"] is None)
    assert abs(sum(r["uncovered_s"] for r in rows) - top) <= 12 * EPS


def test_compile_seconds_name_the_grow_program(run):
    c = run["first"]["counters"]
    assert c["compile.trace_s.jit_grow_tree"] > 0
    assert c["compile.lower_s.jit_grow_tree"] > 0
    assert (c["compile.backend_s.jit_grow_tree"] > 0
            or c.get("compile.cache_retrieval_s", 0) > 0)
    assert c["compile.programs"] >= 2  # the grower and the objective
    # no name that jax's own spelling, "jit(grow_tree)", leaks into
    assert not [k for k in c if "(" in k]


def test_second_update_moves_no_compile_counter(run):
    first = {k: v for k, v in run["first"]["counters"].items()
             if k.startswith("compile.")}
    second = {k: v for k, v in run["second"]["counters"].items()
              if k.startswith("compile.")}
    assert first and first == second


def test_host_spans_cover_the_iteration(run):
    spans, counters = run["second"]["spans"], run["second"]["counters"]
    trees = iterations = counters["train_iters"]
    assert iterations == 2
    assert spans["lgbm.host.sample"]["count"] == iterations
    # one at a tree's ``models.append``, one at an iteration's tail
    assert spans["lgbm.host.book"]["count"] == trees + iterations
    for name in ("gradients", "grow", "stop_check", "post_grow"):
        assert spans["lgbm.host." + name]["count"] == trees


def test_ingest_counters_say_the_tables_shape(run):
    X, c = run["X"], run["second"]["counters"]
    n, f = X.shape
    assert c["ingest.rows"] == n
    assert c["ingest.columns"] == f
    assert c["ingest.used_columns"] == f - 1
    assert c["ingest.sample_rows"] == n  # under bin_construct_sample_cnt
    assert c["ingest.float64_bytes"] == n * f * 8
    assert c["ingest.bin_bytes"] == n * (f - 1)
    # the [F, n] bins, a score and a bag weight a row, at the least
    assert c["setup.upload_bytes"] >= n * (f - 1) + 8 * n


def test_manifest_carries_the_timeline(run):
    tel = telemetry.get_telemetry()
    m = RunManifest.collect("test_setup_spans")
    assert m.setup == telemetry.setup_timeline(tel.snapshot())
    assert RunManifest.from_dict(m.to_dict()).setup == m.setup


def test_telemetry_off_records_nothing():
    tel = telemetry.get_telemetry()
    was = tel.enabled
    tel.reset()
    telemetry.set_enabled(False)
    try:
        X, y, _ = make_table("regression", 1559)  # it compiles anew
        params = {**PARAMS, "objective": "regression"}
        booster = lgb.Booster(params=params, train_set=lgb.Dataset(
            X, label=y, params=params))
        booster.update()
        snap = tel.snapshot(include_compiles=False)
    finally:
        telemetry.set_enabled(was)
    assert snap["spans"] == {} and snap["counters"] == {}
    assert telemetry.setup_timeline(snap) == []


SCRIPT = """
import numpy as np, lightgbm_tpu as lgb
from lightgbm_tpu.log import Log
Log.reset_log_level(0)  # the booster's own Info line goes to stdout
rng = np.random.default_rng(3)
X = rng.standard_normal((400, 5))
lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1},
          lgb.Dataset(X, label=X[:, 0]), num_boost_round=2)
"""


def test_json_mode_prints_one_line_at_exit():
    env = {**os.environ, "LGBM_TPU_TELEMETRY": "json",
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout == ""
    lines = [ln for ln in p.stderr.splitlines()
             if "lgbm_tpu_telemetry" in ln]
    assert len(lines) == 1
    snap = json.loads(lines[0])["lgbm_tpu_telemetry"]
    assert [r["name"] for r in snap["setup"]][0] == INGEST
    assert {r["name"] for r in snap["setup"]} == set(SETUP_SPANS)
    c = snap["counters"]
    assert c["setup.import_s"] > 0 and c["setup.import_unix_s"] > 1e9
    assert c["compile.trace_s.jit_grow_tree"] > 0
    assert snap["spans"][INGEST]["first_start_s"] >= c["setup.import_s"]
