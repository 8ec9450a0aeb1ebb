"""The program against the plain reference, configuration by configuration.

Tier-1 compared the program's paths with each other for a long time and
passed while every objective but L2 grew a wrong first leaf (ROADMAP,
queue 3, item 1).  Here three trees go through ``Booster.update`` on the
default float32 path (and once more through the fused grower a TPU chip
runs, interpreted) at each benchmark configuration's rehearsal size, the
plain reference (``benchmarks/references/gbdt_replay.py``: numpy, float64
sums, nothing of the program; one-vs-rest where the cell's driver binds
``onevsrest_replay.py``) follows them with the configuration's own
objective, and ``check.verdict`` holds the comparison to the
configuration's own ``limits/<config>.json``: the comparison that decides
``correct`` on the chip, at a size the CPU can run.

On the parent of PR 28 the binary case read ``leaf_value_gap`` 2.0e-3 to
3.2e-3 against its limit of 1e-3 and failed.  A histogram rounded to
bfloat16 where the program builds it has to fail too, or the limits would
hold nothing.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED = 2_147_483_659  # past 32 signed bits, as the driver's seeds are


def configurations():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [c["name"] for c in json.load(fh)["configs"]]


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own modules, imported the way ``run.py`` does."""
    sys.path.append(BENCH)
    import cells
    import check
    import run as entry

    yield cells, check, entry
    sys.path.remove(BENCH)


def numbers_of(harness, config: str, rows=None) -> tuple[dict, dict]:
    """``(numbers, limits)`` of three trees at the rehearsal size, or at
    ``rows`` rows."""
    cells, check, entry = harness
    # the configuration's own cell: its traffic file names the driver, and
    # the driver binds the plain reference the trees are held to
    bench = cells.benchmark()
    work = next(w for w in bench["workloads"] if w["config"] == config)
    workload = work["name"]
    cell = cells.assemble(work, bench)
    driver = cells.plugin("drivers", cell["traffic"]["driver"])
    run = entry.Run(entry.parse(["--workload", workload, "--seed", str(SEED),
                                 "--seconds", "0", "--rehearsal"]), cell)
    if rows is not None:
        run.generator_params["rows"] = rows
    run.traffic = {**run.traffic, "quiet_trees": 0,
                   "min_warmup_trees": run.traffic["checked_trees"]}
    state = driver.first_trees(run, driver.setup(run))
    return (driver.compared(run, state, driver.reference(run, state)),
            check.limits_of(config))


# rows of the fused grower's case: its kernels run interpreted here, a
# tile at a time, and 12,000 rows keep a case near 12 s
FUSED_ROWS = 12_000


@pytest.mark.parametrize("config", configurations())
@pytest.mark.parametrize("grower", ["canonical", "fused"])
def test_three_trees_agree_with_the_plain_reference(
        harness, config, grower, monkeypatch):
    """``canonical``: what the library selects on the CPU.  ``fused``:
    the grower a TPU chip runs (learners/fused.py), its kernels in
    interpret mode -- the path every ledger line comes from, held to the
    plain reference off the chip too."""
    from lightgbm_tpu.learners import fused
    from lightgbm_tpu.models.gbdt import GBDT

    rows = None
    if grower == "fused":
        monkeypatch.setattr(
            GBDT, "select_grower", lambda self, row_mask=False: ("fused", ""))
        # (a wide configuration's rehearsal is smaller still: its cases
        # pay by the column)
        rows = min(FUSED_ROWS, harness[0].read_json(
            BENCH, "configs", config + ".json")["rehearsal"]["rows"])
    # the fused grower traced (alone, or inside a data-parallel program,
    # whose trace leaves its own cache as it was): it builds its record
    built = []
    record = fused.build_record
    monkeypatch.setattr(fused, "build_record",
                        lambda *a, **k: built.append(1) or record(*a, **k))
    numbers, limits = numbers_of(harness, config, rows)
    assert bool(built) == (grower == "fused")
    correct, table = harness[1].verdict(numbers, limits)
    assert correct, table
    assert numbers["count_mismatch"] == 0


@pytest.mark.parametrize("config", configurations())
def test_a_bfloat16_histogram_fails_the_same_comparison(
        harness, config, monkeypatch):
    from lightgbm_tpu.learners import serial
    from lightgbm_tpu.ops import histogram

    sound = serial.histogram_feature_major

    def rounded(*args, **kwargs):
        hist = sound(*args, **kwargs)
        return hist.astype(jnp.bfloat16).astype(hist.dtype)

    # the serial grower's, and the one the data-parallel hooks take
    # (ops/histogram.py select_single_hist_fn)
    monkeypatch.setattr(serial, "histogram_feature_major", rounded)
    monkeypatch.setattr(histogram, "histogram_feature_major", rounded)
    jax.clear_caches()  # the grower was traced with the sound one
    try:
        numbers, limits = numbers_of(harness, config)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    correct, table = harness[1].verdict(numbers, limits)
    assert not correct, table


def test_a_program_that_ignores_the_declaration_is_not_timed(harness):
    """``airline-13.train``'s driver looks at what the program binned
    before it builds the booster: where the columns the configuration
    declares categorical were binned as numbers (the parent of PR 38
    ignored ``categorical_column`` for a matrix), the run ends at once
    with no result, as a program that cannot run the configuration."""
    cells, _, entry = harness
    bench = cells.benchmark()
    work = next(w for w in bench["workloads"] if w["config"] == "airline-13")
    cell = cells.assemble(work, bench)
    driver = cells.plugin("drivers", cell["traffic"]["driver"])
    params = dict(cell["config"]["params"])
    assert params.pop("categorical_column") == "1,2,3,6,9,10"
    cell["config"] = {**cell["config"], "params": params}
    run = entry.Run(entry.parse(["--workload", work["name"], "--seed",
                                 str(SEED), "--seconds", "0",
                                 "--rehearsal"]), cell)
    with driver.bound(), pytest.raises(SystemExit, match="nothing was run"):
        driver.setup(run)


def test_a_kept_list_one_category_short_is_not_correct(harness):
    """The one-vs-rest reference searches the kept categories the
    program hands it, so ``airline-13.train``'s driver holds the lists to
    the raw matrix's own counts: Origin with 253 kept where the bin
    sample met more than 254 fails ``kept_categories_off`` and nothing
    else."""
    cells, check, entry = harness
    bench = cells.benchmark()
    work = next(w for w in bench["workloads"] if w["config"] == "airline-13")
    cell = cells.assemble(work, bench)
    driver = cells.plugin("drivers", cell["traffic"]["driver"])
    run = entry.Run(entry.parse(["--workload", work["name"], "--seed",
                                 str(SEED), "--seconds", "0",
                                 "--rehearsal"]), cell)
    run.traffic = {**run.traffic, "quiet_trees": 0, "min_warmup_trees": 1,
                   "checked_trees": 1}
    with driver.bound():
        state = driver.first_trees(run, driver.setup(run))
    limits = check.limits_of("airline-13")
    sound = driver.compared(run, dict(state), driver.reference(run, state))
    assert check.verdict(sound, limits)[0]
    origin = next(i for i, (col, _) in enumerate(state["bounds"])
                  if col == 9)
    assert len(state["bounds"][origin][1]) == 254
    state["bounds"][origin] = (9, state["bounds"][origin][1][:-1])
    short = driver.compared(run, dict(state), driver.reference(run, state))
    correct, table = check.verdict(short, limits)
    assert not correct and short["kept_categories_off"] == 1
    assert [k for k, row in table.items()
            if not row["value"] <= row["limit"]] == ["kept_categories_off"]


def _bosch(harness, params=None, seed=SEED):
    """``bosch-968.train``'s driver and a rehearsal run of it, with the
    configuration's ``params`` replaced by ``params`` where given."""
    cells, _, entry = harness
    bench = cells.benchmark()
    work = next(w for w in bench["workloads"] if w["config"] == "bosch-968")
    cell = cells.assemble(work, bench)
    if params is not None:
        cell["config"] = {**cell["config"], "params": params}
    run = entry.Run(entry.parse(["--workload", work["name"], "--seed",
                                 str(seed), "--seconds", "0",
                                 "--rehearsal"]), cell)
    run.traffic = {**run.traffic, "quiet_trees": 0,
                   "min_warmup_trees": run.traffic["checked_trees"]}
    return cells.plugin("drivers", cell["traffic"]["driver"]), run


def test_a_program_that_bins_nan_as_zero_is_not_timed(harness):
    """``bosch-968.train``'s driver module looks at what the program
    binned before any booster: where a column with NaN cells has no
    missing bin (a program without missing bins reads NaN as 0.0; here
    ``use_missing=false`` stands in for it), the run ends at once with
    no result."""
    params = {**harness[0].read_json(BENCH, "configs", "bosch-968.json")[
        "params"], "use_missing": False}
    driver, run = _bosch(harness, params)
    with driver.bound(), pytest.raises(SystemExit, match="nothing was run"):
        driver.setup(run)


def test_a_missing_row_sent_the_other_way_is_not_correct(harness):
    """The planted fault of the mechanism: one split of each tree sends
    its missing rows to the side the program did not choose, in the
    replay that stands in the program's place.  The comparison must fail
    on it, on the counts of the rows the split moved."""
    _, check, _ = harness
    driver, run = _bosch(harness)
    with driver.bound():
        state = driver.first_trees(run, driver.setup(run))
    ref = driver.reference(run, state)
    limits = check.limits_of("bosch-968")
    assert check.verdict(driver.compared(run, state, ref), limits)[0]
    planted = driver.compared(run, state, ref, fault="altered_default")
    correct, table = check.verdict(planted, limits)
    assert not correct and planted["count_mismatch"] > 0, table


def test_a_search_without_missing_left_splits_is_not_correct(harness):
    """The planted fault of the search: no split that sends its missing
    rows left is offered, in the replay that stands in the program's
    place.  It changes an answer only where such a split is the best:
    at this seed's first root (at ``SEED`` no sampled node's best is one,
    and the fault reads 0 there), whose search ``root_argmax_gap`` holds,
    and at a node below it, which ``resolved_node_argmax_gap`` holds."""
    _, check, _ = harness
    driver, run = _bosch(harness, seed=2_200_015_838)
    with driver.bound():
        state = driver.first_trees(run, driver.setup(run))
    ref = driver.reference(run, state)
    limits = check.limits_of("bosch-968")
    assert check.verdict(driver.compared(run, state, ref), limits)[0]
    planted = driver.compared(run, state, ref, fault="withheld_left")
    correct, table = check.verdict(planted, limits)
    assert not correct, table
    assert planted["root_argmax_gap"] > limits["root_argmax_gap"]["limit"]
    assert planted["resolved_node_argmax_gap"] > limits[
        "resolved_node_argmax_gap"]["limit"], table


def test_the_new_cell_rehearses_correct_through_run_py():
    """``benchmarks/run.py`` itself, as the driver starts it, finds the
    cell's files by the names in BENCHMARK.json and reads ``correct``."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "malware-81.train", "--seed", str(SEED), "--seconds", "0.5",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stderr.strip().splitlines()[-1]
    result = json.loads(last[len("rehearsal: "):])
    assert result["correct"] is True, result["checked"]
    assert result["checked"]["leaf_value_gap"]["value"] < 1e-3
    # the cell's own per-layer metrics, and none of the other cell's
    assert "binning_s.malware-81" in result["metrics"]
    assert "binning_s.train" not in result["metrics"]
    assert np.isfinite(result["metrics"]["compile_s.malware-81"]["value"])
