"""The comparison that decides ``correct`` has to fail what it should.

At the rehearsal size on the CPU.  The CPU path's float32 histograms are a
different program from the chip's and read about 1e-2 against the
reference, so these tests give the program ``hist_dtype=float64``: a sound
run then reads 1e-7 and stands well under limits that were set from the
chip's readings.

* the control: the reference in the program's place, in bfloat16, comes
  out not correct, and so does each fault planted in it;
* the same faults planted underneath a whole run (``run.execute`` past the
  look for a chip) come out with ``correct`` false: a step that leaves its
  state unchanged, half of the rows left out, an answer altered where it
  is produced (one leaf's value with the wrong sign, in the tree and in
  the scores).  No exchange between chips exists in a one-chip cell.
"""

import numpy as np
import pytest

import cells
import check
import program
import run as entry
from drivers import train_steady

CELLS = ("synthetic-100.train", "malware-81.train")


def make_run(workload, monkeypatch, seed=2_147_483_659):
    cell = cells.assemble(
        {"name": workload, "config": workload.rsplit(".", 1)[0],
         "traffic": "train_steady", "chips": 1}, cells.benchmark())
    cell["config"]["params"]["hist_dtype"] = "float64"
    monkeypatch.setattr(cells, "cell", lambda name: cell)
    args = entry.parse(["--workload", workload, "--seed", str(seed),
                        "--seconds", "0.5", "--rehearsal"])
    return args, entry.Run(args, cell), cell


@pytest.fixture(scope="module")
def first_trees():
    """The program's first trees at the rehearsal size, once for a cell."""
    made = {}

    def get(workload):
        if workload not in made:
            mp = pytest.MonkeyPatch()
            _, run, cell = make_run(workload, mp)
            mp.undo()
            run.traffic = {**run.traffic, "min_warmup_trees": 3,
                           "quiet_trees": 0}
            s = train_steady.setup(run)
            state = train_steady.first_trees(run, s)
            made[workload] = (run, state, train_steady.reference(run, state),
                              check.limits_of(cell["config"]["name"]))
        return made[workload]

    return get


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_program_is_correct(workload, first_trees):
    run, state, ref, limits = first_trees(workload)
    ok, table = check.verdict(train_steady.compared(run, state, ref), limits)
    assert ok, table


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("precision,fault", [
    ("bfloat16", None), ("float32", "half_batch"),
    ("float32", "state_unchanged"), ("float32", "altered_split"),
    ("float32", "altered_leaf")])
def test_control_and_faults_are_not_correct(workload, precision, fault,
                                            first_trees):
    run, state, ref, limits = first_trees(workload)
    numbers = train_steady.compared(run, state, ref, precision, fault)
    ok, table = check.verdict(numbers, limits)
    assert not ok, table


def leave_state_unchanged(monkeypatch):
    from lightgbm_tpu.models import gbdt

    real = gbdt._post_grow_step

    def step(tree, scores, *rest):
        kept = scores + 0
        tree, _ = real(tree, scores, *rest)
        return tree, kept

    monkeypatch.setattr(gbdt, "_post_grow_step", step)


def leave_half_out(monkeypatch):
    real = program.build_booster

    def build(config, ds):
        booster = real(config, ds)
        g = booster._gbdt
        mask = np.zeros(g.num_data, np.float32)
        mask[::2] = 1
        g._bag_mask = g._by_row(mask)
        return booster

    monkeypatch.setattr(program, "build_booster", build)


def alter_an_answer(monkeypatch):
    from lightgbm_tpu.models import gbdt

    real = gbdt._post_grow_step

    def step(tree, *rest):
        tree, scores = real(tree, *rest)
        wrong = tree.leaf_value.at[3].multiply(-1.0)
        return tree._replace(leaf_value=wrong), scores + (
            wrong - tree.leaf_value)[rest[2]][None, :]

    monkeypatch.setattr(gbdt, "_post_grow_step", step)


@pytest.mark.parametrize("break_it", [
    None, leave_state_unchanged, leave_half_out, alter_an_answer])
def test_a_run_over_a_broken_path_is_not_correct(break_it, monkeypatch):
    args, _, _ = make_run("synthetic-100.train", monkeypatch)
    if break_it is not None:
        break_it(monkeypatch)
    result = entry.execute(args)
    assert result["correct"] is (break_it is None), result["checked"]
    assert list(result)[-1] == "checked"
