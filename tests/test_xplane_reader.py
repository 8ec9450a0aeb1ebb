"""obs/device_time.py held against the trace recorded on the chip.

``benchmarks/testdata/small.xplane.pb.gz`` is the profiler's own file of
an ``istella-220`` rehearsal on one TPU v5 lite (60,000 rows, 31 leaves,
two traced trees; PR 25).  The harness's reader
(``benchmarks/xplane.py`` over ``jax.profiler.ProfileData``) and the
program's (the wire format, here) must read the same numbers from it:
the expected values are those of ``benchmarks/tests/test_recorded_trace``.
"""

import json
import os
import sys

import pytest

from lightgbm_tpu.obs import device_time as dt
from lightgbm_tpu.obs import telemetry

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "testdata",
    "small.xplane.pb.gz")
CHIP = "/device:TPU:0"
TREES = 2


@pytest.fixture(scope="module")
def trace():
    return dt.read(RECORDED)


@pytest.fixture(scope="module")
def rows(trace):
    return dt.attribute(trace, window="bench.window")


@pytest.fixture(scope="module")
def grow(trace):
    return next(p for p in trace["programs"].values()
                if p.name == "jit_grow_tree")


@pytest.mark.parametrize("what,expected", [
    ("device planes", [CHIP]),
    ("op events", 7482),
    ("module runs", 60),
    ("programs with an HloProto", 13),
    ("instructions of jit_grow_tree in the HloProto", 5327),
    ("harness spans on the host plane", [
        "bench.window", "Booster.update", "Booster.update", "final sync"]),
    ("instructions with a tf_op among jit_grow_tree's events", 426),
])
def test_read(trace, grow, what, expected):
    chip = trace["devices"][CHIP]
    got = {
        "device planes": lambda: list(trace["devices"]),
        "op events": lambda: len(chip["ops"]),
        "module runs": lambda: len(chip["modules"]),
        "programs with an HloProto": lambda: len(trace["programs"]),
        "instructions of jit_grow_tree in the HloProto":
            lambda: len(grow.instrs),
        "harness spans on the host plane": lambda: [
            h[2] for h in trace["host"]
            if h[2] in ("bench.window", "Booster.update", "final sync")],
        "instructions with a tf_op among jit_grow_tree's events":
            lambda: len({op.name for _, _, op in chip["ops"] if op.tf_op
                         and trace["programs"].get(op.program_id) is grow}),
    }[what]()
    assert got == expected


def test_an_instruction_is_read_from_text_and_metadata(trace):
    op = next(op for _, _, op in trace["devices"][CHIP]["ops"]
              if op.name == "%copy.618")
    assert op.opcode == "copy" and op.operands == ("%lgbm.partition.20",)
    assert op.shape == "s32[64,120832]{1,0:T(8,128)}"
    assert op.operand_shape == "s32[64,120832]{1,0:T(8,128)S(1)}"
    assert op.bytes == 61865984 and op.tf_op == ""
    placed = next(op for _, _, op in trace["devices"][CHIP]["ops"]
                  if op.name == "%reshape.2093")
    assert placed.tf_op.endswith(
        "/jit(place_runs)/lgbm.partition/reshape:")
    assert placed.source == "/root/repo/lightgbm_tpu/ops/record.py:893"


def test_a_ref_value_statistic_resolves():
    """``XStat{metadata_id: 26, ref_value: 99}``: the value is the string
    that ``stat_metadata[99]`` holds once."""
    buf = bytes([0x08, 26, 0x38, 99])
    names = {26: "tf_op", 99: "jit(grow_tree)/while:"}
    assert dt._stat(buf, (0, len(buf)), names) == (
        "tf_op", "jit(grow_tree)/while:")


@pytest.mark.parametrize("named,ms_per_tree", [
    ("lgbm.split_step", 12.592357),
    ("lgbm.histogram", 7.824819),
    ("lgbm.partition", 0.5846125),
    (None, 7.6767025),  # all else inside jit_grow_tree: the harness's glue
])
def test_attribute_reproduces_the_harness(rows, named, ms_per_tree):
    """By instruction name, as the layer files match: the two readers
    cannot drift apart unseen."""
    grown = [r for r in rows if r.program == "jit_grow_tree"]
    found = [r for r in grown if (
        named in r.instruction if named else "lgbm." not in r.instruction)]
    assert dt.busy_ns(found) / 1e6 / TREES == pytest.approx(
        ms_per_tree, rel=1e-6)


def test_every_row_has_a_scope_and_rows_sum_to_busy_time(trace, rows):
    assert len(rows) == 7234  # the harness's leaf ops
    for r in rows:
        assert r.scope == dt.UNATTRIBUTED or r.scope in dt.SCOPE_NAMES, r
        assert r.cause == "program" or " > " in r.cause, r
    by_program = {}
    for r in rows:
        by_program.setdefault(r.program, []).append(r)
    for name, mine in by_program.items():
        assert sum(r.end_ns - r.start_ns for r in mine) == pytest.approx(
            dt.busy_ns(mine), rel=1e-12), name
    rep = dt.report(trace, "jit_grow_tree", "bench.window")
    p = rep["programs"]["jit_grow_tree"]
    assert p["runs"] == TREES
    assert p["ms_per_run"] == pytest.approx(
        12.592357 + 7.824819 + 0.5846125 + 7.6767025, rel=1e-6)
    assert sum(ms for _, _, ms, _ in p["scope_cause"]) == pytest.approx(
        p["ms_per_run"], rel=1e-9)
    unattributed = sum(ms for s, _, ms, _ in p["scope_cause"]
                       if s == dt.UNATTRIBUTED)
    assert 1 - unattributed / p["ms_per_run"] == pytest.approx(
        p["attributed_share"])
    # the recorded program has no scope on the grower's own body yet
    assert 0.70 < p["attributed_share"] < 0.85


@pytest.mark.parametrize("name,cause,producer,consumer,chain", [
    ("%copy.618", "kernel > branch_result layout", "lgbm.partition.20",
     "cond.100", "cond.100[1] < cond.98[0] < cond.85[0] < cond.61[0] "
     "< cond.109[0] < while.57"),
    ("%copy.630", "kernel > branch_result layout", "lgbm.partition.19",
     "cond.98",
     "cond.98[1] < cond.85[0] < cond.61[0] < cond.109[0] < while.57"),
    ("%copy.612", "cond > branch_result", "cond.102", "cond.100",
     "cond.100[0] < cond.98[0] < cond.85[0] < cond.61[0] < cond.109[0] "
     "< while.57"),
    ("%copy.698", "cond > carry layout", "cond.109", "while.57", "while.57"),
    ("%copy-done.1", "carry > cond_arg", "while.57", "cond.109", "while.57"),
])
def test_cause_of_the_copies(trace, grow, name, cause, producer, consumer,
                             chain):
    op = next(op for _, _, op in trace["devices"][CHIP]["ops"]
              if op.name == name)
    iid = grow.of(name)
    got, src, dst = dt.cause_of(grow, iid, op)
    assert got == cause
    assert grow.instrs[src].name == producer
    assert grow.instrs[dst].name == consumer
    assert dt._chain(grow, iid) == chain


def test_copy_ledger_lists_what_the_program_did_not_write(trace, rows):
    ledger = dt.copy_ledger(trace, rows, "jit_grow_tree")
    assert [c["instruction"] for c in ledger[:2]] == [
        "%copy.698", "%copy-done.2"]
    top = ledger[0]
    assert top["cause"] == "cond > carry layout" and top["count"] == 60
    assert top["bytes"] == 61865984 and top["chain"] == "while.57"
    assert all(c["share"] >= 0.01 and c["cause"] != "program"
               for c in ledger)


def test_scope_of_takes_the_innermost_component_and_its_tail():
    path = ("jit(grow_tree)/lgbm.grow.loop/while/body/lgbm.grow.book/"
            "lgbm.grow.tier.split/cond/branch_1_fun/jit(split_step_window)/"
            "lgbm.split_step/lgbm.split_step.cap4096/pallas_call:")
    assert dt.scope_of(path) == ("lgbm.split_step", "cap4096")
    assert dt.scope_of(path.split("/jit(split_step")[0]) == (
        "lgbm.grow.tier", "split")
    assert dt.scope_of("jit(grow_tree)/while:") is None
    assert dt.scope_of("jit(f)/lgbm.not_in_the_table/add") == (
        "lgbm.not_in_the_table", "")


def test_idle_gaps_go_to_the_innermost_lgbm_host_span():
    ops = [(0.0, 10.0, None), (30.0, 40.0, None), (70.0, 80.0, None)]
    host = sorted([(5.0, 60.0, "lgbm.host.grow"),
                   (12.0, 20.0, "lgbm.host.stop_check"),
                   (0.0, 100.0, "Booster.update")])
    got = dt.idle_by_host_span(
        {"devices": {CHIP: {"ops": ops}}, "host": host}, 0.0, 100.0)
    assert got == {"lgbm.host.stop_check": 8.0, "lgbm.host.grow": 32.0,
                   "(no span)": 30.0}


def test_command_prints_json(capsys, trace, monkeypatch):
    monkeypatch.setattr(dt, "read", lambda path: trace)
    assert dt.main([RECORDED, "--program", "jit_grow_tree",
                    "--window", "bench.window", "--top", "3",
                    "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert list(rep["programs"]) == ["jit_grow_tree"]
    assert len(rep["programs"]["jit_grow_tree"]["scope_cause"]) == 3
    assert rep["idle_ns"] == {"(no span)": 17402024.0}
    dt.main([RECORDED, "--window", "bench.window", "--top", "2"])
    out = capsys.readouterr().out
    assert "== jit_grow_tree: 28.678 ms/run over 2 run(s)" in out
    assert "-- copy ledger of jit_grow_tree" in out


def test_seconds_by_scope_feeds_the_manifest(trace, monkeypatch):
    monkeypatch.setattr(dt, "read", lambda path: trace)
    phases = dt.seconds_by_scope(RECORDED)
    assert set(phases) <= set(dt.SCOPE_NAMES) | {dt.UNATTRIBUTED}
    assert phases["lgbm.split_step"] > phases["lgbm.histogram"] > 0
    assert dt.newest_xplane(os.path.dirname(RECORDED)) is None


# ------------------------------------------------- spans on the host plane

class _Recorder:
    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.entered.append(self.name)

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("mode,opened", [
    ("on", ["lgbm.host.grow"]),
    ("off", []),
    ("on, jax not loaded", []),
])
def test_span_opens_an_annotation_only_when_on(monkeypatch, mode, opened):
    """Telemetry off: no annotation and no look at jax.  On in a process
    that never loaded jax: a timer alone, and jax stays unloaded."""
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "entered", [])
    tel = telemetry.Telemetry(enabled=mode != "off")
    if mode.endswith("not loaded"):
        monkeypatch.delitem(sys.modules, "jax")
    with tel.span("lgbm.host.grow"):
        pass
    assert _Recorder.entered == opened
    assert ("jax" in sys.modules) == (not mode.endswith("not loaded"))
    assert (tel.span_stat("lgbm.host.grow") is not None) == (mode != "off")
