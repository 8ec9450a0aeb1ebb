"""The driver contract for bench.py: whatever happens, stdout's last
line is ONE JSON object with metric/value/unit/vs_baseline keys (the
round-1 failure mode was an unhandled backend crash printing nothing),
and a run that failed — or found a platform nobody named — exits
non-zero after printing it.

Round-5 additions (VERDICT r5 item 4): every row self-describes its
warm-up (iterations, discarded trees, compile counters), a RunManifest
lands next to the artifacts, and two back-to-back small-shape runs must
agree within 5% — the "bench numbers are reproducible" done-condition.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_always_emits_json_line(tmp_path):
    env = dict(os.environ)
    # BENCH_SKIP_REF: the contract under test is "one JSON line, always"
    # — without it, a container that ships /root/reference would
    # cmake-build the reference CLI inside this test and eat the whole
    # tier-1 time budget
    env.update(BENCH_ROWS="20000", BENCH_TREES="2", BENCH_PLATFORM="cpu",
               BENCH_SKIP_REF="1", BENCH_MANIFEST_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT,
    )
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr: {r.stderr[-400:]}"
    out = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline", "platform"):
        assert key in out, out
    assert out["unit"] == "s/tree"
    assert out["value"] > 0, out
    assert out["platform"] == "cpu"
    # the headline must be the reference-parity growth mode on EVERY
    # platform (VERDICT r2: a bench may not advertise the approximate
    # depthwise mode and its ~0.01 AUC gap as the result)
    assert out["growth"] == "leafwise"
    assert out["stop_lag"] == 4  # the row says which stop check ran
    # self-description: warm-up + compile evidence inside the row
    for key in ("warmup_iters", "warm_trees_discarded", "compile_stable",
                "compiles_warmup", "compiles_timed", "timed_trees"):
        assert key in out, out
    assert out["warmup_iters"] >= 2
    assert out["warm_trees_discarded"] >= out["warmup_iters"]
    # ... and a v1 RunManifest next to the artifacts, with git sha,
    # compile counts and phase slot (the acceptance criterion's fields)
    from lightgbm_tpu.obs.manifest import RunManifest

    assert "manifest" in out, out
    mpath = tmp_path / "bench_r20000_t2_l255_b255.manifest.json"
    assert mpath.exists(), list(tmp_path.iterdir())
    man = RunManifest.load(str(mpath))
    assert man.entry == "bench.py"
    assert man.git["sha"], man.git
    assert "backend_compiles" in man.telemetry["counters"]
    assert man.warmup["compiles_warmup"] >= 1
    assert man.per_tree.get("count") == out["timed_trees"]
    assert man.phases == {}  # bench.py takes no trace


def test_bench_refuses_a_platform_nobody_named(tmp_path):
    """The bench measures the chip.  With no TPU and no BENCH_PLATFORM it
    prints its row with the error and the platform it found, exits
    non-zero, and trains nothing."""
    env = dict(os.environ)
    env.pop("BENCH_PLATFORM", None)
    env.update(JAX_PLATFORMS="cpu", BENCH_MANIFEST_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert r.returncode != 0, r.stdout[-400:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["platform"] == "none" and out["value"] == 0.0, out
    assert "BENCH_PLATFORM" in out["error"], out
    assert "binning" not in r.stderr, r.stderr[-400:]


def test_chip_smoke_refuses_without_a_chip():
    """chip_smoke.py with no TPU: non-zero exit, the reason on stderr,
    and no result line — a CPU rehearsal is an explicit argument."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr, r.stderr[-400:]
    assert r.stdout.strip() == "", r.stdout[-400:]


def _inprocess_bench_run(bench):
    """One in-process bench measurement at the contract's small shape
    (module constants are patched by the caller)."""
    X, y = bench.make_data(50_000)
    v, _auc, _vauc, info = bench.ours_sec_per_tree(X, y, "leafwise")
    assert info["compile_stable"], info
    return v


def test_back_to_back_runs_agree_within_5pct(monkeypatch):
    """VERDICT r5 item 4's done-condition.  Runs share the process (and
    so the jit cache + binned dataset), exactly like two consecutive
    timed sections of one driver bench; the warm-up gate in front of
    each timed loop is the thing being validated.  One retry is allowed
    to absorb scheduler noise on the 1-core bench box — the assertion
    is then on the LAST two back-to-back runs."""
    import bench

    # bench.ours_sec_per_tree setdefault-exports LGBM_TPU_STOP_LAG into
    # the process env; route it through monkeypatch so the lagged-stop
    # mode cannot leak into later tests' boosters (they read the env at
    # construction)
    monkeypatch.setenv("LGBM_TPU_STOP_LAG", "4")
    monkeypatch.setattr(bench, "TREES", 8)
    monkeypatch.setattr(bench, "NUM_LEAVES", 63)
    monkeypatch.setattr(bench, "MIN_DATA", 20)
    monkeypatch.setattr(bench, "_DATASET_CACHE", {})
    try:
        a = _inprocess_bench_run(bench)
        b = _inprocess_bench_run(bench)
        rel = abs(b - a) / min(a, b)
        for _ in range(2):  # retries absorb a noisy neighbor, not drift
            if rel <= 0.05:
                break
            a, b = b, _inprocess_bench_run(bench)
            rel = abs(b - a) / min(a, b)
        assert rel <= 0.05, (a, b, rel)
    finally:
        bench._DATASET_CACHE.clear()
