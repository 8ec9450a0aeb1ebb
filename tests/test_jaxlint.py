"""Tier-1 gate for jaxlint stage 1 (AST rules) + the runtime analysis
machinery (recompile counter, donation detection, record-chain audit).

The rule-fires tests pin each rule on a minimal synthetic positive AND
a negative control, so a rule that silently stops matching (or starts
over-matching) fails here before it lets a real regression through.
"""

import os
import textwrap

import numpy as np

from lightgbm_tpu.analysis import (
    AST_RULES,
    lint_paths,
    lint_source,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "lightgbm_tpu")


def _rules(src: str, path: str = "mod.py") -> set:
    return {f.rule for f in lint_source(textwrap.dedent(src), path=path)}


# ------------------------------------------------------------ AST rules

def test_host_sync_in_jit_fires():
    src = """
    import jax
    import numpy as np

    @jax.jit
    def f(x):
        y = np.asarray(x)
        return y, x.item(), x.tolist()
    """
    fs = [f for f in lint_source(textwrap.dedent(src), path="m.py")
          if f.rule == "host-sync-in-jit"]
    assert len(fs) == 3, fs


def test_host_sync_in_jit_negative():
    src = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.asarray(x) + jnp.sum(x)

    def host_fn(x):
        import numpy as np
        return np.asarray(x)  # not traced: no finding
    """
    assert "host-sync-in-jit" not in _rules(src)


def test_python_loop_over_device_array_fires():
    src = """
    import jax

    @jax.jit
    def f(xs):
        t = 0
        for x in xs:
            t = t + x
        return t
    """
    assert "python-loop-over-device-array" in _rules(src)


def test_static_loops_in_jit_are_fine():
    src = """
    import jax

    @jax.jit
    def f(x):
        t = x
        for i in range(4):
            t = t + i
        for cap in sorted((512, 1024), reverse=True):
            t = t + cap
        for name in ("a", "b"):
            t = t * 1
        return t
    """
    assert "python-loop-over-device-array" not in _rules(src)


def test_env_read_at_trace_fires_through_callee():
    # the helper is only reachable FROM the jitted function — the
    # module-local call graph must propagate tracedness to it
    src = """
    import functools
    import os

    import jax

    def helper():
        return int(os.environ.get("KNOB", "2"))

    @functools.partial(jax.jit, static_argnames=())
    def f(x):
        return x * helper()
    """
    assert "env-read-at-trace" in _rules(src)


def test_env_read_outside_trace_is_fine():
    src = """
    import os

    def setup():
        return os.environ.get("KNOB", "2")
    """
    assert "env-read-at-trace" not in _rules(src)


def test_f64_literal_in_traced_fires_and_file_pragma_suppresses():
    src = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x.astype(jnp.float64)
    """
    assert "f64-literal-in-traced" in _rules(src)
    suppressed = (
        "# jaxlint: disable-file=f64-literal-in-traced\n"
        + textwrap.dedent(src)
    )
    assert "f64-literal-in-traced" not in {
        f.rule for f in lint_source(suppressed, path="m.py")}


def test_jit_cache_miss_risk_fires():
    src = """
    import jax

    def step(x):
        return jax.jit(lambda y: y * 2)(x)

    def sweep(xs):
        out = []
        for x in xs:
            out.append(jax.jit(helper)(x))
        return out
    """
    fs = [f for f in lint_source(textwrap.dedent(src), path="m.py")
          if f.rule == "jit-cache-miss-risk"]
    assert len(fs) == 2, fs


def test_host_sync_in_loop_fires_in_hot_module_only():
    src = """
    def drive(metrics, dev):
        out = {}
        for m in metrics:
            out[m.name] = float(m.eval_jax_jit(dev))
        return out
    """
    # hot path: fires
    assert "host-sync-in-loop" in _rules(src, path="lightgbm_tpu/models/gbdt.py")
    # cold module: silent
    assert "host-sync-in-loop" not in _rules(src, path="lightgbm_tpu/cli.py")


def test_host_sync_in_loop_ignores_host_numpy():
    src = """
    import numpy as np

    def rebind(vals, bounds):
        out = []
        for v in vals:
            out.append(int(np.searchsorted(bounds, v)))
        return out
    """
    assert "host-sync-in-loop" not in _rules(
        src, path="lightgbm_tpu/models/gbdt.py")


def test_line_pragma_suppresses():
    src = """
    import numpy as np

    def drain(chunks):
        parts = []
        for c in chunks:
            parts.append(np.asarray(c))  # jaxlint: disable=host-sync-in-loop
        return parts
    """
    assert "host-sync-in-loop" not in _rules(
        src, path="lightgbm_tpu/models/gbdt.py")


def test_wallclock_without_sync_fires():
    # the async-dispatch mis-timing hazard: jnp work between the start
    # mark and the stop timestamp, nothing blocking before the stop
    src = """
    import time
    import jax.numpy as jnp

    def timed_step(x):
        t0 = time.perf_counter()
        y = jnp.dot(x, x)
        return y, time.perf_counter() - t0
    """
    assert "wallclock-without-sync" in _rules(src)


def test_wallclock_with_sync_or_host_only_is_fine():
    src = """
    import time
    import jax
    import jax.numpy as jnp
    import numpy as np

    def timed_synced(x):
        t0 = time.perf_counter()
        y = jnp.dot(x, x)
        jax.block_until_ready(y)
        return y, time.perf_counter() - t0

    def timed_via_asarray(x):
        t0 = time.perf_counter()
        y = jnp.dot(x, x)
        out = np.asarray(y)
        return out, time.perf_counter() - t0

    def host_only(n):
        t0 = time.perf_counter()
        s = sum(range(n))
        return s, time.perf_counter() - t0
    """
    assert "wallclock-without-sync" not in _rules(src)


def test_raw_artifact_write_fires():
    # both shapes: open-for-write and json.dump into an inline open
    src = """
    import json

    def save(path, obj):
        with open(path, "w") as fh:
            json.dump(obj, fh)

    def save_inline(path, obj):
        json.dump(obj, open(path, "w"))

    def save_kw(path, data):
        with open(path, mode="wb") as fh:
            fh.write(data)
    """
    fs = [f for f in lint_source(textwrap.dedent(src), path="m.py")
          if f.rule == "raw-artifact-write"]
    assert {f.line for f in fs} == {5, 9, 12}, fs


def test_raw_artifact_write_negative_controls():
    # reads, appends, non-constant modes, and the atomic helpers are
    # all exempt; a pragma'd implementation site is silent
    src = """
    from lightgbm_tpu.resilience.atomic import atomic_write, atomic_writer

    def ok(path, obj):
        atomic_write(path, obj)
        with atomic_writer(path) as fh:
            fh.write("x")
        with open(path) as fh:          # read
            fh.read()
        with open(path, "a") as fh:     # append-mode log
            fh.write("line")
        with open(path, "r+b") as fh:   # in-place patch
            fh.write(b"x")

    def impl(tmp, mode):
        return open(tmp, mode)          # non-constant mode

    def pragma(tmp):
        return open(tmp, "w")  # jaxlint: disable=raw-artifact-write
    """
    assert "raw-artifact-write" not in _rules(src)


def test_device_buffer_retention_fires():
    # global-name binding of a device value in an event-scope module
    src = """
    import jax.numpy as jnp
    _CACHE = None

    def handle(x):
        global _CACHE
        _CACHE = jnp.zeros((1024, 1024))
        return x
    """
    assert "device-buffer-retention" in _rules(
        src, path="lightgbm_tpu/serving/mod.py")
    # class-attribute binding: a process-lifetime pin shared across
    # instances
    src = """
    import jax.numpy as jnp

    class Engine:
        pass

    def warm(x):
        Engine.scratch = jnp.ones((8, 8))
    """
    assert "device-buffer-retention" in _rules(
        src, path="lightgbm_tpu/obs/mod.py")


def test_device_buffer_retention_negative_controls():
    # instance attributes die with their (registerable) owner — legal
    src = """
    import jax.numpy as jnp

    class Engine:
        def warm(self, x):
            self.scratch = jnp.ones((8, 8))
    """
    assert "device-buffer-retention" not in _rules(
        src, path="lightgbm_tpu/serving/mod.py")
    # host numpy is not a device buffer
    src = """
    import numpy as np
    _CACHE = None

    def handle(x):
        global _CACHE
        _CACHE = np.zeros((8, 8))
    """
    assert "device-buffer-retention" not in _rules(
        src, path="lightgbm_tpu/serving/mod.py")
    # a cached jitted CALLABLE (the engine's dispatch-cache idiom)
    # retains compiled code, not a device buffer
    src = """
    import jax
    _DISPATCH = None

    def dispatch():
        global _DISPATCH
        if _DISPATCH is None:
            _DISPATCH = jax.jit(lambda x: x)
        return _DISPATCH
    """
    assert "device-buffer-retention" not in _rules(
        src, path="lightgbm_tpu/serving/mod.py")
    # outside the hot/serving/obs scope the rule does not apply
    src = """
    import jax.numpy as jnp
    _CACHE = None

    def handle(x):
        global _CACHE
        _CACHE = jnp.zeros((8, 8))
    """
    assert "device-buffer-retention" not in _rules(
        src, path="lightgbm_tpu/io/mod.py")
    # pragma suppression
    src = """
    import jax.numpy as jnp
    _C = None

    def handle(x):
        global _C
        _C = jnp.zeros((8,))  # jaxlint: disable=device-buffer-retention
    """
    assert "device-buffer-retention" not in _rules(
        src, path="lightgbm_tpu/serving/mod.py")


def test_rule_table_complete():
    # every rule the walker can emit is documented (CLI --list-rules)
    assert set(AST_RULES) == {
        "host-sync-in-jit", "python-loop-over-device-array",
        "env-read-at-trace", "f64-literal-in-traced",
        "jit-cache-miss-risk", "host-sync-in-loop",
        "wallclock-without-sync", "raw-artifact-write",
        "unbounded-event-buffer", "device-buffer-retention",
    }


def test_repo_lints_clean():
    """The acceptance gate: jaxlint stage 1 runs clean on the package.
    A new finding means either a real regression (fix it) or an
    intentional, documented exception (pragma it with justification)."""
    findings = lint_paths([PKG])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_concurrency_clean():
    """Stage-3 acceptance gate: the lock-discipline lint runs clean on
    the committed tree.  A new finding is a real concurrency hazard
    (fix it) or a proven-safe pattern (suppress it WITH the protecting
    invariant stated inline — see docs/jaxlint.md)."""
    from lightgbm_tpu.analysis import lint_concurrency_paths

    findings = lint_concurrency_paths([PKG])
    assert findings == [], "\n".join(str(f) for f in findings)


# ------------------------------------------- runtime analysis machinery

def test_recompile_counter_counts_compiles_not_cache_hits():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis import compile_counter

    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8))  # warm
    cc = compile_counter()
    f(jnp.ones(8))
    f(jnp.ones(8))
    assert cc.delta() == 0
    f(jnp.ones(16))  # new shape -> retrace + compile
    assert cc.delta() >= 1


def test_grow_loop_recompile_flat():
    """The recompile-in-steady-loop gate on the REAL grow loop: after
    the first iteration compiles everything, further same-shape
    boosting iterations must add zero backend compiles."""
    from lightgbm_tpu.analysis.hlo_audit import steady_loop_recompiles
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(0)
    X = rng.randn(256, 4).astype(np.float32)
    y = (X[:, 0] + rng.randn(256) * 0.1 > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=4, max_bin=16,
                 min_data_in_leaf=5)
    ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
    booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))

    def step():
        booster.train_one_iter()
        np.asarray(booster._scores[0, :1])  # force completion

    n = steady_loop_recompiles(step, iters=3)
    assert n == 0, f"{n} backend compiles inside a warm grow loop"


def test_donation_drop_is_detected():
    """Deliberately break donation (wrap the donating placement kernel
    in an outer non-donating jit — nesting drops the inner donation)
    and assert the audit flags it."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.hlo_audit import (
        _compile_entry, check_budgets)
    from lightgbm_tpu.ops import record as rec_mod

    T = rec_mod.TILE
    W = rec_mod.rec_height(4, 4)
    rec = jnp.zeros((W, 2 * T), jnp.int32)
    comp = jnp.zeros((1, W, 2 * T), jnp.int32)
    counts = (jnp.full(1, T // 2, jnp.int32),) * 2  # a tile's run lengths

    def call_place(rec_):
        return rec_mod.place_runs(
            rec_, comp, counts, jnp.int32(0), jnp.int32(T), jnp.int32(T // 2),
            jnp.bool_(True), jnp.int32(0), jnp.int32(1),
            cap=T, leaf_row=rec_mod.num_words(4, 4) + 4, interpret=True)

    # donating entry point: aliasing present
    ops, has_alias, warn, mem = _compile_entry(
        rec_mod.place_runs.lower(
            rec, comp, counts, jnp.int32(0), jnp.int32(T), jnp.int32(T // 2),
            jnp.bool_(True), jnp.int32(0), jnp.int32(1),
            cap=T, leaf_row=rec_mod.num_words(4, 4) + 4, interpret=True))
    assert has_alias and not warn
    # the same compile exposes the static memory_analysis numbers the
    # mem_* budgets gate (ISSUE 16)
    assert mem.get("output_bytes", 0) > 0, mem

    # donation dropped: no aliasing in the compiled module
    undonated = jax.jit(call_place)
    _ops, has_alias_bad, warn_bad, _mem = _compile_entry(
        undonated.lower(rec))
    measured = {"place_runs": {
        "ops": _ops, "donation": has_alias_bad and not warn_bad,
        "donation_warnings": warn_bad, "has_alias": has_alias_bad}}
    budgets = {"entries": {"place_runs": {"donation": True}}}
    findings = check_budgets(measured, budgets)
    assert [f.rule for f in findings] == ["hlo-donation-dropped"], (
        has_alias_bad, findings)


def test_record_multi_use_is_detected():
    """A second read of the donated record around the aliased placement
    (the exact round-5 full-record-copy trigger) must be flagged."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.hlo_audit import (
        _jaxpr_use_count, check_budgets)
    from lightgbm_tpu.ops import record as rec_mod

    T = rec_mod.TILE
    W = rec_mod.rec_height(4, 4)
    rec = jnp.zeros((W, 2 * T), jnp.int32)
    comp = jnp.zeros((1, W, 2 * T), jnp.int32)
    counts = (jnp.full(1, T // 2, jnp.int32),) * 2  # a tile's run lengths
    kw = dict(cap=T, leaf_row=rec_mod.num_words(4, 4) + 4, interpret=False)
    args = (comp, counts, jnp.int32(0), jnp.int32(T), jnp.int32(T // 2),
            jnp.bool_(True), jnp.int32(0), jnp.int32(1))

    def good(rec_):
        return rec_mod.place_runs(rec_, *args, **kw)

    def bad(rec_):
        out = rec_mod.place_runs(rec_, *args, **kw)
        return out, rec_.sum()  # second mention of the donated record

    assert _jaxpr_use_count(jax.make_jaxpr(good)(rec), 0) == 1
    uses = _jaxpr_use_count(jax.make_jaxpr(bad)(rec), 0)
    assert uses > 1
    measured = {"split_step_record_chain": {
        "ops": {}, "donation": None, "donation_warnings": [],
        "record_uses": uses, "record_single_use": False}}
    budgets = {"entries": {"split_step_record_chain": {
        "record_single_use": True}}}
    findings = check_budgets(measured, budgets)
    assert [f.rule for f in findings] == ["record-chain-multi-use"]


# ------------------------------------------------------------ CLI wrapper

def test_cli_emits_copycheck_schema(tmp_path):
    """tools/jaxlint.py is the standalone entry: exit 0 on the clean
    repo (AST stage) and a COPYCHECK.json in the established schema."""
    import json
    import subprocess
    import sys

    out_json = tmp_path / "COPYCHECK.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "jaxlint.py"),
         "--ast-only", "--json", str(out_json)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(out_json.read_text())
    for key in ("threshold", "flagged", "error"):
        assert key in data, data
    assert data["flagged"] == []
    assert data["error"] == ""


def test_cli_concurrency_only_clean_and_rule_table():
    """--concurrency-only runs just stage 3 (exit 0 on the clean tree)
    and --list-rules includes the stage-3 rule table."""
    import subprocess
    import sys

    cli = os.path.join(ROOT, "tools", "jaxlint.py")
    r = subprocess.run(
        [sys.executable, cli, "--concurrency-only"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr

    r = subprocess.run(
        [sys.executable, cli, "--list-rules"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    for rule in ("shared-state-unlocked", "lock-order-cycle",
                 "device-sync-under-lock", "signal-unsafe-lock"):
        assert rule in r.stdout, rule
