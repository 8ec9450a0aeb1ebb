"""jaxlint stage 2: compiled-artifact audit of the hot entry points.

Traces the serial grow loop, the fused split step (interpret mode on
CPU — the interpreter lowers the Pallas grid to real XLA HLO, so the
SURROUNDING program structure the budgets guard is the real thing),
the aliased placement kernel, and the matmul predictor, then checks:

* **hlo-op-budget** — compiled-HLO op counts (``copy``, ``transpose``,
  ``convert``, ``gather``, ``dynamic-update-slice``) against the
  committed budgets in ``analysis/budgets.json``.  The round-5 failure
  class — XLA copy-insertion cloning the full record/histogram buffer
  once per split inside the grow while-body — shows up as a step
  change in the ``copy`` count of these small-shape programs.
* **hlo-donation-dropped** — every donated entry point must compile
  with ``input_output_alias`` in the HLO module header and without a
  "donated buffers were not usable" warning.
* **record-chain-multi-use** — in the jaxprs of the hardware-config
  split step and placement, the donated record argument must be
  consumed by EXACTLY ONE equation: a second mention (a window slice,
  a go vector, a sibling view) is what forced copy-insertion to clone
  the record every split (~1 s/tree at 10M rows, round-5 measurement).
* **recompile-in-steady-loop** — re-running an already-warm callable
  over the same shapes must add zero backend compiles
  (``steady_loop_recompiles``; the tier-1 test drives the real grow
  loop through it).
* **hlo-memory-budget** — ``compiled.memory_analysis()`` bytes
  (temp/argument/output) against ``mem_*`` ceilings in the same
  budgets file: the static half of the memory-observability layer
  (obs/memory.py is the runtime half) — an XLA temp allocation that
  balloons at the pinned shape fails tier-1 before any chip time is
  spent.

Budgets are CPU-backend numbers at pinned small shapes; see
docs/jaxlint.md for the update workflow (never raise a budget to make
a red gate green without a bench row justifying the new count).
"""

from __future__ import annotations

import collections
import functools
import json
import os
import re
import warnings
from typing import Dict, List, Optional

from .ast_rules import Finding

ARTIFACT_RULES: Dict[str, str] = {
    "hlo-op-budget": (
        "compiled-HLO op count (copy/transpose/convert/gather/...) "
        "exceeds the committed budget in analysis/budgets.json"
    ),
    "hlo-donation-dropped": (
        "a donated entry point compiled without input_output_alias, or "
        "XLA warned that donated buffers were unusable"
    ),
    "record-chain-multi-use": (
        "the donated record argument is consumed by more than one "
        "jaxpr equation — copy-insertion will clone the full record "
        "per split (the round-5 ~1 s/tree regression class)"
    ),
    "recompile-in-steady-loop": (
        "an iteration of an already-warm loop triggered a backend "
        "compile — lazy recompiles pollute any timed loop"
    ),
    "hlo-memory-budget": (
        "compiled.memory_analysis() bytes (temp/argument/output) exceed "
        "the committed memory budget in analysis/budgets.json — a "
        "kernel change ballooned XLA's allocation at the pinned shape"
    ),
}

_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?\s([\w\-]+)\(")
_ALIAS = re.compile(r"input_output_alias=\{\s*([^}]*\S)[^}]*\}")
_DONATION_WARNING = re.compile(r"donated", re.IGNORECASE)

# shapes for the audited programs: small enough to compile in seconds
# on CPU, big enough to exercise the multi-tier cond structure where
# the copy regressions live (n=2048 gives three hist/partition tiers)
_N, _F, _B, _L = 2048, 4, 16, 8


def budgets_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "budgets.json")


def load_budgets(path: Optional[str] = None) -> dict:
    with open(path or budgets_path(), encoding="utf-8") as fh:
        return json.load(fh)


def hlo_op_counts(hlo_text: str) -> Dict[str, int]:
    """Instruction-opcode histogram of an HLO module text."""
    counts: collections.Counter = collections.Counter()
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            counts[m.group(1)] += 1
    return dict(counts)


def _memory_analysis(compiled) -> dict:
    """``compiled.memory_analysis()`` normalized to plain ints (the
    static half of obs/memory.py's accounting).  {} when the backend
    does not expose it — the budget gate then treats the entry as
    unmeasurable rather than zero."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for key in ("temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes"):
        val = getattr(ma, key, None)
        if val is not None:
            out[key.replace("_size_in_bytes", "_bytes")] = int(val)
    return out


def _compile_entry(lowered):
    """Compile a lowered computation, capturing donation warnings.
    Returns (op_counts, has_alias, warning_strings, memory_bytes)."""
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        compiled = lowered.compile()
    txt = compiled.as_text()
    has_alias = _ALIAS.search(txt) is not None
    donation_warnings = [
        str(w.message) for w in wlog
        if _DONATION_WARNING.search(str(w.message))
    ]
    return (hlo_op_counts(txt), has_alias, donation_warnings,
            _memory_analysis(compiled))


def _jaxpr_use_count(closed_jaxpr, invar_index: int) -> int:
    """How many equations consume the given top-level input variable."""
    var = closed_jaxpr.jaxpr.invars[invar_index]
    uses = 0
    for eqn in closed_jaxpr.jaxpr.eqns:
        if any(v is var for v in eqn.invars):
            uses += 1
    if any(v is var for v in closed_jaxpr.jaxpr.outvars):
        uses += 1
    return uses


# ------------------------------------------------------------ entry points

def _grow_inputs():
    import jax.numpy as jnp
    import numpy as np

    from ..learners.serial import TreeLearnerParams

    rng = np.random.RandomState(0)
    bins_T = jnp.asarray(
        rng.randint(0, _B, size=(_F, _N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(_N).astype(np.float32))
    hess = jnp.ones(_N, jnp.float32)
    bag = jnp.ones(_N, jnp.float32)
    fmask = jnp.ones(_F, bool)
    nbpf = jnp.full(_F, _B, jnp.int32)
    iscat = jnp.zeros(_F, bool)
    params = TreeLearnerParams(
        min_data_in_leaf=jnp.float32(1.0),
        min_sum_hessian_in_leaf=jnp.float32(1e-3),
        lambda_l1=jnp.float32(0.0),
        lambda_l2=jnp.float32(0.0),
        min_gain_to_split=jnp.float32(0.0),
        max_depth=jnp.int32(0),
    )
    return bins_T, grad, hess, bag, fmask, nbpf, iscat, params


def _measure_grow_tree_serial() -> dict:
    """The CPU serial grow loop (order-based partition, segment hists):
    the path every tier-1 test and the CPU bench fallback run."""
    from ..learners.serial import grow_tree

    args = _grow_inputs()
    lowered = grow_tree.lower(*args, num_bins=_B, max_leaves=_L)
    ops, has_alias, dwarn, mem = _compile_entry(lowered)
    return {"ops": ops, "donation": None, "donation_warnings": dwarn,
            "has_alias": has_alias, "memory": mem}


_FOREST_LANES = 4


def _measure_grow_forest_batched() -> dict:
    """The forest-batched grower (learners/forest.py, explicit batched
    loop): one traced program advancing _FOREST_LANES independent trees
    — the multiclass / cv-fold / train_many dispatch.  Audited at the
    same (n, F, bins, leaves) pin as grow_tree_serial so the two
    entries' op counts stay comparable lane-for-lane."""
    import jax.numpy as jnp

    from ..learners.forest import make_grow_forest, stack_learner_params

    bins_T, grad, hess, bag, fmask, nbpf, iscat, params = _grow_inputs()
    B = _FOREST_LANES
    gf = make_grow_forest(_B, _L, "batched")
    lowered = gf.lower(
        bins_T,
        jnp.broadcast_to(grad, (B, _N)),
        jnp.broadcast_to(hess, (B, _N)),
        jnp.broadcast_to(bag, (B, _N)),
        jnp.broadcast_to(fmask, (B, _F)),
        nbpf, iscat,
        stack_learner_params([params] * B))
    ops, has_alias, dwarn, mem = _compile_entry(lowered)
    return {"ops": ops, "donation": None, "donation_warnings": dwarn,
            "has_alias": has_alias, "memory": mem}


def _split_step_inputs(tiles=1, tail=0, blocks_of=1):
    """A leaf of ``tiles`` tiles less ``tail`` rows at the record's
    origin, in a window and a record of whole blocks of ``blocks_of``
    tiles (the audit's: one tile, a one-tile window, ``n_pad = 2 *
    TILE``, which it launches at one tile a grid step)."""
    import jax.numpy as jnp
    import numpy as np

    from ..ops import record as rec_mod
    from ..ops.pallas_search import _pack_meta, _pack_scal

    T = rec_mod.TILE
    n = tiles * T - tail
    cap = rec_mod.round_up(tiles, blocks_of) * T
    k = rec_mod.bins_per_word(jnp.uint8)
    rng = np.random.RandomState(0)
    bins_T = jnp.asarray(rng.randint(0, _B, size=(_F, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    rec = rec_mod.build_record(
        bins_T, grad, jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
        cap + blocks_of * T)
    Fp = rec_mod.round_up(_F, 8)
    Bp = rec_mod.round_up(_B, 128)
    hists = jnp.zeros((2, Fp, 4, Bp), jnp.float32)
    scal_f = _pack_scal(
        jnp.float32(1.0), jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(n), jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(n), jnp.float32(1.0), jnp.float32(1e-3),
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
    meta = _pack_meta(jnp.ones(_F, bool), jnp.full(_F, _B, jnp.int32),
                      jnp.zeros(_F, bool), Fp)
    scalars = dict(
        begin=jnp.int32(0), pcnt=jnp.int32(n),
        do_split=jnp.bool_(True), f=jnp.int32(1), thr=jnp.int32(3),
        is_cat=jnp.bool_(False), parent_slot=jnp.int32(0),
        new_slot=jnp.int32(1),
        # the grower's form of the two launches: the tile count is an
        # operand (here the leaf's tiles), the grid a run-time bound
        live_tiles=jnp.int32(tiles))
    return rec, hists, scal_f, meta, scalars, cap, k


def _measure_split_step_window() -> dict:
    """The fused grower's launch pair (split step, then placement),
    interpret mode: donation of the hists buffer plus the op budget of
    the surrounding XLA program."""
    import jax

    from ..ops.record import num_words, place_runs, split_step_window

    rec, hists, scal_f, meta, s, cap, k = _split_step_inputs()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def split(hists_, rec_):
        hists2, comp, nleft, res, cl, cr, rec_pass = split_step_window(
            hists_, rec_, s["begin"], s["pcnt"], s["do_split"], s["f"],
            s["thr"], s["is_cat"], s["parent_slot"], s["new_slot"],
            scal_f, meta, F=_F, cap=cap, k=k, interpret=True,
            live_tiles=s["live_tiles"], tiles_per_step=1)
        rec2 = place_runs(
            rec_pass, comp, (cl, cr), s["begin"], s["pcnt"], nleft,
            s["do_split"], s["parent_slot"], s["new_slot"], cap=cap,
            leaf_row=num_words(_F, k) + 4, interpret=True,
            live_tiles=s["live_tiles"])
        return hists2, rec2, nleft, res

    ops, has_alias, dwarn, mem = _compile_entry(split.lower(hists, rec))
    return {"ops": ops, "donation": has_alias and not dwarn,
            "donation_warnings": dwarn, "has_alias": has_alias,
            "memory": mem}


def _measure_split_step_record_chain() -> dict:
    """Jaxpr of the HARDWARE config (direct_read aliased path): the
    donated record must be consumed by exactly one equation."""
    import jax

    from ..ops.record import split_step_window

    rec, hists, scal_f, meta, s, cap, k = _split_step_inputs()

    def run(rec_, hists_):
        return split_step_window(
            hists_, rec_, s["begin"], s["pcnt"], s["do_split"], s["f"],
            s["thr"], s["is_cat"], s["parent_slot"], s["new_slot"],
            scal_f, meta, F=_F, cap=cap, k=k,
            interpret=False, live_tiles=s["live_tiles"], tiles_per_step=1)

    jaxpr = jax.make_jaxpr(run)(rec, hists)
    uses = _jaxpr_use_count(jaxpr, 0)
    return {"ops": {}, "donation": None, "donation_warnings": [],
            "record_uses": uses, "record_single_use": uses == 1}


def _measure_place_runs() -> dict:
    """The aliased placement: donation of the record (compiled,
    interpret fallback) AND single-mention in the hardware jaxpr."""
    import jax
    import jax.numpy as jnp

    from ..ops import record as rec_mod

    T = rec_mod.TILE
    rec, _hists, _scal_f, _meta, s, cap, k = _split_step_inputs()
    nt = cap // T
    W = rec.shape[0]
    comp = jnp.zeros((nt, W, 2 * T), jnp.int32)
    counts = (jnp.full(nt, T // 2, jnp.int32),) * 2
    args = (comp, counts, s["begin"], s["pcnt"], jnp.int32(cap // 2),
            s["do_split"], s["parent_slot"], s["new_slot"])
    kw = dict(cap=cap, leaf_row=rec_mod.num_words(_F, k) + 4,
              live_tiles=s["live_tiles"])

    lowered = rec_mod.place_runs.lower(rec, *args, interpret=True, **kw)
    ops, has_alias, dwarn, mem = _compile_entry(lowered)

    def run_hw(rec_):
        return rec_mod.place_runs(rec_, *args, interpret=False, **kw)

    jaxpr = jax.make_jaxpr(run_hw)(rec)
    uses = _jaxpr_use_count(jaxpr, 0)
    return {"ops": ops, "donation": has_alias and not dwarn,
            "donation_warnings": dwarn, "has_alias": has_alias,
            "record_uses": uses, "record_single_use": uses == 1,
            "memory": mem}


def _measure_partition_window() -> dict:
    """The standalone partition compaction kernel and its placement
    (the record-mode hooks path): a rework that reintroduces layout
    churn around the compaction shows up here before any bench run."""
    import jax.numpy as jnp

    from ..ops import record as rec_mod

    rec, _hists, _scal_f, _meta, s, cap, k = _split_step_inputs()
    go = jnp.zeros(cap, jnp.int32)
    lowered = rec_mod.partition_window.lower(
        rec, go, s["begin"], s["pcnt"], s["do_split"], cap,
        jnp.int32(0), jnp.int32(1),
        leaf_row=rec_mod.num_words(_F, k) + 4, interpret=True)
    ops, has_alias, dwarn, mem = _compile_entry(lowered)
    return {"ops": ops, "donation": None, "donation_warnings": dwarn,
            "has_alias": has_alias, "memory": mem}


def _measure_predict_matmul() -> dict:
    """The matmul predictor: 'zero indexed access' is a budget —
    gather must stay 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.tree import empty_tree, stack_trees
    from ..ops.predict_matmul import build_path_tables, ensemble_sum_matmul

    trees = [empty_tree(_L) for _ in range(2)]
    stacked = stack_trees(trees)
    stacked = jax.tree.map(
        lambda a: a.reshape((2, 1) + a.shape[1:]), stacked)
    tables = build_path_tables(stacked)
    X = jnp.asarray(np.random.RandomState(0)
                    .randn(64, _F).astype(np.float32))
    lowered = ensemble_sum_matmul.lower(tables, stacked, X)
    ops, has_alias, dwarn, mem = _compile_entry(lowered)
    ops.setdefault("gather", 0)
    return {"ops": ops, "donation": None, "donation_warnings": dwarn,
            "has_alias": has_alias, "memory": mem}


def _measure_post_grow_step() -> dict:
    """The per-tree score update: scores donation must hold (a dropped
    donation doubles score-buffer traffic every tree), and the update
    reads each row's leaf value with no gather of the leaf table
    (models/tree.py ``leaf_lookup``: an element gather costs about 8 ns
    a row on a TPU); the gathers left are the threshold finalization's
    reads of the bounds, and the budget's ceiling is their count."""
    import jax.numpy as jnp

    from ..models.gbdt import _post_grow_step
    from ..models.tree import empty_tree, pack_threshold_bounds

    tree = empty_tree(_L)
    scores = jnp.zeros((1, _N), jnp.float32)
    leaf_id = jnp.zeros(_N, jnp.int32)
    bounds_mat, real_feat = pack_threshold_bounds(
        [[0.5, 1.0] for _ in range(_F)], list(range(_F)))
    lowered = _post_grow_step.lower(
        tree, scores, jnp.int32(0), leaf_id, jnp.float32(0.1),
        bounds_mat, real_feat)
    ops, has_alias, dwarn, mem = _compile_entry(lowered)
    return {"ops": ops, "donation": has_alias and not dwarn,
            "donation_warnings": dwarn, "has_alias": has_alias,
            "memory": mem}


_ENTRY_MEASURERS = {
    "grow_tree_serial": _measure_grow_tree_serial,
    "grow_forest_batched": _measure_grow_forest_batched,
    "split_step_window": _measure_split_step_window,
    "split_step_record_chain": _measure_split_step_record_chain,
    "place_runs": _measure_place_runs,
    "partition_window": _measure_partition_window,
    "predict_matmul": _measure_predict_matmul,
    "post_grow_step": _measure_post_grow_step,
}


def measure_entry_points(names: Optional[List[str]] = None) -> dict:
    """Measure the audited entry points (CPU backend).  Returns
    {name: {"ops": {...}, "donation": bool|None, ...}}; a measurement
    that raises is recorded as {"error": str}."""
    out = {}
    for name, fn in _ENTRY_MEASURERS.items():
        if names is not None and name not in names:
            continue
        try:
            out[name] = fn()
        except Exception as e:  # surfaced as an audit finding downstream
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    return out


def check_budgets(measured: dict, budgets: dict,
                  require_all: bool = False) -> List[Finding]:
    """Compare measurements against the committed budgets; every
    violation (or missing/failed measurement) is a Finding.  With
    ``require_all`` a budget entry with NO measurement is itself a
    finding — a renamed measurer or typo'd entry key must not silently
    disable its gate (full audits set it; subset callers don't)."""
    findings: List[Finding] = []
    path = os.path.relpath(budgets_path(), os.getcwd())
    for name, entry in budgets.get("entries", {}).items():
        m = measured.get(name)
        if m is None:
            if require_all:
                findings.append(Finding(
                    "hlo-op-budget", path, 0,
                    f"{name}: budget entry has no measurement — "
                    "measurer renamed or entry key typo'd?"))
            continue  # caller restricted the audit to a subset
        if "error" in m:
            findings.append(Finding(
                "hlo-op-budget", path, 0,
                f"{name}: measurement failed: {m['error']}"))
            continue
        for key, limit in entry.items():
            if key == "donation":
                if limit and not m.get("donation"):
                    detail = ("; ".join(m.get("donation_warnings", []))
                              or "no input_output_alias in compiled HLO")
                    findings.append(Finding(
                        "hlo-donation-dropped", path, 0,
                        f"{name}: donation dropped ({detail})"))
            elif key == "record_single_use":
                if limit and not m.get("record_single_use"):
                    findings.append(Finding(
                        "record-chain-multi-use", path, 0,
                        f"{name}: donated record consumed by "
                        f"{m.get('record_uses')} equations (expected 1)"))
            elif key.startswith("_"):
                continue  # comment/metadata keys
            elif key.startswith("mem_"):
                # static memory budget: compiled.memory_analysis()
                # bytes (mem_temp_bytes -> memory["temp_bytes"], ...)
                mem = m.get("memory", {})
                if not mem:
                    findings.append(Finding(
                        "hlo-memory-budget", path, 0,
                        f"{name}: '{key}' budgeted but the backend "
                        "exposed no memory_analysis()"))
                    continue
                got = mem.get(key[len("mem_"):], 0)
                if got > limit:
                    findings.append(Finding(
                        "hlo-memory-budget", path, 0,
                        f"{name}: memory_analysis "
                        f"'{key[len('mem_'):]}' {got} bytes exceeds "
                        f"budget {limit}"))
            else:
                got = m.get("ops", {}).get(key, 0)
                if got > limit:
                    findings.append(Finding(
                        "hlo-op-budget", path, 0,
                        f"{name}: HLO '{key}' count {got} exceeds "
                        f"budget {limit}"))
    return findings


def audit_artifacts(budgets: Optional[dict] = None,
                    names: Optional[List[str]] = None):
    """Run the full stage-2 audit.  Returns (measured, findings)."""
    if budgets is None:
        budgets = load_budgets()
    measured = measure_entry_points(names)
    return measured, check_budgets(measured, budgets,
                                   require_all=names is None)


def steady_loop_recompiles(step_fn, iters: int = 3) -> int:
    """Run ``step_fn()`` ``iters`` times after it has already been
    called once (warm), returning how many backend compiles the warm
    iterations triggered.  0 is the only acceptable answer for a
    shape-stable loop (the recompile-in-steady-loop rule)."""
    from .recompile import compile_counter

    step_fn()  # warm: compiles happen here
    cc = compile_counter()
    for _ in range(iters):
        step_fn()
    return cc.delta()
