#!/usr/bin/env python
"""benchdiff: compare two bench results and flag regressions.

The round-5 failure mode this tool ends: BENCH_r05 (0.4442 s/tree,
vs_baseline 0.71) was committed next to BENCH_r04 (0.3713 / 1.087) and
nobody diffed them.  ``benchdiff`` normalizes any two result artifacts,
compares headline + phases + compile hygiene against thresholds, and
prints the driver-config bench row ROADMAP item 1 requires in any
perf-motivated serial.py/record.py commit.

Accepted input formats (auto-detected per file):

* driver BENCH artifacts  (``BENCH_r0N.json`` — ``{"parsed": {...}}``)
* raw bench.py rows       (``{"metric": ..., "value": ...}``)
* run manifests           (``*.manifest.json`` — obs.manifest v1; the
  headline comes from ``result``, phases from ``phases``)
* multichip artifacts     (``lightgbm-tpu/multichip-bench/v1`` from the
  8-process dryrun / a real multi-chip run, obs/dist.py): diffs the
  headline under the usual threshold plus a SKEW-REGRESSION gate — the
  per-span / per-collective cross-rank skews (max−min seconds) must not
  grow past the phase threshold above an absolute floor, so a run that
  stays flat in aggregate but develops a straggling rank is flagged;
  a changed collective census (per-op counts) is warned about.  World
  sizes must match (exit 2 otherwise — 4-rank skew and 8-rank skew are
  not comparable).
* serving bench artifacts (``.bench/serving_*.json`` —
  ``lightgbm-tpu/serving-bench/v1`` from tools/bench_serving.py):
  online mode diffs p50 (headline threshold) / p99 (phase threshold) /
  throughput / error-rate, plus PER-STAGE p50s (queue_wait / pad /
  device / scatter, from the request-tracing breakdown) under the same
  +25% per-phase rule training runs get — a stage can no longer
  regress 3x while the headline hides it in noise.  Batch mode diffs
  file-to-file seconds.  Serving and training artifacts are never
  cross-compared (exit 2).
* serving fleet artifacts (``.bench/serving_fleet.json`` —
  ``lightgbm-tpu/serving-fleet/v1`` from ``bench_serving.py
  --overload``): the headline is ACCEPTED p99 — the latency the
  admission layer protects by shedding — gated at the phase threshold;
  any failed request is a regression outright (overload must shed,
  never fail), as is a leaked queue bound or a dead dispatcher; the
  shed rate is only judged at ~flat offered load (shedding more
  because more was offered is the mechanism working, not breaking),
  where growth past an absolute floor plus the phase threshold is a
  protection regression.  Fleet artifacts are never cross-compared
  with any other kind (exit 2).
* train fleet artifacts   (``.bench/train_fleet.json`` —
  ``lightgbm-tpu/train-fleet/v1`` from ``task=train_fleet`` /
  ``tools/chaos.py rank_kill_midtrain``, resilience/gang.py): the
  headline is MEAN TIME TO RECOVER — detection of a rank death/hang to
  the reformed gang's last ready handshake — gated at the phase
  threshold (recovery includes jittered backoff, so it is noisier than
  a steady-state latency) and only when BOTH runs actually recovered
  from something; gates that are never perf tradeoffs: any failed
  iteration (the run ended short of its target) is a regression
  outright, as is an exhausted restart budget; lost iterations growing
  at the same barrier cadence is a rollback-quality regression.  World
  shapes must match (exit 2 — recovery across different rank counts is
  not comparable), and train-fleet artifacts are never cross-compared
  with any other kind (exit 2).
* forest bench artifacts  (``.bench/forest_sweep.json`` —
  ``lightgbm-tpu/forest-bench/v1`` from tools/bench_forest.py):
  headline is the batched forest wall (ONE program advancing all N
  models), diffed under the headline threshold; the
  batched-vs-sequential speedup dropping past the headline threshold
  is a regression even when the batched wall itself stays flat (the
  sequential side got faster and batching stopped paying); a batched
  run whose per-model parity hashes no longer match its own sequential
  replay (``parity_ok`` false) is flagged as a correctness regression,
  and ``grow_traces`` growing means the one-trace contract broke
  (trace-per-model came back).  Model counts must match (exit 2 —
  an 8-model sweep and a 16-model sweep are not comparable), and
  forest artifacts are never cross-compared with any other kind
  (exit 2).

Usage:
    python tools/benchdiff.py OLD NEW [--threshold PCT]
        [--phase-threshold PCT] [--json OUT]

Exit codes (diff semantics): 0 = no regression, 1 = regression flagged,
2 = usage/parse error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

# default thresholds (percent).  Headline: the acceptance bar is
# "+>=15% s/tree is a regression"; phases get more slack because
# per-phase attribution carries trace sampling noise.
HEADLINE_PCT = 15.0
PHASE_PCT = 25.0
AUC_ABS = 0.002  # an AUC drop is a correctness smell, not a perf one

MANIFEST_SCHEMA = "lightgbm-tpu/run-manifest/v1"
SERVING_SCHEMA = "lightgbm-tpu/serving-bench/v1"
MULTICHIP_SCHEMA = "lightgbm-tpu/multichip-bench/v1"
FOREST_SCHEMA = "lightgbm-tpu/forest-bench/v1"
FLEET_SCHEMA = "lightgbm-tpu/serving-fleet/v1"
TRAIN_FLEET_SCHEMA = "lightgbm-tpu/train-fleet/v1"
# shed-rate noise floor (absolute fraction of offered requests): below
# this, a shed-rate delta at flat load is sampling noise, not a signal
FLEET_SHED_ABS = 0.02
# cross-rank skew gate: a skew below this absolute floor is scheduling
# noise on any backend — relative growth only matters above it
SKEW_ABS_FLOOR_S = 0.02
# serving error-rate discipline: a regression needs BOTH an absolute
# rise above this floor (noise guard; also covers a 0 baseline) and —
# when the baseline had errors — a relative rise past the headline
# threshold
ERROR_RATE_ABS = 0.001


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _normalize_serving(raw: dict, rec: dict) -> dict:
    """Serving artifacts: the headline is p50 latency (online) or
    file-to-file seconds (batch); p99/throughput/error-rate ride in
    ``aux`` for the serving-specific diff."""
    s = dict(raw.get("serving") or {})
    rec["kind"] = "serving"
    rec["mode"] = s.get("mode", "online")
    if rec["mode"] == "batch":
        rec["value"] = s.get("file_to_file_s")
        rec["unit"] = "s file-to-file"
    else:
        rec["value"] = s.get("p50_ms")
        rec["unit"] = "ms p50"
    rec["aux"] = {k: s.get(k) for k in
                  ("p99_ms", "throughput_rps", "rows_per_s", "error_rate",
                   "requests", "errors", "unpipelined_s", "speedup")
                  if s.get(k) is not None}
    rec["stages"] = dict(s.get("stages") or {})
    rec["shape"] = raw.get("shape") or {}
    rec["knobs"] = raw.get("knobs") or {}
    if rec.get("value") in (None, 0, 0.0):
        raise ValueError(
            f"{rec['path']}: serving artifact has no usable headline "
            f"({'file_to_file_s' if rec['mode'] == 'batch' else 'p50_ms'})")
    return rec


def _normalize_forest(raw: dict, rec: dict) -> dict:
    """Forest-bench artifacts (tools/bench_forest.py): headline is the
    batched wall — the one dispatch-per-round program advancing all N
    models; the sequential wall / speedup / parity hashes / trace
    counters ride in ``aux`` for the forest-specific diff."""
    f = dict(raw.get("forest") or {})
    rec["kind"] = "forest"
    rec["num_models"] = f.get("num_models")
    rec["value"] = f.get("batched_wall_s")
    rec["unit"] = "s batched-wall"
    rec["aux"] = {k: f.get(k) for k in
                  ("sequential_wall_s", "speedup", "rounds", "rows",
                   "features", "num_class", "grow_traces",
                   "forest_dispatches", "forest_batched_trees")
                  if f.get(k) is not None}
    rec["parity"] = dict(f.get("parity") or {})
    rec["parity_ok"] = f.get("parity_ok")
    rec["shape"] = {k: f.get(k) for k in
                    ("rows", "features", "num_class", "rounds")}
    rec["knobs"] = raw.get("knobs") or {}
    if rec.get("value") in (None, 0, 0.0):
        raise ValueError(
            f"{rec['path']}: forest artifact has no usable headline "
            "(forest.batched_wall_s)")
    return rec


def _normalize_fleet(raw: dict, rec: dict) -> dict:
    """Serving-fleet overload artifacts (tools/bench_serving.py
    --overload): headline is the ACCEPTED p99 — the latency the
    admission layer protects by shedding; offered/accepted rates, the
    shed split, and the failure count ride in ``aux`` for the
    fleet-specific gates."""
    f = dict(raw.get("fleet") or {})
    rec["kind"] = "fleet"
    rec["value"] = f.get("accepted_p99_ms")
    rec["unit"] = "ms accepted-p99"
    rec["aux"] = {k: f.get(k) for k in
                  ("sustainable_rps", "offered_rps", "accepted_rps",
                   "offered", "accepted", "completed", "shed_total",
                   "shed_rate", "failed", "accepted_p50_ms",
                   "deadline_ms", "max_queue_rows",
                   "max_pending_rows_observed", "queue_bound_held",
                   "dispatcher_alive", "overload_factor")
                  if f.get(k) is not None}
    rec["shed"] = dict(f.get("shed") or {})
    rec["shape"] = raw.get("shape") or {}
    if rec.get("value") in (None, 0, 0.0):
        raise ValueError(
            f"{rec['path']}: fleet artifact has no usable headline "
            "(fleet.accepted_p99_ms)")
    return rec


def _normalize_train_fleet(raw: dict, rec: dict) -> dict:
    """Train-fleet recovery artifacts (resilience/gang.py): headline is
    mean-time-to-recover; the recovery ladder's tallies (restarts,
    shrinks, lost/failed iterations, budget spend) ride in ``aux`` for
    the train-fleet-specific gates.  Unlike every other kind an
    mttr_s of 0 is a VALID headline — a run that never needed to
    recover (the uninterrupted baseline) is the best possible result,
    not an unusable record."""
    f = dict(raw.get("train_fleet") or {})
    rec["kind"] = "train_fleet"
    rec["value"] = float(f.get("mttr_s") or 0.0)
    rec["unit"] = "s mttr"
    rec["aux"] = {k: f.get(k) for k in
                  ("world_size_start", "world_size_end", "restarts",
                   "shrinks", "rank_deaths", "rank_hangs", "recoveries",
                   "lost_iterations", "failed_iterations",
                   "target_iterations", "budget_spent",
                   "budget_exhausted", "preempted", "final_barrier",
                   "barriers_committed", "exit_code", "wall_s")
                  if f.get(k) is not None}
    rec["recovery_timeline"] = list(f.get("recovery_timeline") or [])
    rec["shape"] = raw.get("shape") or {}
    rec["counters"] = raw.get("counters") or {}
    return rec


def _normalize_multichip(raw: dict, rec: dict) -> dict:
    """Multichip artifacts: headline from ``result.value``; the skew
    tables (span + reservoir, already ``{name: {max_minus_min_s, ...}}``)
    ride flattened for the skew-regression gate; per-op collective
    counts ride for the census warning."""
    rec["kind"] = "multichip"
    rec["world"] = raw.get("world")
    row = dict(raw.get("result") or {})
    rec["value"] = row.get("value")
    rec["unit"] = row.get("unit", "s")
    skew = raw.get("skew") or {}
    flat = {}
    for group in ("spans", "reservoirs"):
        for name, sk in (skew.get(group) or {}).items():
            flat[name] = sk
    rec["skew"] = flat
    counters = (raw.get("merged") or {}).get("counters") or {}
    rec["collective_census"] = {
        k: counters[k] for k in sorted(counters)
        if k.startswith(("collective_ops.op.", "collective_site."))}
    rec["stragglers"] = raw.get("stragglers") or []
    # per-rank device-memory peaks (obs/memory.py): the artifact-level
    # hbm_peak_bytes is the worst rank — the one the next OOM kills
    rank_hbm = {}
    for r in raw.get("ranks") or []:
        if r.get("hbm_peak_bytes"):
            rank_hbm[r.get("process_index")] = int(r["hbm_peak_bytes"])
    extra_hbm = (raw.get("extra") or {}).get("hbm_peak_bytes")
    peak = max(rank_hbm.values(), default=0) or int(extra_hbm or 0)
    if peak:
        rec["hbm_peak_bytes"] = peak
        rec["rank_hbm_peak_bytes"] = rank_hbm
    if rec.get("value") in (None, 0, 0.0):
        raise ValueError(
            f"{rec['path']}: multichip artifact has no usable headline "
            "(result.value)")
    return rec


def normalize(path: str) -> dict:
    """One record shape for every accepted input format:
    ``{label, value, unit, vs_baseline, auc..., phases, compile...}``."""
    raw = _load(path)
    rec: dict = {"label": os.path.basename(path), "path": path,
                 "phases": {}, "sha": None, "kind": "training"}
    if raw.get("schema") == TRAIN_FLEET_SCHEMA:
        return _normalize_train_fleet(raw, rec)
    if raw.get("schema") == FLEET_SCHEMA:
        return _normalize_fleet(raw, rec)
    if raw.get("schema") == FOREST_SCHEMA:
        return _normalize_forest(raw, rec)
    if raw.get("schema") == MULTICHIP_SCHEMA:
        return _normalize_multichip(raw, rec)
    if raw.get("schema") == SERVING_SCHEMA or "serving" in raw:
        return _normalize_serving(raw, rec)
    if raw.get("schema") == MANIFEST_SCHEMA:
        row = dict(raw.get("result") or {})
        rec["phases"] = dict(raw.get("phases") or {})
        rec["sha"] = (raw.get("git") or {}).get("sha")
        rec["per_tree"] = raw.get("per_tree") or {}
        rec["warmup"] = raw.get("warmup") or {}
        # memory section (obs/memory.py manifest_memory_section):
        # hbm peak is gateable like the headline
        hbm = (raw.get("memory") or {}).get("hbm") or {}
        if hbm.get("hbm_peak_bytes"):
            rec["hbm_peak_bytes"] = int(hbm["hbm_peak_bytes"])
        # northstar manifests carry the headline under another key
        if "value" not in row and "steady_sec_per_tree" in row:
            row["value"] = row["steady_sec_per_tree"]
            row.setdefault("unit", "s/tree")
        # cli.train manifests record wall + tree count: synthesize the
        # s/tree headline so any two run manifests really are diffable
        # (README's promise)
        if "value" not in row and row.get("train_wall_s") \
                and row.get("num_trees"):
            row["value"] = float(row["train_wall_s"]) / row["num_trees"]
            row.setdefault("unit", "s/tree (wall, incl. compile)")
    elif "parsed" in raw:  # driver BENCH artifact
        row = dict(raw["parsed"] or {})
    else:  # raw bench.py row
        row = dict(raw)
    for k in ("metric", "value", "unit", "vs_baseline", "platform",
              "growth", "train_auc", "valid_auc", "knobs", "error",
              "warmup_iters", "warm_trees_discarded", "compile_stable",
              "compiles_warmup", "compiles_timed", "timed_trees",
              "hbm_peak_bytes"):
        if k in row:
            rec[k] = row[k]
    if "phases" in row and not rec["phases"]:
        rec["phases"] = dict(row["phases"] or {})
    if rec.get("value") in (None, 0, 0.0) and "error" not in row:
        # a zero headline is an unusable record, not a 100% improvement
        raise ValueError(f"{path}: no usable headline value in {row}")
    return rec


def _pct(old: float, new: float) -> float:
    return (new - old) / old * 100.0 if old else float("inf")


def _diff_hbm(old: dict, new: dict, regressions: list, warnings: list,
              improvements: list, headline_pct: float) -> None:
    """Device-memory gate, shared by training and multichip diffs: at
    the same shape, ``hbm_peak_bytes`` growing past the headline
    threshold is a regression EVEN when the time headline stays flat —
    a +15% peak at 100M rows is the next OOM (ROADMAP items 3/4), and
    time gates alone would wave it through."""
    oh = int(old.get("hbm_peak_bytes") or 0)
    nh = int(new.get("hbm_peak_bytes") or 0)
    if oh <= 0 and nh <= 0:
        return
    if oh <= 0 or nh <= 0:
        side = "old" if nh else "new"
        warnings.append(
            f"hbm_peak_bytes present only in the {side} artifact — "
            "memory coverage changed between the two runs")
        return
    d = _pct(oh, nh)
    if d >= headline_pct:
        regressions.append(
            f"hbm_peak_bytes {oh} -> {nh} (+{d:.1f}%, threshold "
            f"+{headline_pct:.0f}%) — device-memory regression at "
            "same shape")
    elif d <= -headline_pct:
        improvements.append(f"hbm_peak_bytes {oh} -> {nh} ({d:.1f}%)")


def diff_serving(old: dict, new: dict, headline_pct: float = HEADLINE_PCT,
                 phase_pct: float = PHASE_PCT) -> dict:
    """Serving-artifact comparison under the same threshold discipline
    as training: headline (p50 / file-to-file) +headline_pct is a
    regression, p99 gets the looser phase threshold (tail latency is
    noisier), a throughput drop past the headline threshold regresses,
    and an error-rate rise is judged by ERROR_RATE_ABS + the relative
    headline threshold."""
    regressions, warnings, improvements = [], [], []
    if old.get("mode") != new.get("mode"):
        raise ValueError(
            f"serving modes differ (old: {old.get('mode')}, new: "
            f"{new.get('mode')}) — online and batch artifacts are not "
            "comparable")
    unit = new.get("unit", "")
    ov, nv = float(old["value"]), float(new["value"])
    head = _pct(ov, nv)
    headline = {"old": ov, "new": nv, "unit": unit,
                "delta_pct": round(head, 1)}
    if head >= headline_pct:
        regressions.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} (+{head:.1f}%, "
            f"threshold +{headline_pct:.0f}%)")
    elif head <= -headline_pct:
        improvements.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} ({head:.1f}%)")

    oa, na = old.get("aux") or {}, new.get("aux") or {}
    for key, thresh, lower_is_better in (
            ("p99_ms", phase_pct, True),
            ("throughput_rps", headline_pct, False),
            ("rows_per_s", headline_pct, False)):
        if oa.get(key) and na.get(key):
            d = _pct(float(oa[key]), float(na[key]))
            worse = d >= thresh if lower_is_better else d <= -thresh
            better = d <= -thresh if lower_is_better else d >= thresh
            if worse:
                regressions.append(
                    f"{key} {oa[key]:.4g} -> {na[key]:.4g} "
                    f"({d:+.1f}%, threshold {thresh:.0f}%)")
            elif better:
                improvements.append(
                    f"{key} {oa[key]:.4g} -> {na[key]:.4g} ({d:+.1f}%)")
    # per-stage regressions (request-tracing breakdown): same
    # discipline as training phases — +phase_pct on a stage's p50 is a
    # regression even when the headline stays flat (four small stages
    # can hide one 3x stage inside headline noise), a stage present on
    # only one side is reported, never silently dropped
    ost, nst = old.get("stages") or {}, new.get("stages") or {}
    if ost or nst:
        for st in sorted(set(ost) ^ set(nst)):
            side = "old" if st in ost else "new"
            warnings.append(
                f"stage '{st}' present only in the {side} artifact — "
                "tracing coverage changed between the two runs")
        for st in sorted(set(ost) & set(nst)):
            o = float((ost[st] or {}).get("p50_ms") or 0.0)
            n = float((nst[st] or {}).get("p50_ms") or 0.0)
            if o <= 0 or n <= 0:
                if max(o, n) > 0.05:
                    warnings.append(
                        f"stage '{st}' p50 {o:.4g} -> {n:.4g} ms (no "
                        "baseline to diff against)")
                continue
            d = _pct(o, n)
            if d >= phase_pct:
                regressions.append(
                    f"stage '{st}' p50 {o:.4g} -> {n:.4g} ms "
                    f"(+{d:.1f}%, threshold +{phase_pct:.0f}%)")
            elif d <= -phase_pct:
                improvements.append(
                    f"stage '{st}' p50 {o:.4g} -> {n:.4g} ms ({d:.1f}%)")
    elif old.get("mode") == "online":
        warnings.append("no per-stage breakdown on either side "
                        "(re-run tools/bench_serving.py with tracing on)")

    oe = float(oa.get("error_rate") or 0.0)
    ne = float(na.get("error_rate") or 0.0)
    if ne > oe + ERROR_RATE_ABS and (
            oe == 0 or _pct(oe, ne) >= headline_pct):
        regressions.append(
            f"error_rate {oe:.4f} -> {ne:.4f} — serving errors are a "
            "correctness regression, not a perf tradeoff")
    elif oe > ne + ERROR_RATE_ABS:
        improvements.append(f"error_rate {oe:.4f} -> {ne:.4f}")

    os_, ns = old.get("shape") or {}, new.get("shape") or {}
    if os_ and ns and os_ != ns:
        warnings.append(
            f"load shapes differ (old: {os_}, new: {ns}) — comparison "
            "may not be apples-to-apples")
    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def diff_fleet(old: dict, new: dict,
               headline_pct: float = HEADLINE_PCT,
               phase_pct: float = PHASE_PCT) -> dict:
    """Serving-fleet overload comparison.  The headline is accepted-p99
    gated at ``phase_pct`` (tail latency at deliberate saturation is
    noisier than a steady-state p99, so it gets the looser phase
    threshold).  Gates that are never perf tradeoffs: any failed
    request is a regression outright (overload must shed with a typed
    status, never fail), as is a queue that leaked past its row bound
    or a dispatcher that died.  The shed rate is only judged when the
    offered load is ~flat (within ``headline_pct``): shedding more
    because MORE was offered is the admission layer working; shedding
    more at the SAME offered load means the service got less able to
    absorb the same demand."""
    regressions, warnings, improvements = [], [], []
    unit = new.get("unit", "ms accepted-p99")
    ov, nv = float(old["value"]), float(new["value"])
    head = _pct(ov, nv)
    headline = {"old": ov, "new": nv, "unit": unit,
                "delta_pct": round(head, 1)}
    if head >= phase_pct:
        regressions.append(
            f"accepted p99 {ov:.4g} -> {nv:.4g} ms (+{head:.1f}%, "
            f"threshold +{phase_pct:.0f}%) — the latency shedding is "
            "supposed to protect")
    elif head <= -phase_pct:
        improvements.append(
            f"accepted p99 {ov:.4g} -> {nv:.4g} ms ({head:.1f}%)")

    oa, na = old.get("aux") or {}, new.get("aux") or {}
    # correctness gates first: these are never perf tradeoffs
    if int(na.get("failed") or 0) > 0:
        regressions.append(
            f"NEW run FAILED {na['failed']} request(s) — an overloaded "
            "fleet must shed with a typed status, never fail")
    if na.get("queue_bound_held") is False:
        regressions.append(
            "NEW run's queue leaked past its row bound "
            f"(observed {na.get('max_pending_rows_observed')} > "
            f"{na.get('max_queue_rows')} rows) — admission control is "
            "not actually bounding memory")
    if na.get("dispatcher_alive") is False:
        regressions.append(
            "NEW run's dispatcher died under overload — shedding must "
            "leave the serving loop standing")

    oo = float(oa.get("offered_rps") or 0)
    no_ = float(na.get("offered_rps") or 0)
    osr = float(oa.get("shed_rate") or 0)
    nsr = float(na.get("shed_rate") or 0)
    if oo > 0 and no_ > 0:
        load_delta = _pct(oo, no_)
        if abs(load_delta) < headline_pct:
            rel = _pct(osr, nsr) if osr > 0 else float("inf")
            if nsr > osr + FLEET_SHED_ABS and rel >= phase_pct:
                regressions.append(
                    f"shed_rate {osr:.4f} -> {nsr:.4f} at ~flat offered "
                    f"load ({oo:.4g} -> {no_:.4g} req/s) — the service "
                    "got less able to absorb the same demand")
            elif osr > nsr + FLEET_SHED_ABS:
                improvements.append(
                    f"shed_rate {osr:.4f} -> {nsr:.4f} at ~flat offered "
                    f"load ({oo:.4g} -> {no_:.4g} req/s)")
        else:
            warnings.append(
                f"offered load moved {oo:.4g} -> {no_:.4g} req/s "
                f"({load_delta:+.1f}%) — shed rates ({osr:.4f} vs "
                f"{nsr:.4f}) are not comparable across different demand")
    oar, nar = oa.get("accepted_rps"), na.get("accepted_rps")
    if oar and nar:
        d = _pct(float(oar), float(nar))
        if d <= -headline_pct:
            regressions.append(
                f"accepted throughput {float(oar):.4g} -> "
                f"{float(nar):.4g} req/s ({d:.1f}%, threshold "
                f"-{headline_pct:.0f}%)")
        elif d >= headline_pct:
            improvements.append(
                f"accepted throughput {float(oar):.4g} -> "
                f"{float(nar):.4g} req/s ({d:+.1f}%)")

    os_, ns = old.get("shape") or {}, new.get("shape") or {}
    if os_ and ns and os_ != ns:
        warnings.append(
            f"overload shapes differ (old: {os_}, new: {ns}) — "
            "comparison may not be apples-to-apples")
    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def diff_train_fleet(old: dict, new: dict,
                     headline_pct: float = HEADLINE_PCT,
                     phase_pct: float = PHASE_PCT) -> dict:
    """Train-fleet recovery comparison.  The headline is
    mean-time-to-recover, gated at ``phase_pct`` (recovery spans a
    jittered backoff plus process relaunch, so it is noisier than a
    steady-state measurement) and only when BOTH runs actually
    recovered from something — a chaos run against an uninterrupted
    baseline has no MTTR to diff, only its correctness gates.  Those
    gates are never perf tradeoffs: ANY failed iteration means the run
    ended short of its training target (the gang lost work a rollback
    was supposed to save); an exhausted restart budget means the gang
    crash-looped to death; lost iterations growing past the phase
    threshold at the same barrier cadence means rollbacks landed
    further from the failure than they used to."""
    regressions, warnings, improvements = [], [], []
    oa, na = old.get("aux") or {}, new.get("aux") or {}
    osh, nsh = old.get("shape") or {}, new.get("shape") or {}
    if osh and nsh and (osh.get("ranks"), osh.get("barrier_every")) != \
            (nsh.get("ranks"), nsh.get("barrier_every")):
        raise ValueError(
            f"train-fleet shapes differ (old: {osh}, new: {nsh}) — "
            "recovery across different rank counts / barrier cadences "
            "is not comparable")
    ov, nv = float(old.get("value") or 0), float(new.get("value") or 0)
    headline = {"old": ov, "new": nv, "unit": new.get("unit", "s mttr"),
                "delta_pct": None}
    if ov > 0 and nv > 0:
        head = _pct(ov, nv)
        headline["delta_pct"] = round(head, 1)
        if head >= phase_pct:
            regressions.append(
                f"mean time to recover {ov:.4g} -> {nv:.4g} s "
                f"(+{head:.1f}%, threshold +{phase_pct:.0f}%)")
        elif head <= -phase_pct:
            improvements.append(
                f"mean time to recover {ov:.4g} -> {nv:.4g} s "
                f"({head:.1f}%)")
    elif (ov > 0) != (nv > 0):
        side = "old" if ov > 0 else "new"
        warnings.append(
            f"only the {side} run recovered from anything "
            f"({oa.get('recoveries', 0)} vs {na.get('recoveries', 0)} "
            "recoveries) — no MTTR to diff, correctness gates only")

    # correctness gates: these are never perf tradeoffs
    if int(na.get("failed_iterations") or 0) > 0:
        regressions.append(
            f"NEW run FAILED {na['failed_iterations']} iteration(s) "
            f"(reached barrier {na.get('final_barrier')} of "
            f"{na.get('target_iterations')}) — the gang lost training "
            "work a rollback was supposed to save")
    if na.get("budget_exhausted"):
        regressions.append(
            "NEW run exhausted its restart budget "
            f"(spent {na.get('budget_spent')}) — the gang crash-looped "
            "to death instead of finishing")
    ol = int(oa.get("lost_iterations") or 0)
    nl = int(na.get("lost_iterations") or 0)
    if nl > ol and (ol == 0 or _pct(ol, nl) >= phase_pct):
        regressions.append(
            f"lost_iterations {ol} -> {nl} at the same barrier cadence "
            "— rollbacks land further from the failure than they "
            "used to")
    elif ol > nl:
        improvements.append(f"lost_iterations {ol} -> {nl}")
    if int(na.get("world_size_end") or 0) < \
            int(na.get("world_size_start") or 0):
        warnings.append(
            f"NEW run shrank its gang "
            f"({na.get('world_size_start')} -> "
            f"{na.get('world_size_end')} ranks, "
            f"{na.get('shrinks')} shrink(s)) — it finished, but on "
            "fewer hosts than it was given")
    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def diff_forest(old: dict, new: dict,
                headline_pct: float = HEADLINE_PCT,
                phase_pct: float = PHASE_PCT) -> dict:
    """Forest-bench comparison: the batched wall under the usual
    headline threshold, PLUS the gates that keep the batching honest —
    the batched-vs-sequential speedup must not shrink past the headline
    threshold (a flat batched wall over a faster sequential engine
    means the fused dispatch stopped paying), ``parity_ok`` false is a
    correctness regression outright (the batched trees diverged from
    their own sequential replay), and a ``grow_traces`` count that grew
    means the one-trace-for-all-models contract broke."""
    regressions, warnings, improvements = [], [], []
    if old.get("num_models") != new.get("num_models"):
        raise ValueError(
            f"forest model counts differ (old: {old.get('num_models')}, "
            f"new: {new.get('num_models')}) — batched walls across "
            "different sweep widths are not comparable")
    unit = new.get("unit", "s")
    ov, nv = float(old["value"]), float(new["value"])
    head = _pct(ov, nv)
    headline = {"old": ov, "new": nv, "unit": unit,
                "delta_pct": round(head, 1),
                "num_models": new.get("num_models")}
    if head >= headline_pct:
        regressions.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} (+{head:.1f}%, "
            f"threshold +{headline_pct:.0f}%)")
    elif head <= -headline_pct:
        improvements.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} ({head:.1f}%)")

    oa, na = old.get("aux") or {}, new.get("aux") or {}
    osp, nsp = oa.get("speedup"), na.get("speedup")
    if osp and nsp:
        d = _pct(float(osp), float(nsp))
        if d <= -headline_pct:
            regressions.append(
                f"batched-vs-sequential speedup {osp:.2f}x -> {nsp:.2f}x "
                f"({d:.1f}%, threshold -{headline_pct:.0f}%) — the fused "
                "dispatch pays less than it used to")
        elif d >= headline_pct:
            improvements.append(
                f"batched-vs-sequential speedup {osp:.2f}x -> {nsp:.2f}x "
                f"({d:+.1f}%)")
    if nsp is not None and float(nsp) < 1.0:
        regressions.append(
            f"NEW speedup {float(nsp):.2f}x < 1 — the batched program is "
            "slower than the sequential loop it replaces")

    # correctness gates: these are never perf tradeoffs
    if new.get("parity_ok") is False:
        regressions.append(
            "NEW run's per-model parity hashes do not match the "
            "sequential replay (parity_ok false) — the batched grower "
            "diverged from the tree-by-tree path")
    ot = oa.get("grow_traces")
    nt = na.get("grow_traces")
    if nt is not None and ot is not None and int(nt) > int(ot):
        regressions.append(
            f"grow_traces {ot} -> {nt} — the batched sweep retraces; "
            "one-program-for-the-forest no longer holds")
    op_, np_ = old.get("parity") or {}, new.get("parity") or {}
    if op_ and np_ and sorted(op_) == sorted(np_) and op_ != np_:
        changed = sorted(k for k in op_ if op_[k] != np_.get(k))
        warnings.append(
            "per-model parity hashes changed vs the OLD artifact "
            f"({len(changed)}/{len(op_)} models: "
            + ", ".join(changed[:4])
            + (" ..." if len(changed) > 4 else "")
            + ") — the trained trees themselves moved, expected only "
            "after an intentional numerics change")

    os_, ns = old.get("shape") or {}, new.get("shape") or {}
    if os_ and ns and os_ != ns:
        warnings.append(
            f"sweep shapes differ (old: {os_}, new: {ns}) — comparison "
            "may not be apples-to-apples")
    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def diff_multichip(old: dict, new: dict,
                   headline_pct: float = HEADLINE_PCT,
                   phase_pct: float = PHASE_PCT) -> dict:
    """Multichip comparison: headline under the usual threshold, plus
    the skew-regression gate — a cross-rank skew (max−min seconds of a
    span/collective series) growing past ``phase_pct`` above the
    absolute floor is a regression even when the headline stays flat
    (one straggling rank hides inside an aggregate mean)."""
    regressions, warnings, improvements = [], [], []
    if old.get("world") != new.get("world"):
        raise ValueError(
            f"multichip world sizes differ (old: {old.get('world')}, "
            f"new: {new.get('world')}) — skew across different worlds "
            "is not comparable")
    unit = new.get("unit", "s")
    ov, nv = float(old["value"]), float(new["value"])
    head = _pct(ov, nv)
    headline = {"old": ov, "new": nv, "unit": unit,
                "delta_pct": round(head, 1), "world": new.get("world")}
    if head >= headline_pct:
        regressions.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} (+{head:.1f}%, "
            f"threshold +{headline_pct:.0f}%)")
    elif head <= -headline_pct:
        improvements.append(
            f"headline {unit} {ov:.4g} -> {nv:.4g} ({head:.1f}%)")

    osk, nsk = old.get("skew") or {}, new.get("skew") or {}
    for name in sorted(set(osk) ^ set(nsk)):
        side = "old" if name in osk else "new"
        warnings.append(
            f"skew series '{name}' present only in the {side} artifact "
            "— instrumentation coverage changed between the two runs")
    for name in sorted(set(osk) & set(nsk)):
        o = float((osk[name] or {}).get("max_minus_min_s") or 0.0)
        n = float((nsk[name] or {}).get("max_minus_min_s") or 0.0)
        if n <= SKEW_ABS_FLOOR_S and o <= SKEW_ABS_FLOOR_S:
            continue  # both inside scheduling noise
        if o <= 0:
            # a skew APPEARING from a clean baseline is the worst
            # straggler regression, not a footnote — a 0s -> 5s skew
            # must never pass a gate a 0.03s -> 0.04s one fails
            regressions.append(
                f"cross-rank skew '{name}' appeared: 0 -> {n:.4f}s "
                f"max-min (implicated rank "
                f"{(nsk[name] or {}).get('max_rank')})")
            continue
        d = _pct(o, n)
        who = (nsk[name] or {}).get("min_rank") \
            if name.endswith(".wait_s") else (nsk[name] or {}).get("max_rank")
        if d >= phase_pct and n > SKEW_ABS_FLOOR_S:
            regressions.append(
                f"cross-rank skew '{name}' {o:.4f}s -> {n:.4f}s max-min "
                f"(+{d:.1f}%, threshold +{phase_pct:.0f}%; implicated "
                f"rank {who})")
        elif d <= -phase_pct and o > SKEW_ABS_FLOOR_S:
            improvements.append(
                f"cross-rank skew '{name}' {o:.4f}s -> {n:.4f}s "
                f"({d:.1f}%)")

    _diff_hbm(old, new, regressions, warnings, improvements,
              headline_pct)
    # per-rank memory skew: a rank whose peak diverges from its peers
    # is the data-balance analog of a time straggler
    orh = old.get("rank_hbm_peak_bytes") or {}
    nrh = new.get("rank_hbm_peak_bytes") or {}
    if len(nrh) >= 2:
        mx, mn = max(nrh.values()), min(nrh.values())
        if mn > 0 and _pct(mn, mx) >= phase_pct:
            omx, omn = (max(orh.values()), min(orh.values())) \
                if len(orh) >= 2 else (0, 0)
            was_skewed = omn > 0 and _pct(omn, omx) >= phase_pct
            who = max(nrh, key=lambda r: nrh[r])
            msg = (f"per-rank hbm_peak_bytes skew: min {mn}, max {mx} "
                   f"(+{_pct(mn, mx):.1f}%; heaviest rank {who})")
            if was_skewed:
                warnings.append(msg + " — already skewed in baseline")
            else:
                regressions.append("memory skew appeared: " + msg)

    oc = old.get("collective_census") or {}
    nc = new.get("collective_census") or {}
    if oc and nc and oc != nc:
        changed = sorted(k for k in set(oc) | set(nc)
                         if oc.get(k) != nc.get(k))
        warnings.append(
            "collective census changed (the per-op contract moved): "
            + ", ".join(f"{k} {oc.get(k, 0)} -> {nc.get(k, 0)}"
                        for k in changed[:6])
            + (" ..." if len(changed) > 6 else ""))
    for s in new.get("stragglers") or []:
        warnings.append(
            f"NEW run names a straggler: rank {s.get('straggler_rank')} "
            f"at {s.get('site')} (wait skew {s.get('wait_skew_s')}s)")
    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def diff(old: dict, new: dict, headline_pct: float = HEADLINE_PCT,
         phase_pct: float = PHASE_PCT) -> dict:
    """Compare two normalized records; returns
    ``{regressions: [...], warnings: [...], improvements: [...],
    headline: {...}}``."""
    if "train_fleet" in (old.get("kind"), new.get("kind")):
        if old.get("kind") != new.get("kind"):
            raise ValueError(
                f"{old['label']} is a {old.get('kind')} artifact, "
                f"{new['label']} is a {new.get('kind')} artifact — "
                "train-fleet recovery metrics and other results are "
                "not comparable (an MTTR has no meaning against a "
                "latency or s/tree headline)")
        return diff_train_fleet(old, new, headline_pct, phase_pct)
    if "fleet" in (old.get("kind"), new.get("kind")):
        if old.get("kind") != new.get("kind"):
            raise ValueError(
                f"{old['label']} is a {old.get('kind')} artifact, "
                f"{new['label']} is a {new.get('kind')} artifact — "
                "fleet-overload and other results are not comparable "
                "(an overload shed-rate has no meaning against a "
                "steady-state serving bench)")
        return diff_fleet(old, new, headline_pct, phase_pct)
    if "forest" in (old.get("kind"), new.get("kind")):
        if old.get("kind") != new.get("kind"):
            raise ValueError(
                f"{old['label']} is a {old.get('kind')} artifact, "
                f"{new['label']} is a {new.get('kind')} artifact — "
                "forest-bench and other results are not comparable")
        return diff_forest(old, new, headline_pct, phase_pct)
    if "multichip" in (old.get("kind"), new.get("kind")):
        if old.get("kind") != new.get("kind"):
            raise ValueError(
                f"{old['label']} is a {old.get('kind')} artifact, "
                f"{new['label']} is a {new.get('kind')} artifact — "
                "multichip and other results are not comparable")
        return diff_multichip(old, new, headline_pct, phase_pct)
    if "serving" in (old.get("kind"), new.get("kind")):
        if old.get("kind") != new.get("kind"):
            raise ValueError(
                f"{old['label']} is a {old.get('kind')} artifact, "
                f"{new['label']} is a {new.get('kind')} artifact — "
                "serving and training results are not comparable")
        return diff_serving(old, new, headline_pct, phase_pct)
    regressions, warnings, improvements = [], [], []

    if old.get("metric") and new.get("metric") \
            and old["metric"] != new["metric"]:
        warnings.append(
            f"metric mismatch: {old['metric']} vs {new['metric']} — "
            "comparison may not be apples-to-apples")

    # an errored/empty NEW run is the worst regression of all, not a
    # -100% improvement (bench.py's crash path emits value 0.0 + error)
    if new.get("error"):
        regressions.append(f"NEW run errored: {new['error']}")
    if old.get("error"):
        warnings.append(f"OLD run errored: {old['error']} — baseline "
                        "side is not a real measurement")
    ov, nv = float(old.get("value") or 0), float(new.get("value") or 0)
    headline = {"old_s_per_tree": ov, "new_s_per_tree": nv,
                "delta_pct": None}
    if nv <= 0 and not new.get("error"):
        regressions.append("NEW run has no headline value")
    if ov > 0 and nv > 0:
        head = _pct(ov, nv)
        headline["delta_pct"] = round(head, 1)
        if head >= headline_pct:
            regressions.append(
                f"headline s/tree {ov:.4f} -> {nv:.4f} "
                f"(+{head:.1f}%, threshold +{headline_pct:.0f}%)")
        elif head <= -headline_pct:
            improvements.append(
                f"headline s/tree {ov:.4f} -> {nv:.4f} ({head:.1f}%)")

    ovb, nvb = old.get("vs_baseline"), new.get("vs_baseline")
    if ovb and nvb:
        headline["vs_baseline"] = {"old": ovb, "new": nvb}
        if float(nvb) < 0.85 * float(ovb):
            regressions.append(
                f"vs_baseline {ovb} -> {nvb} "
                f"({_pct(float(ovb), float(nvb)):.1f}%)")

    # per-phase regressions: only comparable when both runs attributed
    # phases (a missing breakdown is reported, never silently skipped)
    op, np_ = old.get("phases") or {}, new.get("phases") or {}
    shared = sorted(set(op) & set(np_) - {"unattributed"})
    if op or np_:
        if not shared:
            warnings.append("phase breakdowns not comparable "
                            f"(old: {sorted(op)}, new: {sorted(np_)})")
        # a phase present on only ONE side is itself a signal (lost
        # scope attribution, or work that moved to/from unattributed)
        # — never drop it silently
        for ph in sorted(set(op) ^ set(np_)):
            side = "old" if ph in op else "new"
            val = op.get(ph, np_.get(ph, 0.0))
            warnings.append(
                f"phase '{ph}' ({val:.3f}s) present only in the {side} "
                "run — attribution changed between the two runs")
        for ph in shared:
            o, n = float(op[ph]), float(np_[ph])
            if o <= 0 or n <= 0:
                # a 0.0 side has no meaningful percent; only a real
                # appearance is worth a word
                if max(o, n) > 0.05:
                    warnings.append(
                        f"phase '{ph}' {o:.3f}s -> {n:.3f}s (no "
                        "baseline to diff against)")
                continue
            d = _pct(o, n)
            if d >= phase_pct:
                regressions.append(
                    f"phase '{ph}' {o:.3f}s -> {n:.3f}s "
                    f"(+{d:.1f}%, threshold +{phase_pct:.0f}%)")
            elif d <= -phase_pct:
                improvements.append(
                    f"phase '{ph}' {o:.3f}s -> {n:.3f}s ({d:.1f}%)")
    else:
        warnings.append("no phase breakdown on either side (a CLI "
                        "train run with profile=true records one)")

    # compile hygiene of the NEW run (the round-5 mechanism: lazy
    # compiles inside the timed loop)
    if new.get("compiles_timed"):
        regressions.append(
            f"{new['compiles_timed']} backend compile(s) inside the NEW "
            "run's timed loop — the measurement itself is dirty")
    if new.get("compile_stable") is False:
        warnings.append("NEW run's warm-up never went compile-stable "
                        "(BENCH_MAX_WARM exhausted)")

    for k in ("train_auc", "valid_auc"):
        if old.get(k) is not None and new.get(k) is not None:
            d = float(new[k]) - float(old[k])
            if d < -AUC_ABS:
                regressions.append(f"{k} {old[k]} -> {new[k]} ({d:+.4f})")

    _diff_hbm(old, new, regressions, warnings, improvements,
              headline_pct)

    return {"headline": headline, "regressions": regressions,
            "warnings": warnings, "improvements": improvements}


def driver_row(rec: dict) -> str:
    """The bench row ROADMAP item 1 requires in perf-motivated
    serial.py/record.py commits — ready to paste."""
    sha = (rec.get("sha") or "unknown")[:9]
    knobs = ",".join(f"{k.split('LGBM_TPU_')[-1]}={v}"
                     for k, v in (rec.get("knobs") or {}).items()) or "-"
    return ("| {metric} | {value} s/tree | vs_baseline {vsb} | "
            "{platform} | warm {w}/{d} compiles {cw}+{ct} | {knobs} | "
            "{sha} |").format(
        metric=rec.get("metric", "?"), value=rec.get("value", "?"),
        vsb=rec.get("vs_baseline", "?"),
        platform=rec.get("platform", "?"),
        w=rec.get("warmup_iters", "?"),
        d=rec.get("warm_trees_discarded", "?"),
        cw=rec.get("compiles_warmup", "?"),
        ct=rec.get("compiles_timed", "?"),
        knobs=knobs, sha=sha)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--threshold", type=float, default=HEADLINE_PCT,
                    help="headline regression threshold in percent "
                         f"(default {HEADLINE_PCT:.0f})")
    ap.add_argument("--phase-threshold", type=float, default=PHASE_PCT,
                    help="per-phase regression threshold in percent "
                         f"(default {PHASE_PCT:.0f})")
    ap.add_argument("--json", help="also write the full report here")
    args = ap.parse_args(argv)

    try:
        old, new = normalize(args.old), normalize(args.new)
        report = diff(old, new, args.threshold, args.phase_threshold)
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"benchdiff: {e}", file=sys.stderr)
        return 2

    h = report["headline"]
    print(f"benchdiff: {old['label']} -> {new['label']}")
    delta = ("n/a" if h["delta_pct"] is None
             else f"{h['delta_pct']:+.1f}%")
    if new.get("kind") == "multichip":
        print(f"  headline: {h['old']:.4g} -> {h['new']:.4g} "
              f"{h['unit']} ({delta}) at world={h.get('world')}")
    elif new.get("kind") == "forest":
        print(f"  headline: {h['old']:.4g} -> {h['new']:.4g} "
              f"{h['unit']} ({delta}) at num_models="
              f"{h.get('num_models')}")
    elif new.get("kind") == "train_fleet":
        aux = new.get("aux") or {}
        print(f"  headline: {h['old']:.4g} -> {h['new']:.4g} "
              f"{h['unit']} ({delta}) over "
              f"{aux.get('recoveries', 0)} recovery(ies), "
              f"{aux.get('lost_iterations', 0)} lost iteration(s)")
    elif new.get("kind") in ("serving", "fleet"):
        print(f"  headline: {h['old']:.4g} -> {h['new']:.4g} "
              f"{h['unit']} ({delta})")
    else:
        print(f"  headline: {h['old_s_per_tree']:.4f} -> "
              f"{h['new_s_per_tree']:.4f} s/tree ({delta})")
    for r in report["regressions"]:
        print(f"  REGRESSION: {r}")
    for w in report["warnings"]:
        print(f"  warning: {w}")
    for i in report["improvements"]:
        print(f"  improvement: {i}")
    if new.get("kind") not in ("serving", "multichip", "forest",
                               "fleet", "train_fleet"):
        print("  driver-config row (paste into the commit message):")
        print("  " + driver_row(new))

    if args.json:
        # atomic (tmp + rename, the resilience.atomic protocol inlined —
        # this tool stays dependency-free): a preempted benchdiff must
        # never leave half a JSON under the artifact name
        tmp = f"{args.json}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump({"old": old, "new": new, "report": report}, fh,
                      indent=1)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, args.json)
    # diff semantics: 1 means "differences (regressions) found"
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
