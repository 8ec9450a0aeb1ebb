#!/usr/bin/env python
"""jaxlint CLI: JAX-aware lint + compiled-artifact audit gate.

Usage:
    python tools/jaxlint.py                  # all 3 stages over lightgbm_tpu/
    python tools/jaxlint.py --ast-only path/to/file.py
    python tools/jaxlint.py --artifacts-only # stage 2 (CPU trace/compile)
    python tools/jaxlint.py --concurrency-only  # stage 3 (lock discipline)
    python tools/jaxlint.py --list-rules

Exit status 0 = clean, 1 = findings (from ANY stage), 2 = audit
machinery error.

Writes ``COPYCHECK.json`` (schema: {"threshold", "flagged", "error"},
the pre-existing artifact contract) with each finding as
{"rule", "path", "line", "message"} in ``flagged``; extra keys carry
the rule table and the measured HLO op counts for trend tracking.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs for the AST stage "
                         "(default: lightgbm_tpu/)")
    ap.add_argument("--ast-only", action="store_true",
                    help="stage 1 only (pure-AST lint)")
    ap.add_argument("--artifacts-only", action="store_true",
                    help="stage 2 only (compiled-artifact audit)")
    ap.add_argument("--concurrency-only", action="store_true",
                    help="stage 3 only (lock-discipline lint)")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' disables; "
                         "default: the repo COPYCHECK.json for FULL "
                         "runs only — a scoped run must not clobber "
                         "the committed full-audit artifact)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args()

    from lightgbm_tpu.analysis import (
        ARTIFACT_RULES, AST_RULES, CONCURRENCY_RULES, audit_artifacts,
        lint_concurrency_paths, lint_paths)

    if args.list_rules:
        for rid, desc in {**AST_RULES, **ARTIFACT_RULES,
                          **CONCURRENCY_RULES}.items():
            print(f"{rid}\n    {desc}")
        return 0

    only_flags = (args.ast_only, args.artifacts_only,
                  args.concurrency_only)
    if sum(only_flags) > 1:
        ap.error("--ast-only/--artifacts-only/--concurrency-only "
                 "are mutually exclusive")
    run_ast = not (args.artifacts_only or args.concurrency_only)
    run_artifacts = not (args.ast_only or args.concurrency_only)
    run_concurrency = not (args.ast_only or args.artifacts_only)

    if args.json is None:
        full_run = not (any(only_flags) or args.paths)
        args.json = (os.path.join(ROOT, "COPYCHECK.json") if full_run
                     else "")

    findings = []
    measured = {}
    error = ""

    paths = args.paths or [os.path.join(ROOT, "lightgbm_tpu")]
    if run_ast:
        findings.extend(lint_paths(paths))
    if run_concurrency:
        findings.extend(lint_concurrency_paths(paths))

    if run_artifacts:
        # the artifact audit traces/compiles on CPU whatever the outer
        # environment points at: budgets are CPU-backend numbers, and
        # lint must not take a chip.  FORCE the platform, by env var and
        # by config (the env var alone is read only at jax's import)
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        try:
            measured, artifact_findings = audit_artifacts()
            findings.extend(artifact_findings)
        except Exception as e:  # machinery failure, not a finding
            error = f"{type(e).__name__}: {e}"

    rel = []
    for f in findings:
        d = f.as_dict()
        # join() returns absolute paths unchanged, so one expression
        # covers both relative and absolute finding paths
        d["path"] = os.path.relpath(
            os.path.join(os.getcwd(), d["path"]), ROOT)
        rel.append(d)

    if args.json:
        out = {
            "threshold": 0.6,
            "flagged": rel,
            "error": error,
            "measured_hlo": {
                k: v.get("ops", v.get("error"))
                for k, v in measured.items()
            },
        }
        from lightgbm_tpu.resilience.atomic import atomic_write_json

        atomic_write_json(args.json, out, indent=2)

    for d in rel:
        print(f"{d['path']}:{d['line']}: [{d['rule']}] {d['message']}")
    if error:
        print(f"jaxlint: audit error: {error}", file=sys.stderr)
        return 2
    n = len(rel)
    print(f"jaxlint: {n} finding{'s' if n != 1 else ''}")
    return 1 if rel else 0


if __name__ == "__main__":
    sys.exit(main())
