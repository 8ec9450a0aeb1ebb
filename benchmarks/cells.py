"""Find a cell's files by the names in BENCHMARK.json.  No JAX here."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return read_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    """The workload entry, its configuration file and its traffic file."""
    bench = benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
    return assemble(found[0], bench)


def assemble(work: dict, bench: dict) -> dict:
    """A cell from a workload entry, whether BENCHMARK.json lists it yet
    or not (a configuration whose cell is still to be proved)."""
    return {
        "workload": work,
        "config": read_json(HERE, "configs", work["config"] + ".json"),
        "traffic": read_json(HERE, "traffic", work["traffic"] + ".json"),
        "bench": bench,
    }


def metrics_of(bench: dict, section: str, workload: str) -> list:
    """The metrics of a section that this workload reports."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def plugin(kind: str, name: str):
    """``generators/<name>.py``, ``drivers/<name>.py``, ``counts/<name>.py``,
    ``references/<name>.py``: looked up by name, never branched on."""
    return importlib.import_module(f"{kind}.{name.replace('-', '_')}")
