"""Where the benchmark's files are, and how a name finds one.

A later PR adds a configuration, a traffic mix, a chain builder, a
driver or a per-layer metric by adding a file and one entry in
BENCHMARK.json; nothing here lists them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
SPEC_PATH = os.path.join(REPO, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load_spec() -> dict:
    with open(SPEC_PATH) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_named(kind: str, name: str):
    """(module, path) of ``benchmarks/<kind>/<name>.py``, loaded by
    path, once, so that no package name has to be importable."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


def traffic_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", name + ".json")


def resolve_cell(spec: dict, workload: str):
    """(cell, config entry, configuration, traffic mix) by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"])
    return cell, entry, config, traffic


def cell_metrics(spec: dict, group: str, workload: str) -> list:
    """The metrics of ``end_to_end`` / ``per_layer`` this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]
