"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<traffic>.json``); a configuration names its deployment
family (``bench/families/<family>.py``: what its rows are, how they are
drawn, built and checked); every metric of a cell is read by
``bench/metrics/<metric>.py`` where it is a per-layer metric.  A later
change adds a configuration, a family, a mix or a metric by adding files
and entries, and edits none of these.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAMILIES = BENCH / "families"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def family(config: dict):
    """The deployment family module ``FAMILIES/<family>.py`` that a
    configuration names; naming none, or one with no file, is an error."""
    name = config.get("family")
    if not isinstance(name, str) or not NAME.match(name):
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"deployment family (\"family\": {name!r})")
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config.get('name')!r} names "
                                f"family {name!r}, and there is no {path}")
    return _module(path, f"bench_family_{name}")


def metrics_of(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
