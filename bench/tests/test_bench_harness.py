"""``BENCHMARK.json`` resolves by name, keeps the contract's names and units,
and ``bench/run.py`` refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.harness import spec

pytestmark = pytest.mark.tier1

BENCH = spec.load()
RUN = spec.BENCH / "run.py"


FAMILY_API = ("draw", "build_one", "build_rows", "reference", "answers",
              "compare", "small")


def _at(tree: dict, path: str):
    for key in path.split("."):
        tree = tree[key]
    return tree


def cut_faults(entry: dict, cfg: dict) -> list[str]:
    """Why a configuration's cuts are not each tied to its published value:
    ``reduced`` agrees with ``BENCHMARK.json``'s entry, and each cut is a
    key path of ``deployment`` holding a number, one the source states
    (``stated_by_source``), with the published value (``published``) that
    it departs from."""
    faults = []
    if cfg.get("reduced") != entry["reduced"]:
        faults.append(f"reduced {cfg.get('reduced')!r} != {entry['reduced']!r}")
    for path in entry["reduced"]:
        try:
            value = _at(cfg["deployment"], path)
        except (KeyError, TypeError):
            faults.append(f"{path}: not a key path of deployment")
            continue
        published = cfg.get("published", {}).get(path)
        if path not in cfg.get("stated_by_source", []):
            faults.append(f"{path}: not stated by the source")
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            faults.append(f"{path}: not a number")
        elif not isinstance(published, (int, float)) or published == value:
            faults.append(f"{path}: no published value it departs from")
    return faults


def test_every_cell_resolves_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        cfg = spec.config(BENCH, w["config"])
        assert cfg["name"] == w["config"]
        family = spec.family(cfg)
        assert all(callable(getattr(family, f)) for f in FAMILY_API)
        assert isinstance(family.LIMITS, dict)
        assert cut_faults(configs[w["config"]], cfg) == []
        mix = spec.traffic(w["traffic"])
        assert mix["front_door"] in ("simulate", "run_campaign")
        assert w["chips"] in (1, 4)
        assert int(mix.get("mesh_devices", 0)) <= w["chips"]
        for m in spec.metrics_of(BENCH, w["name"], "per_layer"):
            assert callable(spec.metric_reader(m["name"]))
        e2e = {m["name"] for m in spec.metrics_of(BENCH, w["name"], "end_to_end")}
        assert {"setup_s", "scenarios_per_s"} <= e2e


def _cut(**change):
    cfg = spec.config(BENCH, "fig9_10")
    cfg["deployment"]["hosts"]["count"] = 800
    cfg.update(reduced=["hosts.count"], published={"hosts.count": 10000})
    cfg.update(change)
    return {"reduced": ["hosts.count"]}, cfg


@pytest.mark.parametrize("change,fault", [
    ({}, None),
    ({"reduced": []}, "reduced"),
    ({"published": {}}, "no published value"),
    ({"published": {"hosts.count": 800}}, "no published value"),
    ({"stated_by_source": []}, "not stated by the source"),
])
def test_a_cut_is_tied_to_its_published_value(change, fault):
    faults = cut_faults(*_cut(**change))
    assert (faults == []) if fault is None else any(fault in f for f in faults)


def test_a_cut_names_a_key_path_of_the_deployment():
    entry, cfg = _cut()
    entry["reduced"] = cfg["reduced"] = ["hosts.racks"]
    assert any("not a key path" in f for f in cut_faults(entry, cfg))


def test_names_units_and_files_keep_the_contract():
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[key]:
            assert spec.NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert spec.UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for text in ("why", "source", "layer"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert spec.NAME.match(w["traffic"]) and spec.NAME.match(w["config"])
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for path in spec.BENCH.rglob("*"):
        rel = path.relative_to(spec.ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./\-]+$", rel), rel
    assert len(json.dumps(BENCH)) < 64 * 1024


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig4.sweep",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300)


def test_refuses_to_run_without_a_tpu():
    proc = _run(spec.ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_to_run_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
