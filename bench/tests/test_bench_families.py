"""Deployment families: the paper's task family draws and checks exactly
what the harness did before it moved behind the family contract, and a new
family with its configuration runs through ``cell.run`` as new files alone,
with no file of ``bench/`` edited."""
import hashlib
import json
import time

import numpy as np
import pytest

import faults
from bench.harness import cell, spec, traffic

pytestmark = pytest.mark.tier1

BENCH = spec.load()
SEEDS = (1, 7, 2**31 + 7)
ACCEPTED = ("fig9_10.single", "fig4.sweep")

# sha256 of each drawn params dict (key, dtype, bytes; keys sorted) and the
# check lines of a CPU run at the family's small size, both recorded from
# the harness as it stood before the task family moved out of it
DRAWN = {
    ("fig9_10.single", 1): "cbaa432cc41c306d0c7df7da9bd02de55a17f7e865a618a8337eaa489a0e3a39",
    ("fig9_10.single", 7): "cbaa432cc41c306d0c7df7da9bd02de55a17f7e865a618a8337eaa489a0e3a39",
    ("fig9_10.single", 2**31 + 7): "71a61730d49ffb8f802f2fe448e5e265a527d1b85544c1e578cb1bc7f004c590",
    ("fig4.sweep", 1): "1aeee9ea2677ce761848aee868b959394a8f4c807662344a8457c8779bfa1aca",
    ("fig4.sweep", 7): "40ffcea8e7c65c64e10e4db084a6195202013ccb8a12566acd835217b81da1ca",
    ("fig4.sweep", 2**31 + 7): "0381122cc4700568330e5c376c67353e28528cda73d911e5d55df7b77ae959d8",
}
SINGLE = [["time_err", 5.8593603456874204e-08, 1e-05],
          ["finished_diff", 0, 0], ["events_off", 0, 0]]


def _sweep(best_err):
    return [["events_off", 0, 0], ["hist_total_diff", 0, 0],
            ["hist_excess_rows", 0, 0], ["best_err", best_err, 1e-05],
            ["best_policy_diff", 0, 0]]


CHECKS = {
    ("fig9_10.single", 1): SINGLE,
    ("fig9_10.single", 7): SINGLE,
    ("fig9_10.single", 2**31 + 7): SINGLE,
    ("fig4.sweep", 1): _sweep(2.3642934007724382e-08),
    ("fig4.sweep", 7): _sweep(1.3316956647361044e-08),
    ("fig4.sweep", 2**31 + 7): _sweep(5.11353738129475e-08),
}


def _digest(params: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(params):
        a = np.ascontiguousarray(params[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell_name", ACCEPTED)
@pytest.mark.parametrize("seed", SEEDS)
def test_task_family_draws_what_the_harness_drew(cell_name, seed):
    w = spec.cell(BENCH, cell_name)
    cfg, mix = spec.config(BENCH, w["config"]), spec.traffic(w["traffic"])
    n = int(mix["pool"] if mix["front_door"] == "simulate" else mix["rows"])
    params = spec.family(cfg).draw(cfg, mix, n, traffic.rng_for(seed))
    assert _digest(params) == DRAWN[cell_name, seed]


@pytest.mark.parametrize("cell_name", ACCEPTED)
@pytest.mark.parametrize("seed", SEEDS)
def test_task_family_checks_what_the_harness_checked(cell_name, seed):
    cfg, mix = faults.small(BENCH, cell_name)
    result, checks = cell.run(BENCH, cell_name, seed, 1.0, False,
                              time.perf_counter(), config=cfg, mix=mix)
    assert result["correct"]
    got = [[k, c["value"], c["limit"]] for k, c in checks.items()]
    assert got == CHECKS[cell_name, seed]


@pytest.mark.parametrize("config,error", [
    ({"name": "x"}, KeyError),
    ({"name": "x", "family": "no_such_family"}, FileNotFoundError),
])
def test_a_configuration_must_name_a_family_that_exists(config, error):
    with pytest.raises(error):
        spec.family(config)


# A family of a few lines over the task family's engine calls that
# compares one number, the makespan of each simulate call; SKEW plants a
# wrong reference.
NEW_FAMILY = '''
import jax
import numpy as np

from bench.families import cloudsim_tasks as tasks

LIMITS = {{"makespan_err": 1e-5}}
SKEW = {skew!r}


def draw(config, mix, n, rng):
    return {{"host_policy": np.zeros(n, np.int32),
            "vm_policy": np.arange(n, dtype=np.int32) % 2,
            "length_scale": rng.uniform(1.0, 1.5, n).astype(np.float32)}}


def build_one(config, params, i, mix):
    return tasks.build_one(config, params, i, mix)


def build_rows(config, params, mix):
    return tasks.build_rows(config, params, mix)


def reference(config, params):
    return tasks.reference(config, params)["makespan"] * SKEW


def answers(outputs, mix):
    return [(i, float(jax.device_get(r.makespan))) for i, r in outputs]


def compare(answers, ref, params, mix):
    gaps = [abs(m - ref[i]) / ref[i] for i, m in answers]
    return ({{"makespan_err": max(gaps)}},
            sum(int(g > LIMITS["makespan_err"]) for g in gaps))


def small(config, mix):
    return config, mix
'''


@pytest.mark.parametrize("skew,correct", [(1.0, True), (1.01, False)])
def test_a_new_family_runs_as_new_files_alone(tmp_path, monkeypatch, skew,
                                              correct):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "makespan_only.py").write_text(
        NEW_FAMILY.format(skew=skew))
    cfg = spec.config(BENCH, "fig9_10")
    cfg.update(name="tiny", family="makespan_only")
    dep = cfg["deployment"]
    dep["hosts"]["count"], dep["vms"]["count"] = 20, 4
    dep["tasks"]["count"], dep["tasks"]["group_size"] = 8, 4
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    mix = {"front_door": "simulate", "pool": 2, "sweep_impl": "jnp",
           "trace_seconds": 1.0}
    bench = {"configs": [{"name": "tiny", "file": "tiny.json", "reduced": []}],
             "workloads": [{"name": "tiny.single", "config": "tiny",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "FAMILIES", tmp_path / "families")

    bench = spec.load(tmp_path)
    result, checks = cell.run(bench, "tiny.single", 2**31 + 3, 0.5, False,
                              time.perf_counter(),
                              config=spec.config(bench, "tiny", tmp_path),
                              mix=mix)
    assert list(checks) == ["makespan_err"]
    assert checks["makespan_err"]["limit"] == 1e-5
    assert result["attempted"] > 0
    assert result["correct"] is correct, checks
