"""CloudSim's power-aware consolidation family: one datacenter of host
classes with 11-point power tables, VMs of several types whose CPU demand
follows a utilisation series over a day of scheduling ticks, and an
overload detector per row (Beloglazov & Buyya, CCPE 24(13), 2012; CloudSim
3.0's ``power.planetlab`` examples).

The family contract is ``cloudsim_tasks``'s (``draw``, ``build_one``,
``build_rows``, ``reference``, ``answers``, ``compare``, ``LIMITS``,
``small``).  The traffic mix's own keys:

* ``policies``: ``{"detector": "THR" | "IQR" | "MAD", "param": s}``, each
  given to an equal share of the rows; the seed only orders them;
* ``sweep_impl``: the engine's advance sweep, ``jnp`` or ``pallas``.

The day is drawn from the seed (``make_day``), one per run, shared by all
rows: whole-percent samples whose marginal matches the published
statistics of the PlanetLab day the configuration names (mean, standard
deviation, quartiles; ``deployment.day.stats``), through a Gaussian copula
with a per-VM level (evenly spaced normal quantiles dealt in a seeded
order) and AR(1) noise between ticks.  The PlanetLab files are
not in the repository; the configuration lists this under ``assumed``.

Numbers compared against ``bench/reference/power.py`` (float64, the same
rows), per sweep of the window, only through ``run_campaign``:

* ``migrations_diff``: the summed ``n_migrations`` against the reference's
  (exact: every decision must agree);
* ``events_off``: the summed ``n_events`` against the reference's, one
  per tick (exact);
* ``hist_total_diff``, ``hist_excess_rows``: the energy histogram's total
  against the rows, and rows binned where no value within ``ENERGY_LIMIT``
  of the reference's lies (exact);
* ``best_err``: the relative gap (no floor on the denominator: ESV is far
  below 1) of the reported best ESV from the reference's, and of the
  reference's ESV at the reported row from its best;
* ``best_policy_diff``: detector and parameter drawn for the reported row
  against those of the reference's best row (lowest index on ties).

The limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtr, ndtri

from bench.harness.correct import HUGE
from bench.reference import fold, power

DETECTORS = {"THR": 0, "IQR": 1, "MAD": 2}
# relative gaps, each set between the program's readings on the chip and
# the bfloat16 control's (PERF.md §6): of energy, which float32 sums over
# whole tick intervals, and of ESV, whose SLATAH and PDM sum migration
# windows of a few seconds on a float32 clock
ENERGY_LIMIT = 1e-4
ESV_LIMIT = 1e-2
LIMITS = {
    "migrations_diff": 0,
    "events_off": 0,
    "hist_total_diff": 0,
    "hist_excess_rows": 0,
    "best_err": ESV_LIMIT,
    "best_policy_diff": 0,
}
# the CPU tests' size
SMALL = {"hosts": 40, "vms": 52, "ticks": 48, "rows": 6}
# the day's copula: share of a VM's variance that is its own level, and
# the correlation of its noise from one tick to the next; no published
# source states either (PERF.md §6 reads the reference under others)
LEVEL_SHARE, TICK_CORR = 0.6, 0.95


def _quantile_fn(stats: dict):
    """A quantile function through (0, 0), (1/4, q1), (1/2, median),
    (3/4, q3), linear between them, and ``q3 + M t^a`` above with ``M``,
    ``a`` fitted so the mean and standard deviation are the stated ones."""
    q = np.array([0.0, stats["q1"], stats["median"], stats["q3"]])
    lo_m1 = 0.25 * np.sum((q[:-1] + q[1:]) / 2)
    lo_m2 = 0.25 * np.sum((q[:-1] ** 2 + q[:-1] * q[1:] + q[1:] ** 2) / 3)
    mean, var = stats["mean"], stats["std"] ** 2
    r = (mean - lo_m1) / 0.25 - q[3]                       # M / (a + 1)
    s2 = (var + mean ** 2 - lo_m2) / 0.25 - q[3] ** 2 - 2 * q[3] * r
    c = s2 / r ** 2                                        # (a+1)^2 / (2a+1)
    a = (c - 1) + np.sqrt((c - 1) * c)
    M = r * (a + 1)

    def qf(p):
        p = np.asarray(p, np.float64)
        low = np.interp(p, [0, 0.25, 0.5, 0.75], q)
        high = q[3] + M * np.clip((p - 0.75) / 0.25, 0, 1) ** a
        return np.where(p <= 0.75, low, high)

    return qf


def make_day(config: dict, rng) -> np.ndarray:
    """``[V, K]`` int32 whole-percent utilisation, one row per VM."""
    dep = config["deployment"]
    V, K = dep["vms"]["count"], dep["day"]["ticks"]
    # each VM's level: evenly spaced normal quantiles, dealt in a seeded
    # order, so the day's spread across VMs does not hang on a few draws
    level = ndtri((rng.permutation(V) + 0.5) / V)
    noise = rng.standard_normal((V, K))
    e = np.empty((V, K))
    e[:, 0] = noise[:, 0]
    w = np.sqrt(1 - TICK_CORR ** 2)
    for k in range(1, K):
        e[:, k] = TICK_CORR * e[:, k - 1] + w * noise[:, k]
    z = np.sqrt(LEVEL_SHARE) * level[:, None] + np.sqrt(1 - LEVEL_SHARE) * e
    u = _quantile_fn(dep["day"]["stats"])(ndtr(z))
    return np.clip(np.rint(u), 0, 100).astype(np.int32)


def draw(config: dict, mix: dict, n: int, rng) -> dict:
    """``detector``, ``param`` for ``n`` rows and the day's ``util``."""
    pols = [(DETECTORS[p["detector"]], float(p["param"]))
            for p in mix["policies"]]
    which = rng.permutation(np.arange(n) % len(pols))
    return {"detector": np.array([pols[i][0] for i in which], np.int32),
            "param": np.array([pols[i][1] for i in which], np.float32),
            "util": make_day(config, rng)}


def _layout(config: dict):
    """Host classes and VM types of each row, as numpy columns."""
    dep = config["deployment"]
    h, v = dep["hosts"], dep["vms"]
    classes, types = h["classes"], v["types"]
    N, V = h["count"], v["count"]
    cls = np.arange(N) % len(classes)
    ty = np.arange(V) * len(types) // V
    col = lambda rows, idx, key: np.array([rows[i][key] for i in idx])  # noqa: E731
    return {
        "host_class": cls,
        "cores": col(classes, cls, "cores"), "mips": col(classes, cls, "mips"),
        "watts": np.array([c["watts"] for c in classes], np.float64),
        "vm_mips": col(types, ty, "mips"), "vm_ram": col(types, ty, "ram_mb"),
    }


def row_builder(config: dict, sweep_impl: str = "jnp"):
    """``build(detector, param, util) -> Scenario`` for one row."""
    from repro.core import Cloudlets, Hosts, Scenario, VMRequests, scenarios
    from repro.core.consolidate import Consolidation, ConsolidationPolicy
    from repro.core.entities import SPACE_SHARED, TIME_SHARED

    dep = config["deployment"]
    if dep["datacenters"] != 1:
        raise ValueError("deployments span one datacenter")
    h, v, day = dep["hosts"], dep["vms"], dep["day"]
    L = _layout(config)
    N, V = h["count"], v["count"]
    f32, i32 = jnp.float32, jnp.int32
    horizon = day["ticks"] * float(day["interval_s"])
    # every move must land before the next tick (DESIGN.md §15.3)
    if max(L["vm_ram"]) / (h["bw_mbps"] / 16.0) >= day["interval_s"]:
        raise ValueError("a migration would outlast the scheduling interval")

    def build(detector, param, util):
        hosts = Hosts(
            cores=jnp.asarray(L["cores"][None], i32),
            mips=jnp.asarray(L["mips"][None], f32),
            ram_mb=jnp.full((1, N), h["ram_mb"], f32),
            storage_mb=jnp.full((1, N), h["storage_mb"], f32),
            bw_mbps=jnp.full((1, N), h["bw_mbps"], f32),
            kv_blocks=jnp.zeros((1, N), f32),
            exists=jnp.ones((1, N), bool))
        vms = VMRequests(
            dc=jnp.zeros((V,), i32), cores=jnp.full((V,), v["cores"], i32),
            mips=jnp.asarray(L["vm_mips"], f32),
            ram_mb=jnp.asarray(L["vm_ram"], f32),
            storage_mb=jnp.full((V,), v["storage_mb"], f32),
            bw_mbps=jnp.full((V,), v["bw_mbps"], f32),
            kv_blocks=jnp.zeros((V,), f32), request_t=jnp.zeros((V,), f32),
            image_mb=jnp.asarray(L["vm_ram"], f32),
            exists=jnp.ones((V,), bool), pool=jnp.zeros((V,), bool))
        cls = Cloudlets(
            vm=jnp.arange(V, dtype=i32),
            length_mi=jnp.full((V,), dep["cloudlet_length_mi"], f32),
            cores=jnp.ones((V,), i32), submit_t=jnp.zeros((V,), f32),
            input_mb=jnp.zeros((V,), f32), input_dc=jnp.full((V,), -1, i32),
            output_mb=jnp.zeros((V,), f32), deadline=jnp.full((V,), 3.0e38, f32),
            prompt_tokens=jnp.zeros((V,), f32),
            max_new_tokens=jnp.zeros((V,), f32), exists=jnp.ones((V,), bool))
        consol = Consolidation.build(
            util, L["host_class"][None], L["watts"],
            (L["cores"] * L["mips"])[None],
            ConsolidationPolicy(detector=jnp.asarray(detector, i32),
                                param=jnp.asarray(param, f32)),
            day["interval_s"])
        return Scenario(
            hosts=hosts, vms=vms, cloudlets=cls,
            market=scenarios.uniform_market(1),
            policy=scenarios.make_policy(host_policy=TIME_SHARED,
                                         vm_policy=SPACE_SHARED,
                                         horizon=horizon),
            dynamic_consolidation=consol, max_steps=int(dep["max_steps"]),
            sweep_impl=sweep_impl)

    return build


def build_rows(config: dict, params: dict, mix: dict):
    """Every row as one stacked ``Scenario`` on the device, one jitted
    call; the day is shared, each row holds its copy."""
    build = row_builder(config, mix["sweep_impl"])
    return jax.jit(jax.vmap(build, in_axes=(0, 0, None)))(
        jnp.asarray(params["detector"]), jnp.asarray(params["param"]),
        jnp.asarray(params["util"]))


def build_one(config: dict, params: dict, i: int, mix: dict):
    build = row_builder(config, mix["sweep_impl"])
    return jax.jit(build)(jnp.int32(params["detector"][i]),
                          jnp.float32(params["param"][i]),
                          jnp.asarray(params["util"]))


def reference(config: dict, params: dict, dtype=np.float64) -> dict:
    return power.simulate_rows(power.rows_from_config(config, params), dtype)


def answers(outputs, mix: dict):
    if mix["front_door"] != "run_campaign":
        raise ValueError("the power family is checked through run_campaign")
    return jax.device_get([o for _, o in outputs])


def _rel(prog, ref) -> float:
    """``|prog - ref| / |ref|``: ESV is far below 1, so no floor of 1 on
    the denominator; 0 against 0 is no gap."""
    prog, ref = float(prog), float(ref)
    if prog == ref:
        return 0.0
    gap = abs(prog - ref) / abs(ref) if ref else HUGE
    return gap if np.isfinite(gap) else HUGE


def best_row(values: np.ndarray, mode: str = "min") -> int:
    """The reference's best row, lowest index on ties."""
    v = np.asarray(values, np.float64)
    return int(np.argmin(v) if mode == "min" else np.argmax(v))


def compare(answers, ref: dict, params: dict, mix: dict) -> tuple[dict, int]:
    """``({number: worst value}, sweeps failing a limit)``."""
    worst = dict.fromkeys(LIMITS, 0)
    failed = 0
    n = len(ref["n_events"])
    for a in answers:
        nums = {}
        for key, r in mix["reduce"].items():
            got = a[key]
            metric = r["metric"]
            if r["kind"] == "sum":
                name = {"n_migrations": "migrations_diff",
                        "n_events": "events_off"}[metric]
                nums[name] = abs(int(got) - int(ref[metric].sum()))
            elif r["kind"] == "histogram":
                counts = np.asarray(got["counts"])
                nums["hist_total_diff"] = abs(int(counts.sum()) - n)
                nums["hist_excess_rows"] = fold.histogram_excess(
                    counts, ref[metric], float(r["lo"]), float(r["hi"]),
                    int(r["bins"]), ENERGY_LIMIT)
            elif r["kind"] == "argbest":
                v = np.asarray(ref[metric], np.float64)
                b = best_row(v, r.get("mode", "min"))
                idx = int(got["index"])
                at = v[idx] if 0 <= idx < n else np.inf
                nums["best_err"] = float(max(_rel(got["value"], v[b]),
                                             _rel(at, v[b])))
                nums["best_policy_diff"] = 2 if not 0 <= idx < n else (
                    int(params["detector"][idx] != params["detector"][b])
                    + int(params["param"][idx] != params["param"][b]))
        failed += any(v > LIMITS[k] for k, v in nums.items())
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def small(config: dict, mix: dict) -> tuple[dict, dict]:
    """Copies of ``config`` and ``mix`` cut to ``SMALL``: a day of 48
    ticks, every other policy of the mix."""
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    dep = config["deployment"]
    dep["hosts"]["count"] = SMALL["hosts"]
    dep["vms"]["count"] = SMALL["vms"]
    dep["day"]["ticks"] = SMALL["ticks"]
    dep["max_steps"] = SMALL["ticks"] + 8
    mix["policies"] = mix["policies"][::2]
    mix["rows"] = mix["chunk_size"] = SMALL["rows"]
    return config, mix
