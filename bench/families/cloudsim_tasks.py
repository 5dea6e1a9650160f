"""The paper's task family: one datacenter of uniform hosts and VMs, tasks
submitted in groups, and a host and a VM scheduling policy per row.

A deployment family is what the harness needs to know of one kind of
configuration (``"family"`` in its file names this module):

* ``draw(config, mix, n, rng)``: the traced values of ``n`` rows;
* ``build_one(config, params, i, mix)``, ``build_rows(config, params,
  mix)``: row ``i`` as a ``Scenario``, and all rows stacked on the device;
* ``reference(config, params)``: the plain reference over the same rows;
* ``answers(outputs, mix)``, ``compare(answers, ref, params, mix)`` and
  ``LIMITS``: what is compared, and each number's limit;
* ``small(config, mix)``: the size the CPU tests run at.

Here the traffic mix's own keys are:

* ``policies``: the host/VM policy pairs, each given to an equal share of
  the rows (keys left out take the configuration's value); the seed only
  orders them, so every seed asks for the same work;
* ``length_scale``: ``[lo, hi]``, a task-length multiplier per row, uniform;
* ``sweep_impl``: the engine's advance sweep, ``jnp`` or ``pallas``.

The rows are built from the configuration's numbers and the engine's
public entity types, not from the program's scenario presets, so that a
change to a preset cannot move the yardstick.  Rows differ only in the
traced values each row draws: one jitted call builds all of them.

Numbers compared against the reference (``bench/reference``), which runs
once the window has closed, over the same drawn rows, in float64:

* ``time_err``: the largest relative gap of a task's start or finish time,
  ``|program - reference| / max(reference, 1 s)``, over every task of every
  answer (``simulate`` cells);
* ``finished_diff``: tasks finished, program against reference (exact);
* ``events_off``: event batches outside ``[reference, reference + slack]``,
  the slack being the batches a float32 engine may split off an instant
  where kinds of event meet in exact arithmetic (exact);
* sweeps, for every sweep of the window: ``events_off`` of the summed
  ``n_events``; ``hist_total_diff``, rows the histogram counts against rows
  in the grid (exact); ``hist_excess_rows``, rows binned where no value
  within ``time_err``'s limit of the reference lies (exact); ``best_err``,
  the relative gap of the reported best value from the reference's, and of
  the reference's value at the reported index from its best;
  ``best_policy_diff``, policy fields of the reported best row that differ
  from the grid's row at that index (exact).

The limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.correct import _events_off, _rel
from bench.reference import fold, sim

POLICIES = {"space_shared": 0, "time_shared": 1}

# A relative gap of times: set between the program's readings on the chip
# and the bfloat16 control's (PERF.md, "Limits of the check").
TIME_LIMIT = 1e-5
LIMITS = {
    "time_err": TIME_LIMIT,
    "finished_diff": 0,
    "events_off": 0,
    "hist_total_diff": 0,
    "hist_excess_rows": 0,
    "best_err": TIME_LIMIT,
    "best_policy_diff": 0,
}
# the CPU tests' size: a Figure 9/10 deployment cut to this many hosts, VMs
# and task groups, and a sweep to this many rows in chunks of this size
SMALL = {"hosts": 200, "vms": 10, "groups": 4, "rows": 128, "chunk_size": 64}


def draw(config: dict, mix: dict, n: int, rng) -> dict:
    """``host_policy``, ``vm_policy``, ``length_scale`` for ``n`` rows."""
    pol = config["deployment"]["policy"]
    pairs = [(POLICIES[p.get("host_policy", pol["host_policy"])],
              POLICIES[p.get("vm_policy", pol["vm_policy"])])
             for p in mix["policies"]]
    which = rng.permutation(np.arange(n) % len(pairs))
    lo, hi = mix["length_scale"]
    scale = rng.uniform(lo, hi, n) if hi > lo else np.full(n, float(lo))
    return {"host_policy": np.array([pairs[i][0] for i in which], np.int32),
            "vm_policy": np.array([pairs[i][1] for i in which], np.int32),
            "length_scale": scale.astype(np.float32)}


def task_layout(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """``(vm, submit_t)`` of each task, in submission order."""
    k = config["deployment"]["tasks"]
    v = config["deployment"]["vms"]["count"]
    i = np.arange(k["count"])
    if k["binding"] == "round_robin":
        vm = i % v
    elif k["binding"] == "contiguous":
        vm = i // (k["count"] // v)
    else:
        raise ValueError(f"unknown task binding {k['binding']!r}")
    submit = (i // k["group_size"]) * float(k["group_interval_s"])
    return vm.astype(np.int32), submit.astype(np.float32)


def row_builder(config: dict, sweep_impl: str = "jnp"):
    """``build(host_policy, vm_policy, length_scale) -> Scenario`` for one
    row; vmap it for a sweep."""
    from repro.core import Cloudlets, Scenario, scenarios

    dep = config["deployment"]
    h, v, k, pol, mk = (dep["hosts"], dep["vms"], dep["tasks"], dep["policy"],
                        dep["market"])
    if dep["datacenters"] != 1:
        raise ValueError("deployments span one datacenter")
    cl_vm, submit = task_layout(config)
    C = k["count"]

    def build(host_policy, vm_policy, length_scale):
        hosts = scenarios.uniform_hosts(
            1, h["count"], cores=h["cores"], mips=h["mips"], ram_mb=h["ram_mb"],
            storage_mb=h["storage_mb"], bw_mbps=h["bw_mbps"])
        vms = scenarios.uniform_vms(
            v["count"], cores=v["cores"], mips=v["mips"], ram_mb=v["ram_mb"],
            storage_mb=v["storage_mb"], bw_mbps=v["bw_mbps"],
            request_t=v["request_t"], image_mb=v["image_mb"])
        f32 = jnp.float32
        cls = Cloudlets(
            vm=jnp.asarray(cl_vm),
            length_mi=jnp.full((C,), k["length_mi"], f32) * length_scale,
            cores=jnp.full((C,), k["cores"], jnp.int32),
            submit_t=jnp.asarray(submit),
            input_mb=jnp.full((C,), k["input_mb"], f32),
            input_dc=jnp.full((C,), -1, jnp.int32),
            output_mb=jnp.full((C,), k["output_mb"], f32),
            deadline=jnp.full((C,), 3.0e38, f32),
            prompt_tokens=jnp.zeros((C,), f32),
            max_new_tokens=jnp.zeros((C,), f32),
            exists=jnp.ones((C,), bool),
        )
        policy = scenarios.make_policy(
            host_policy=host_policy, vm_policy=vm_policy,
            core_reserving=pol["core_reserving"], best_fit=pol["best_fit"],
            horizon=pol["horizon_s"])
        market = scenarios.uniform_market(
            1, cpu=mk["cpu_per_s"], ram=mk["ram_per_mb"],
            storage=mk["storage_per_mb"], bw=mk["bw_per_mb"])
        return Scenario(hosts=hosts, vms=vms, cloudlets=cls, market=market,
                        policy=policy, sweep_impl=sweep_impl)

    return build


def build_rows(config: dict, params: dict, mix: dict):
    """Every row of ``params`` as one stacked ``Scenario`` on the device,
    made in one jitted call."""
    build = row_builder(config, mix["sweep_impl"])
    args = tuple(jnp.asarray(params[k]) for k in
                 ("host_policy", "vm_policy", "length_scale"))
    return jax.jit(jax.vmap(build))(*args)


def build_one(config: dict, params: dict, i: int, mix: dict):
    """Row ``i`` as an unbatched ``Scenario`` on the device."""
    build = row_builder(config, mix["sweep_impl"])
    return jax.jit(build)(jnp.int32(params["host_policy"][i]),
                          jnp.int32(params["vm_policy"][i]),
                          jnp.float32(params["length_scale"][i]))


def reference(config: dict, params: dict, dtype=np.float64) -> dict:
    return sim.simulate_rows(sim.rows_from_config(config, params), dtype)


def answers(outputs, mix: dict):
    """What each question of the window answered, on the host: per
    ``simulate`` call ``(pool row, answer)``, per sweep its folded answer."""
    if mix["front_door"] == "simulate":
        got = jax.device_get([{"start_t": r.start_t, "finish_t": r.finish_t,
                               "n_finished": r.n_finished,
                               "n_events": r.n_events}
                              for _, r in outputs])
        return [(i, a) for (i, _), a in zip(outputs, got)]
    return jax.device_get([o for _, o in outputs])


def compare(answers, ref: dict, params: dict, mix: dict) -> tuple[dict, int]:
    """``({number: worst value}, answers failing a limit)``."""
    if mix["front_door"] == "simulate":
        return compare_simulate(answers, ref)
    return compare_campaign(answers, ref, params, mix)


def compare_simulate(answers, ref: dict) -> tuple[dict, int]:
    worst = dict.fromkeys(("time_err", "finished_diff", "events_off"), 0)
    failed = 0
    for i, a in answers:
        nums = {
            "time_err": float(max(_rel(a["start_t"], ref["start_t"][i]).max(),
                                  _rel(a["finish_t"], ref["finish_t"][i]).max())),
            "finished_diff": abs(int(a["n_finished"]) - int(ref["n_finished"][i])),
            "events_off": _events_off(int(a["n_events"]), int(ref["n_events"][i]),
                                      int(ref["event_slack"][i])),
        }
        failed += any(v > LIMITS[k] for k, v in nums.items())
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def compare_campaign(answers, ref: dict, params: dict, mix: dict) -> tuple[dict, int]:
    names = ("events_off", "hist_total_diff", "hist_excess_rows", "best_err",
             "best_policy_diff")
    worst = dict.fromkeys(names, 0)
    failed = 0
    n = len(ref["n_events"])
    for a in answers:
        nums = {}
        for key, r in mix["reduce"].items():
            got = a[key]
            v = np.asarray(ref[r["metric"]], np.float64)
            if r["kind"] == "sum":
                nums["events_off"] = _events_off(
                    int(got), int(ref["n_events"].sum()),
                    int(ref["event_slack"].sum()))
            elif r["kind"] == "histogram":
                counts = np.asarray(got["counts"])
                nums["hist_total_diff"] = abs(int(counts.sum()) - n)
                nums["hist_excess_rows"] = fold.histogram_excess(
                    counts, v, float(r["lo"]), float(r["hi"]), int(r["bins"]),
                    TIME_LIMIT)
            elif r["kind"] == "argbest":
                best = v.min() if r.get("mode", "min") == "min" else v.max()
                idx = int(got["index"])
                at = v[idx] if 0 <= idx < n else np.inf
                nums["best_err"] = float(max(_rel(got["value"], best),
                                             _rel(at, best)))
                pol = got["policy"]
                nums["best_policy_diff"] = 2 if not 0 <= idx < n else (
                    int(int(pol.host_policy) != params["host_policy"][idx])
                    + int(int(pol.vm_policy) != params["vm_policy"][idx]))
        failed += any(v > LIMITS[k] for k, v in nums.items())
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def small(config: dict, mix: dict) -> tuple[dict, dict]:
    """Copies of ``config`` and ``mix`` cut to ``SMALL`` where larger."""
    config, mix = copy.deepcopy(config), dict(mix)
    dep = config["deployment"]
    dep["hosts"]["count"] = min(dep["hosts"]["count"], SMALL["hosts"])
    v, k = dep["vms"], dep["tasks"]
    if v["count"] > SMALL["vms"]:
        v["count"] = SMALL["vms"]
        k["count"], k["group_size"] = SMALL["groups"] * v["count"], v["count"]
    if mix["front_door"] == "run_campaign":
        mix["rows"] = min(int(mix["rows"]), SMALL["rows"])
        mix["chunk_size"] = min(int(mix["chunk_size"]), SMALL["chunk_size"])
    return config, mix
