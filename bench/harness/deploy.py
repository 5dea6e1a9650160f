"""A configuration's deployment as the engine's ``Scenario`` pytrees.

Built here from the configuration's numbers and the engine's public entity
types, not from the program's scenario presets, so that a change to a
preset cannot move the yardstick.  The rows of a sweep differ only in the
traced values each row draws (host policy, VM policy, task-length scale):
one jitted call builds all of them on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

POLICIES = {"space_shared": 0, "time_shared": 1}


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


def build_rows(config: dict, params: dict, sweep_impl: str = "jnp"):
    """Every row of ``params`` as one stacked ``Scenario`` on the device,
    made in one jitted call."""
    build = row_builder(config, sweep_impl)
    args = tuple(jnp.asarray(params[k]) for k in
                 ("host_policy", "vm_policy", "length_scale"))
    return jax.jit(jax.vmap(build))(*args)


def build_one(config: dict, host_policy: int, vm_policy: int,
              length_scale: float, sweep_impl: str = "jnp"):
    """One row as an unbatched ``Scenario`` on the device."""
    build = row_builder(config, sweep_impl)
    return jax.jit(build)(jnp.int32(host_policy), jnp.int32(vm_policy),
                          jnp.float32(length_scale))
