"""Two-level space/time-shared scheduling (paper §3.2, Figure 4).

CloudSim schedules at two levels, each independently space- or time-shared:

* **host -> VM** (``VMMAllocationPolicy``): how a host's cores are granted to
  the VMs placed on it.
* **VM -> cloudlet** (``VMScheduling``): how a VM's granted capacity is
  divided among its task units.

Both levels reduce to one statement: *given the entity set, produce a MIPS
rate vector*.  Rates are piecewise-constant between events, so the engine
advances all work with ``rem -= rate * dt`` — this function pair IS the
paper's ``updateVMsProcessing()``/``updateGridletsProcessing()`` sweep,
re-derived as dataflow.

Space-shared = FCFS core occupancy (exclusive, queue otherwise) — Figure 4a/c.
Time-shared  = proportional share of capacity, capped at demand — Figure 4b/d.

Both variants are always computed and selected with ``where`` on the traced
policy flag, so a single compilation serves all four Figure-4 combinations
and campaigns may vmap over policies.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import Array

from repro.core.entities import INF, TIME_SHARED, Scenario, SimState
from repro.core import consolidate, segments


def cloudlet_ready(scn: Scenario, state: SimState) -> Array:
    """[C] bool — dispatched and staged-in (SANStorage input transfer done).

    ``cl_ready_t`` is state, not schedule: fixed-binding rows carry their
    precomputed submit + stage-in time from ``init_state``; service-routed
    rows hold INF until the broker dispatches them (step.py).
    """
    return (state.t >= state.cl_ready_t) & scn.cloudlets.exists


def cloudlet_finished(state: SimState) -> Array:
    return state.finish_t < INF / 2


def vm_done(scn: Scenario, state: SimState) -> Array:
    """[V] bool — VM has work assigned and all of it has finished.

    A "done" VM releases its cores (CloudSim destroys VMs whose workload
    completed) — this is what lets Figure 4a's VM2 start after VM1 drains.
    VMs with no cloudlets idle forever (broker never destroys them here).

    Two auto-scaling refinements (DESIGN.md §7): while any service-routed
    cloudlet is still undispatched, no VM is done — every eligible VM is a
    potential dispatch target, and destroying drained VMs could leave a late
    service burst with an empty fleet (service rows would never run).  The
    cost is deliberate: in mixed fixed+service scenarios, a drained
    fixed-binding VM holds its slot until the last service row dispatches.
    And pool VMs are destroyed only by the autoscaler's scale-down
    (``provision.release_pool_vms``, which returns the row to the inactive
    pool state so it can be re-activated later), never by workload drain —
    an idle pool VM holds its slot until utilization says otherwise.
    """
    V = scn.vms.n_vms
    assigned = state.cl_vm >= 0
    cl_fin = cloudlet_finished(state) | ~scn.cloudlets.exists
    seg = jnp.where(scn.cloudlets.exists & assigned, state.cl_vm, V)
    all_fin = segments.segment_all(cl_fin, seg, V)
    has_work = segments.segment_sum(
        (scn.cloudlets.exists & assigned).astype(jnp.float32), seg, V
    ) > 0
    pending = jnp.any(scn.cloudlets.exists & ~assigned)
    done = has_work & all_fin & ~pending
    return jnp.where(scn.vms.pool, state.vm_released, done)


def sla_violation_mask(scn: Scenario, state: SimState) -> Array:
    """[C] bool — existing cloudlet with a real deadline (< INF) that
    finished past it, or never finished at all (finish_t stuck at INF).

    The SLA ledger of DESIGN.md §9: ``finalize_result`` sums this into
    ``SimResult.sla_violations``, so vmapped campaigns get per-row violation
    counts for MTBF x ckpt x policy grids with no post-processing.
    """
    cls = scn.cloudlets
    return (
        cls.exists
        & (cls.deadline < INF / 2)
        & (state.finish_t > cls.deadline)
    )


def vm_outstanding_mi(scn: Scenario, state: SimState) -> Array:
    """[V] assigned-but-unfinished remaining MI per VM.

    The broker's dispatch load key and the migration policies' "how much work
    rides on this VM" signal share this reduction.
    """
    V = scn.vms.n_vms
    seg = jnp.where(scn.cloudlets.exists & (state.cl_vm >= 0), state.cl_vm, V)
    return segments.segment_sum(
        jnp.where(cloudlet_finished(state), 0.0, state.rem_mi), seg, V
    )


def vm_demand_mips(scn: Scenario, state: SimState) -> Array:
    """[V] MIPS demanded right now: each ready, unfinished cloudlet wants
    ``cores`` of its VM's per-core MIPS whether or not the host throttles it
    (queued work counts fully — run-queue pressure, DESIGN.md §7/§8).
    """
    cls, vms = scn.cloudlets, scn.vms
    V = vms.n_vms
    want = cls.exists & cloudlet_ready(scn, state) & ~cloudlet_finished(state)
    seg = jnp.where(want & (state.cl_vm >= 0), state.cl_vm, V)
    cores = segments.segment_sum(
        jnp.where(want, cls.cores.astype(jnp.float32), 0.0), seg, V
    )
    return cores * vms.mips


def host_level_mips(scn: Scenario, state: SimState) -> Array:
    """[V] f32 — total MIPS each VM is granted by its host right now."""
    if scn.dynamic_consolidation is not None:
        # demand follows each VM's utilisation series (DESIGN.md §15)
        return consolidate.vm_grant(scn, state)
    hosts, vms = scn.hosts, scn.vms
    D, H = hosts.cores.shape
    n_seg = D * H

    done = vm_done(scn, state)
    # Occupying: holds cores at its host (even while the image is migrating —
    # the slot is reserved from placement). Usable: may actually execute.
    occupying = state.vm_placed & ~done & vms.exists
    usable = occupying & (state.t >= state.vm_avail_t)

    seg = jnp.where(occupying, state.vm_dc * H + state.vm_host, n_seg)
    host_cores_v = hosts.cores[state.vm_dc, state.vm_host].astype(jnp.float32)
    host_mips_v = hosts.mips[state.vm_dc, state.vm_host]
    vm_cores_f = vms.cores.astype(jnp.float32)

    # --- space-shared (Fig 4a): FCFS exclusive core grants ---
    demand_cores = jnp.where(occupying, vm_cores_f, 0.0)
    prefix = segments.segment_prefix_sum(demand_cores, seg, n_seg)
    fits = prefix + vm_cores_f <= host_cores_v + 1e-6
    percore = jnp.minimum(vms.mips, host_mips_v)
    space = jnp.where(usable & fits, vm_cores_f * percore, 0.0)

    # --- time-shared (Fig 4c): proportional share of host capacity ---
    demand_mips = jnp.where(occupying, vm_cores_f * vms.mips, 0.0)
    total = segments.segment_sum(demand_mips, seg, n_seg)
    cap = (hosts.cores.astype(jnp.float32) * hosts.mips).reshape(-1)
    seg_safe = jnp.clip(seg, 0, n_seg - 1)
    total_v = total[seg_safe]
    scale = jnp.where(
        total_v > 0, jnp.minimum(1.0, cap[seg_safe] / jnp.maximum(total_v, 1e-9)), 0.0
    )
    time = jnp.where(usable, vm_cores_f * vms.mips * scale, 0.0)

    return jnp.where(scn.policy.host_policy == TIME_SHARED, time, space)


def cloudlet_rates(scn: Scenario, state: SimState) -> tuple[Array, Array]:
    """([C] MIPS rate per cloudlet, [V] granted VM MIPS).

    The per-cloudlet rate is *per required core* x cores, i.e. a 2-core
    cloudlet of length L finishes after L/(rate/cores) seconds of per-core
    progress; the engine tracks per-core remaining MI so dt = rem / (rate/cores).
    To keep the engine uniform we return the rate already normalized to
    per-core progress MIPS: rem_mi decreases at ``rate`` MI/s.
    """
    cls, vms = scn.cloudlets, scn.vms
    V = vms.n_vms

    vm_mips = host_level_mips(scn, state)

    # The effective binding: fixed rows carry their Cloudlets.vm from init,
    # service rows the broker's dispatch choice (undispatched rows are not
    # ready, so the clipped lookup below never grants them capacity).
    vmi = jnp.clip(state.cl_vm, 0, V - 1)

    ready = cloudlet_ready(scn, state)
    fin = cloudlet_finished(state)
    occ = ready & ~fin & scn.cloudlets.exists
    # Serving rows (prompt_tokens > 0) are scheduled by the continuous-batch
    # model below, never by the Figure-4 pair; excluding them here keeps them
    # out of the legacy core-occupancy reductions.  Non-serving scenarios
    # have the mask all-False, so occ_leg == occ bitwise.
    is_serving = cls.prompt_tokens > 0.0
    occ_leg = occ & ~is_serving
    seg = jnp.where(occ_leg, vmi, V)
    cl_cores_f = cls.cores.astype(jnp.float32)
    vm_cores_f = jnp.maximum(vms.cores.astype(jnp.float32), 1.0)

    percore_capacity = vm_mips / vm_cores_f              # [V] MIPS per granted core

    # --- time-shared inside the VM (Fig 4b/d): equal per-core share ---
    total_demand = segments.segment_sum(
        jnp.where(occ_leg, cl_cores_f, 0.0), seg, V)
    denom = jnp.maximum(total_demand, vms.cores.astype(jnp.float32))
    share = vm_mips / jnp.maximum(denom, 1e-9)           # per demanded core

    # --- continuous-batching decode (DESIGN.md §14) ---
    # An admitted serving row decodes as a member of its VM's batch: per-step
    # rate is the per-core capacity degraded by 1 / (1 + alpha * (b - 1)) for
    # a decode batch of b.  A row awaiting KV-block admission makes no
    # progress.  All-False masks keep non-serving scenarios bitwise.
    occ_srv = occ & is_serving & state.cl_admitted
    seg_srv = jnp.where(occ_srv, vmi, V)
    batch = segments.segment_sum(occ_srv.astype(jnp.float32), seg_srv, V)
    slow = 1.0 + scn.policy.batch_degradation * jnp.maximum(batch - 1.0, 0.0)

    # every per-VM quantity at each cloudlet's VM, read through one mask
    cores_c, percore_c, share_c, slow_c, mips_c = segments.lookup(
        (vms.cores, percore_capacity, share, slow, vm_mips), vmi)

    # --- space-shared inside the VM (Fig 4a/b upper): FCFS core occupancy ---
    prefix = segments.segment_prefix_sum(
        jnp.where(occ_leg, cl_cores_f, 0.0), seg, V)
    fits = prefix + cl_cores_f <= cores_c.astype(jnp.float32) + 1e-6
    space = jnp.where(occ_leg & fits, percore_c, 0.0)
    time = jnp.where(occ_leg, share_c, 0.0)
    rate = jnp.where(scn.policy.vm_policy == TIME_SHARED, time, space)

    srv_rate = percore_c / jnp.maximum(slow_c, 1e-9)
    rate = jnp.where(is_serving, jnp.where(occ_srv, srv_rate, 0.0), rate)

    # A cloudlet only runs while its VM is granted capacity.
    rate = jnp.where(mips_c > 0, rate, 0.0)
    return rate, vm_mips
