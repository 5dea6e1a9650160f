"""CloudSim entities as struct-of-arrays pytrees.

Paper mapping (§3.1, §4):

===============  =============================================================
CloudSim class   Here
===============  =============================================================
Datacenter       the leading axis ``d`` of every ``[D, H]`` host array
Host             one column of the ``Hosts`` arrays
VirtualMachine   one row of ``VMRequests`` + per-VM state in ``SimState``
Cloudlet         one row of ``Cloudlets`` + per-cloudlet state in ``SimState``
DatacenterBroker the arrival schedule baked into ``request_t`` / ``submit_t``
SANStorage       ``input_mb``/``output_mb`` transfer latency + bandwidth cost
CloudCoordinator ``sensed_load`` + the federation placement rule (provision.py)
                 + the runtime migration policies (step.MigrationInstrument)
Sensor           the periodic ``sensed_load`` refresh (engine.py tick)
CIS registry     implicit: placement searches the global ``[D, H]`` host table
===============  =============================================================

All sizes (D datacenters, H hosts/DC, V VMs, C cloudlets) are static shapes;
all *values* — including the policy selectors — are traced, so one compiled
engine serves an entire campaign (policy x seed x workload sweep) via vmap.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.pytree import pytree_dataclass

# Scheduling policies (paper §3.2, Figure 4). Traced int32 values.
SPACE_SHARED = 0
TIME_SHARED = 1

# A time/MI that behaves as "never/unreachable".  A numpy scalar, not a
# device array: a module constant must not be a buffer that a donating call
# (the campaign fold donates its reducer carries) can delete.
INF = np.float32(3.0e38)


@pytree_dataclass
class Hosts:
    """Physical machines, ``[D, H]`` per field (paper §3.1 ``Host``)."""

    cores: Array        # [D,H] i32  processing elements per host
    mips: Array         # [D,H] f32  MIPS per core
    ram_mb: Array       # [D,H] f32
    storage_mb: Array   # [D,H] f32
    bw_mbps: Array      # [D,H] f32
    kv_blocks: Array    # [D,H] f32  KV-cache blocks the host's accelerators
                        #            hold — the binding memory resource of LLM
                        #            serving (0: not a serving host, §14)
    exists: Array       # [D,H] bool (ragged datacenters are masked, not padded out)

    @property
    def n_dc(self) -> int:
        return self.cores.shape[0]

    @property
    def n_hosts(self) -> int:
        return self.cores.shape[1]


@pytree_dataclass
class VMRequests:
    """VM creation requests, ``[V]`` per field (paper §4 ``VirtualMachine``)."""

    dc: Array          # [V] i32  origin datacenter (the broker submits here)
    cores: Array       # [V] i32  required processing elements
    mips: Array        # [V] f32  required MIPS per core
    ram_mb: Array      # [V] f32
    storage_mb: Array  # [V] f32
    bw_mbps: Array     # [V] f32
    kv_blocks: Array   # [V] f32  KV-cache blocks the VM (a serving replica)
                       #          reserves on its host — its decode-batch pool
    request_t: Array   # [V] f32  when the broker asks for the VM
    image_mb: Array    # [V] f32  VM image size — migration transfer volume
    exists: Array      # [V] bool
    pool: Array        # [V] bool spare auto-scaling rows: held inactive until
                       #          the AutoscaleInstrument activates them

    @property
    def n_vms(self) -> int:
        return self.dc.shape[0]


@pytree_dataclass
class Cloudlets:
    """Application task units, ``[C]`` per field (paper §4 ``Cloudlet``).

    ``length_mi`` is per-core million-instructions (GridSim convention); a
    cloudlet needing ``cores`` PEs advances on each of them at its share rate.
    Rows must be ordered by ``submit_t`` (ties by row) — FCFS below is row
    order, exactly CloudSim's arrival-ordered queues.

    ``vm == -1`` marks a *service-routed* row: the broker dispatches it at
    submit time to the least-loaded active VM (including activated pool VMs),
    which is what makes horizontal auto-scaling visible to the application
    (DESIGN.md §7).  ``vm >= 0`` rows keep CloudSim's fixed binding.

    ``deadline`` is the per-cloudlet SLA: the absolute sim time by which the
    row must finish (INF = no guarantee).  A row violates its SLA when it
    finishes later — or never finishes at all (DESIGN.md §9).

    ``input_dc >= 0`` declares where the row's ``input_mb`` lives: the image
    must be staged from that datacenter to the assigned VM's DC before
    execution.  Under a ``Scenario.topology`` the stage-in becomes a real
    network transfer drawing fair-share bandwidth from the link ledger
    (DESIGN.md §13); without a topology it bills the flat
    ``Policy.interdc_bw_mbps`` divisor when remote.  ``input_dc == -1`` keeps
    the legacy VM-local stage-in (``input_mb / vm_bw``).

    ``prompt_tokens > 0`` marks a *serving* row: an LLM inference request
    generating ``max_new_tokens`` tokens (``length_mi / max_new_tokens`` MI
    each), which must hold ``ceil((prompt + generated) / block_tokens)`` KV
    blocks of its VM's pool while in the decode batch (DESIGN.md §14).
    ``prompt_tokens == 0`` rows keep classic batch-cloudlet semantics.
    """

    vm: Array         # [C] i32  target VM (-1: broker-dispatched at submit)
    length_mi: Array  # [C] f32
    cores: Array      # [C] i32
    submit_t: Array   # [C] f32
    input_mb: Array   # [C] f32  staged in before execution (SAN transfer)
    input_dc: Array   # [C] i32  datacenter holding the input data (-1: VM-local)
    output_mb: Array  # [C] f32  staged out at completion
    deadline: Array   # [C] f32  absolute SLA finish time (INF: none)
    prompt_tokens: Array   # [C] f32  prompt length; > 0 marks a serving row
    max_new_tokens: Array  # [C] f32  decode budget of a serving row (its
                           #          length_mi spreads evenly across tokens)
    exists: Array     # [C] bool

    @property
    def n_cloudlets(self) -> int:
        return self.vm.shape[0]


@pytree_dataclass
class Outages:
    """Per-host failure/repair schedule, ``[D, H, K]`` per field (K = max
    outages per host, a static shape; DESIGN.md §9).

    Times are absolute sim seconds; a host is *down* during
    ``[fail_t[k], repair_t[k])``.  Windows along K are disjoint and sorted by
    construction (``workload.host_outages``); INF entries are padding ("no
    k-th outage"), which is how an MTBF = ∞ control shares shapes — and the
    compiled program — with failing rows in one vmapped campaign.
    """

    fail_t: Array    # [D,H,K] f32 outage starts (INF: padding)
    repair_t: Array  # [D,H,K] f32 outage ends

    def down_at(self, t) -> Array:
        """[D, H] bool — host inside an outage window at time ``t``."""
        return jnp.any((self.fail_t <= t) & (t < self.repair_t), axis=-1)

    def next_fail_after(self, t) -> Array:
        """[D, H] earliest failure time strictly after ``t`` (INF: none)."""
        return jnp.min(jnp.where(self.fail_t > t, self.fail_t, INF), axis=-1)

    def next_repair_after(self, t) -> Array:
        """[D, H] earliest repair time strictly after ``t`` (INF: none)."""
        return jnp.min(
            jnp.where(self.repair_t > t, self.repair_t, INF), axis=-1
        )


@pytree_dataclass
class Market:
    """Per-datacenter prices (paper §3.3), ``[D]`` per field."""

    cost_per_cpu_sec: Array     # charged while a cloudlet executes
    cost_per_ram_mb: Array      # one-time, at VM creation (paper: "incur during
    cost_per_storage_mb: Array  # virtual machine creation")
    cost_per_bw_mb: Array       # per MB transferred (cloudlet IO + migration)


@pytree_dataclass
class Policy:
    """All policy selectors, traced so campaigns can sweep them."""

    host_policy: Array        # scalar i32: SPACE_SHARED | TIME_SHARED (VMM level)
    vm_policy: Array          # scalar i32: cloudlet scheduler inside each VM
    federation: Array         # scalar bool: CloudCoordinator migration on/off
    core_reserving: Array     # scalar bool: provisioner also reserves PEs
    best_fit: Array           # scalar bool: best-fit (by leftover RAM) vs first-fit
    sensor_interval: Array    # scalar f32: Sensor refresh period (sim seconds)
    migration_fixed_s: Array  # scalar f32: fixed VM re-creation latency
    interdc_bw_mbps: Array    # scalar f32: inter-datacenter link for migration
    horizon: Array            # scalar f32: simulation end time
    autoscale: Array          # scalar bool: AutoscaleInstrument acts on the pool
    scale_up_thresh: Array    # scalar f32: sustained DC utilization above this
                              #             activates one pool VM per DC
    scale_down_thresh: Array  # scalar f32: DC utilization below this releases
                              #             one idle pool VM per DC (0 disables)
    # --- runtime (live) migration, DESIGN.md §8 ---
    live_migration: Array            # scalar bool: MigrationInstrument acts
    migrate_balance_thresh: Array    # scalar f32: a DC whose demand exceeds
                                     #   this may shed its busiest VM to the
                                     #   least-loaded feasible peer
    migrate_consolidate_thresh: Array  # scalar f32: a DC below this drains
                                     #   its idlest VM toward the busiest
                                     #   feasible peer (0 disables)
    # --- reliability (host failures + SLA), DESIGN.md §9 ---
    ckpt_interval: Array      # scalar f32: checkpoint spacing in per-core MI —
                              #   a host failure rolls in-flight cloudlets back
                              #   to the last completed multiple (INF: restart
                              #   from zero)
    evacuation: Array         # scalar bool: ReliabilityInstrument proactively
                              #   drains doomed hosts to federation peers
    evac_lead_s: Array        # scalar f32: evacuation alarm this long before
                              #   each scheduled host failure
    # --- contention-aware network layer, DESIGN.md §13 ---
    locality_dispatch: Array  # scalar bool: broker weighs estimated stage-in
                              #   transfer time against queue depth when
                              #   choosing a VM for service-routed cloudlets
                              #   (needs Scenario.topology; False keeps the
                              #   least-loaded rank dispatch bitwise)
    # --- LLM serving (KV-bound continuous batching), DESIGN.md §14 ---
    block_tokens: Array       # scalar f32: tokens per KV-cache block — a
                              #   serving row holds ceil(ctx / block_tokens)
                              #   blocks of its VM's pool
    batch_degradation: Array  # scalar f32: per-step decode rate of a batched
                              #   request scales by 1 / (1 + alpha * (b - 1))
                              #   for a decode batch of b (0: free batching)


@pytree_dataclass(static=("max_steps", "sweep_impl"))
class Scenario:
    """A complete experiment: infrastructure + workload + policy + prices.

    ``power`` and ``topology`` (core/energy.py) are optional: the paper's
    stated future work — energy accounting and BRITE-style inter-DC links —
    activate when provided and change nothing when None.  ``outages`` (an
    ``Outages`` schedule, usually from ``workload.host_outages``) activates
    the reliability subsystem — K_FAILURE/K_REPAIR events, eviction with
    checkpoint rollback, SLA/downtime accounting (DESIGN.md §9) — and
    likewise changes nothing when None.

    ``dynamic_consolidation`` (``core/consolidate.py``) attaches CloudSim's
    power-aware dynamic consolidation: per-VM utilisation series, per-host
    power tables and an overload detector, with a detect/select/place pass
    at every scheduling tick (DESIGN.md §15); None changes nothing.  It is
    not ``scenarios.consolidation_scenario``, which drains a datacenter
    through ``step.MigrationInstrument`` (DESIGN.md §8).

    ``instruments`` holds *extra* step.Instrument observables, threaded
    through the event loop after the defaults (sensor, market, energy); their
    array fields are traced data, so campaigns may vmap over them.
    """

    hosts: Hosts
    vms: VMRequests
    cloudlets: Cloudlets
    market: Market
    policy: Policy
    power: object = None        # energy.PowerModel | None
    topology: object = None     # energy.Topology | None
    outages: object = None      # Outages | None — per-host failure schedule
    instruments: tuple = ()     # tuple[step.Instrument, ...] extra observables
    dynamic_consolidation: object = None  # consolidate.Consolidation | None
                                          # — the per-tick PABFD pass (§15)
    max_steps: int = 0          # 0 -> derived bound (see step.default_max_steps)
    sweep_impl: str = "jnp"     # "jnp" | "pallas" — advance-sweep implementation


@pytree_dataclass
class SimState:
    """Everything the event loop carries (one pytree through while_loop)."""

    t: Array            # scalar f32 simulation clock
    step: Array         # scalar i32 event-batch counter
    # --- VM lifecycle ---
    vm_host: Array       # [V] i32 host index within vm_dc, -1 if unplaced
    vm_dc: Array         # [V] i32 current datacenter (!= origin after migration)
    vm_placed: Array     # [V] bool
    vm_failed: Array     # [V] bool (terminal: creation rejected everywhere —
                         #          never set, and never cleared, by the
                         #          transient host-failure path, DESIGN.md §9)
    vm_evicted: Array    # [V] bool transient: lost its slot to a host failure,
                         #          re-queued through the creation path; cleared
                         #          once placed and available again
    vm_avail_t: Array    # [V] f32 creation/migration completes at this time
    vm_released: Array   # [V] bool resources returned after all work done
    vm_migrations: Array # [V] i32
    vm_mig_src: Array    # [V] i32 source DC of an in-flight *live* migration
                         #         (-1 at rest / once arrived) — the fixed-shape
                         #         pending-move marker, DESIGN.md §8
    pool_active: Array   # [V] bool pool row activated by the autoscaler
                         #          (inactive -> activating -> active -> released)
    # --- host free capacity (provisioner view) ---
    host_up: Array       # [D,H] bool host currently powered/working (failure
                         #            windows flip this, DESIGN.md §9)
    free_ram: Array      # [D,H] f32
    free_storage: Array  # [D,H] f32
    free_bw: Array       # [D,H] f32
    free_cores: Array    # [D,H] f32 (only enforced when core_reserving)
    free_kv: Array       # [D,H] f32 KV-cache blocks not reserved by placed
                         #           serving VMs (DESIGN.md §14)
    # --- cloudlet execution ---
    cl_vm: Array         # [C] i32 current VM assignment; rows submitted with
                         #         vm == -1 are broker-dispatched at submit time
    cl_ready_t: Array    # [C] f32 stage-in completes (INF until dispatched)
    cl_admitted: Array   # [C] bool serving row currently in its VM's decode
                         #          batch (admission gated on free KV blocks)
    cl_kv: Array         # [C] f32 KV blocks the row holds in its VM's pool
                         #         (0 while waiting / preempted / finished)
    rem_mi: Array        # [C] f32 remaining million-instructions (per core)
    cl_rollback_mi: Array  # [C] f32 work re-done after failures: total MI added
                           #         back to rem_mi by checkpoint rollbacks
    started: Array       # [C] bool
    start_t: Array       # [C] f32 (INF until started)
    finish_t: Array      # [C] f32 (INF until finished)
    cpu_time: Array      # [C] f32 accumulated executing seconds
    # --- federation ---
    sensed_load: Array   # [D] f32 last Sensor reading per DC
    last_tick: Array     # scalar f32
    # --- market accounting (per DC) ---
    cpu_cost: Array      # [D] f32
    ram_cost: Array      # [D] f32
    storage_cost: Array  # [D] f32
    bw_cost: Array       # [D] f32
    energy_j: Array      # [D] f32 (0 unless Scenario.power is set)
    # --- reliability accounting (0 unless Scenario.outages is set) ---
    vm_downtime: Array   # [V] f32 seconds spent evicted/awaiting recovery
    n_evacuations: Array # scalar i32 proactive drains committed
    # --- contention-aware transfer ledger (idle unless Scenario.topology is
    #     set; fixed [D,D]/[V]/[C] shapes so one compiled program serves
    #     topology campaigns, DESIGN.md §13) ---
    link_busy: Array     # [D,D] i32 active transfers per directed DC link
    link_share: Array    # [D,D] f32 fair-share Mbps granted per transfer at
                         #           the last transfer-phase recompute
                         #           (bw / max(busy, 1); doubles as the
                         #           occupancy-change detector)
    vm_xfer_src: Array   # [V] i32 source DC of the VM's in-flight image
                         #         transfer (-1: no active transfer)
    vm_xfer_dst: Array   # [V] i32 destination DC of that transfer (pinned at
                         #         commit: eviction may reset vm_dc before the
                         #         ledger slot is freed)
    vm_xfer_rem: Array   # [V] f32 MB still to move as of the last recompute
    vm_xfer_share: Array # [V] f32 Mbps this transfer currently receives
    cl_xfer_dst: Array   # [C] i32 destination DC of the cloudlet's in-flight
    cl_xfer_rem: Array   # [C] f32   stage-in transfer (-1 / MB / Mbps,
    cl_xfer_share: Array # [C] f32   mirroring the VM transfer columns)
    # --- power-aware consolidation (None unless Scenario.dynamic_consolidation) ---
    consol: object = None  # consolidate.PowerState | None


@pytree_dataclass
class SimResult:
    """Derived outcome of one simulation (what the paper's tables report)."""

    finish_t: Array      # [C]
    start_t: Array       # [C]
    cl_vm: Array         # [C] final VM binding (service rows: the broker's
                         #     dispatch choice; -1 if never dispatched)
    turnaround: Array    # [C] finish - submit (INF for never-finished)
    makespan: Array      # scalar: max finish over finished cloudlets
    mean_turnaround: Array  # scalar over finished cloudlets
    n_finished: Array    # scalar i32
    n_events: Array      # scalar i32 event batches processed
    n_migrations: Array  # scalar i32
    vm_placed: Array     # [V] bool
    vm_dc: Array         # [V] i32 final datacenter
    vm_failed: Array     # [V] bool
    cpu_cost: Array      # [D]
    ram_cost: Array      # [D]
    storage_cost: Array  # [D]
    bw_cost: Array       # [D]
    energy_j: Array      # [D]
    total_cost: Array    # scalar
    end_t: Array         # scalar: clock when the loop exited
    # --- SLA / reliability (DESIGN.md §9) ---
    sla_violations: Array  # scalar i32: existing cloudlets that finished past
                           #             their deadline, or never finished
    downtime: Array        # scalar f32: total VM-seconds lost to failures
                           #             (evicted + recovery transfer windows)
    n_evacuations: Array   # scalar i32: proactive pre-failure drains
    # --- serving tail latency (DESIGN.md §14; INF when no serving rows) ---
    ttft_p50: Array        # scalar f32: median time-to-first-token over
                           #             finished serving rows
    ttft_p99: Array        # scalar f32: p99 time-to-first-token
    tpot_p50: Array        # scalar f32: median time-per-output-token
    tpot_p99: Array        # scalar f32: p99 time-per-output-token
    # --- power-aware consolidation (None unless Scenario.dynamic_consolidation) ---
    power: object = None   # consolidate.PowerResult | None


def finished_mask(res: SimResult) -> Array:
    return jnp.isfinite(res.finish_t) & (res.finish_t < INF / 2)
