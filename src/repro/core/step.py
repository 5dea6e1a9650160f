"""The event-step kernel: the simulator's event-loop body, exactly once.

``simulate``, ``simulate_trace`` and ``simulate_history`` (engine.py) are thin
drivers over one function — ``event_step`` — which advances the world by one
event batch:

    0. host failure/repair edges     (outage schedule: evict + roll back)
    1. instrument ``pre`` hooks      (Sensor tick lives here)
    2. VM lifecycle                  (release drained, place due requests)
    3. policy sweep                  (per-cloudlet MIPS rates)
    4. next-event bound              (ready / request / migration / failure /
                                      repair / instrument bounds / horizon)
    5. fused advance                 (min-time-to-completion + work depletion,
                                      jnp or Pallas — resolved once per driver)
    6. instrument ``post`` hooks     (market accrual, energy integration,
                                      trace sampling, custom observables)

Cross-cutting observables are **Instruments**: small pytrees with
``init / pre / bound / post / finalize`` hooks threaded through the loop as an
auxiliary carry.  The engine body knows nothing about federation sensing,
prices, power models or progress traces — each is one class below, and a new
observable (say, a per-DC utilization timeline for Figure 9/10-style plots)
is one more class, not an engine fork.  See DESIGN.md §2 for the equivalence
argument and §3 for the instrument contract.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import Array

from repro.core import kvserve, policies, provision, segments
from repro.kernels import ops as _kernel_ops
from repro.core.entities import (
    INF,
    Scenario,
    SimResult,
    SimState,
)
from repro.core.pytree import pytree_dataclass

# Event kinds recorded by ``StepEvent.kind`` / ``History.kind``.
K_COMPLETION = 0   # a cloudlet ran out of work
K_READY = 1        # a submitted cloudlet finished stage-in
K_VM_REQUEST = 2   # a broker VM request came due
K_MIGRATION = 3    # a VM creation/migration transfer completed
K_TICK = 4         # a federation Sensor refresh
K_INSTRUMENT = 5   # a custom instrument clock stop
K_HORIZON = 6      # the simulation horizon
K_SCALE = 7        # an autoscaler evaluation tick (AutoscaleInstrument)
K_FAILURE = 8      # a scheduled host failure (Scenario.outages)
K_REPAIR = 9       # a failed host came back (empty)
K_STAGE = 10       # a pending data stage-in became openable (topology only)
K_SERVING = 11     # a decoding request crossed a KV-block boundary (§14)

# Named scopes wrapping the phase-skip ``lax.cond``s.  The names land in the
# optimized HLO's op metadata (``op_name=.../phase_provision/cond``), which is
# how simlint rule R1 verifies the predicates survive XLA lowering as real
# ``conditional`` ops with branch computations — not flattened into ``select``
# (the vmap degradation that silently pays both branches, DESIGN.md §10/§11).
SCOPE_PROVISION = "phase_provision"
SCOPE_DISPATCH = "phase_dispatch"
SCOPE_TRANSFER = "phase_transfer"
SCOPE_SERVING = "phase_serving"
# SCOPE_TRANSFER only exists in programs traced with a topology attached;
# simlint's lint scenarios carry one so R1 covers all four phases.
PHASE_SCOPES = (SCOPE_PROVISION, SCOPE_DISPATCH, SCOPE_TRANSFER, SCOPE_SERVING)

# Named scopes over the rest of the program, so a profiler trace assigns
# nearly every device op to a phase (bench/metrics reads them by name).  They
# sit on no ``conditional``, so they stay out of PHASE_SCOPES (simlint R1).
# Each is opened at the call site, around a phase's ``jax.vmap`` on the batch
# path: a scope opened inside a vmapped function renders as
# ``vmap(phase_bound)`` and no longer matches as a whole path component.
SCOPE_PROLOGUE = "phase_prologue"   # outage edges, transfers, pre hooks, release
SCOPE_BOUND = "phase_bound"         # policy sweep, candidate times, bound hooks
SCOPE_ADVANCE = "phase_advance"     # ctx.advance, jnp or Pallas
SCOPE_COMMIT = "phase_commit"       # state update, post hooks
SCOPE_LOOP_COND = "loop_cond"       # the while drivers' condition, batch_live
SCOPE_FREEZE = "phase_freeze"       # batch path: finished rows' row select
SCOPE_INIT = "sim_init"             # init_state / init_aux in the drivers
SCOPE_FINALIZE = "sim_finalize"     # finalize_result / finalize_outputs
SCOPE_FOLD = "campaign_fold"        # the reducers' folds in a chunk program
TRACE_SCOPES = (
    SCOPE_PROLOGUE, SCOPE_BOUND, SCOPE_ADVANCE, SCOPE_COMMIT, SCOPE_LOOP_COND,
    SCOPE_FREEZE, SCOPE_INIT, SCOPE_FINALIZE, SCOPE_FOLD,
)


def default_max_steps(scn: Scenario) -> int:
    """Safety bound on event batches: starts + finishes + VM lifecycle + slack.

    Federation scenarios add ~horizon/sensor_interval tick events; builders
    for those pass ``Scenario.max_steps`` explicitly.  An outage schedule
    adds its fail/repair edges plus per-edge eviction/evacuation slack
    (schedule *shapes* are static, so this stays a Python int).
    """
    extra = 0
    if scn.outages is not None:
        n_out = int(scn.outages.fail_t.size)
        extra = 4 * n_out + 2 * scn.vms.n_vms
    if scn.topology is not None:
        # network stage-ins add a K_STAGE open plus a K_READY arrival per
        # row, and fair-share recomputes can split previously-coincident
        # completions into separate events
        extra += 2 * scn.cloudlets.n_cloudlets
    return 4 * (scn.cloudlets.n_cloudlets + scn.vms.n_vms) + 260 + extra


def resolve_max_steps(scn: Scenario, instruments: tuple = ()) -> int:
    """Driver step budget: scenario override or derived bound, plus whatever
    the attached instruments declare via ``Instrument.extra_steps``."""
    base = scn.max_steps if scn.max_steps > 0 else default_max_steps(scn)
    return base + sum(ins.extra_steps(scn) for ins in instruments)


def resolve_advance(scn: Scenario) -> Callable:
    """Choose the advance-sweep implementation once per driver (DESIGN.md §4).

    The kernels import happens at module scope, NOT here: importing a module
    mid-trace would create its module-level jnp constants under the active
    jit trace and leak tracers into later compilations.
    """
    return _kernel_ops.resolve_advance(scn.sweep_impl)


def _eps_mi(length_mi: Array) -> Array:
    """Finish tolerance: float32 work counters drift ~ulp per event (DESIGN.md
    §2, "f64-free"); tests bound the induced completion-time error."""
    return 1e-5 * length_mi + 0.25


def _min_where(x: Array, mask: Array) -> Array:
    return jnp.min(jnp.where(mask, x, INF), initial=INF)


def _done_or_doomed(scn: Scenario, st: SimState) -> Array:
    fin = policies.cloudlet_finished(st)
    assigned = st.cl_vm >= 0
    doomed = assigned & st.vm_failed[jnp.clip(st.cl_vm, 0, scn.vms.n_vms - 1)]
    return fin | doomed | ~scn.cloudlets.exists


def step_cond(scn: Scenario, st: SimState, max_steps: int) -> Array:
    """The loop-continuation predicate shared by every driver."""
    return (
        (st.step < max_steps)
        & (st.t < scn.policy.horizon)
        & ~jnp.all(_done_or_doomed(scn, st))
    )


def ready_times(scn: Scenario) -> Array:
    """[C] submit + SAN stage-in: when each cloudlet may start executing.

    Only meaningful for fixed-binding rows (``vm >= 0``); ``init_state`` sets
    service-routed rows to INF until the broker dispatches them, at which
    point the stage-in clock starts against the assigned VM's bandwidth.

    ``input_dc >= 0`` rows staging from a remote DC bill the flat
    ``interdc_bw_mbps`` divisor here; under a topology ``init_state``
    overrides them to INF and the transfer phase prices the move on the link
    ledger instead (DESIGN.md §13).
    """
    cls, vms = scn.cloudlets, scn.vms
    vmi = jnp.clip(cls.vm, 0, vms.n_vms - 1)
    stage_in = jnp.where(
        cls.input_mb > 0,
        cls.input_mb / jnp.maximum(vms.bw_mbps[vmi], 1e-6),
        0.0,
    )
    if scn.topology is None:
        remote = (cls.input_dc >= 0) & (cls.input_dc != vms.dc[vmi])
        stage_in = jnp.where(
            remote,
            cls.input_mb / jnp.maximum(scn.policy.interdc_bw_mbps, 1e-6),
            stage_in,
        )
    return cls.submit_t + stage_in


@pytree_dataclass
class StepEvent:
    """What one ``event_step`` emitted — everything instruments may observe.

    Rates are piecewise-constant over ``[t0, t1)`` (DESIGN.md §2), so any
    linear observable integrates exactly from these fields alone.
    """

    t0: Array              # scalar f32: interval start (clock before the step)
    t1: Array              # scalar f32: interval end (clock after the step)
    dt: Array              # scalar f32: t1 - t0
    kind: Array            # scalar i32: K_* event classification
    rate: Array            # [C] f32  per-cloudlet MIPS during the interval
    active: Array          # [C] bool executing during the interval
    rem_before: Array      # [C] f32  remaining MI at t0
    newly_started: Array   # [C] bool first granted capacity this step
    newly_finished: Array  # [C] bool depleted their work this step
    vm_mips: Array         # [V] f32  host-level granted MIPS during the interval


class Instrument:
    """Base observable: override any subset of the five hooks.

    ``aux`` is an arbitrary pytree threaded through the loop (the instrument's
    private state); hooks must be pure and shape-stable.  ``pre`` may rewrite
    ``SimState`` before the policy sweep, ``bound`` contributes an absolute
    next-event time (a clock stop), ``post`` observes the emitted ``StepEvent``
    after the state update, ``finalize`` turns the final aux into outputs.
    """

    name: str = "instrument"
    bound_kind: int = K_INSTRUMENT

    def init(self, scn: Scenario):
        return ()

    def extra_steps(self, scn: Scenario) -> int:
        """Static add-on to the driver's ``max_steps`` safety bound.

        An instrument whose ``bound()`` adds clock stops creates events the
        default bound (starts/finishes/lifecycle) does not count; override
        this with a concrete int so the loop cannot silently truncate.
        (Traced quantities — e.g. horizon/period with a traced horizon —
        cannot be counted here; set ``Scenario.max_steps`` explicitly then,
        as the federation builders do for Sensor ticks.)
        """
        return 0

    def pre(self, scn: Scenario, st: SimState, aux):
        return st, aux

    def bound(self, scn: Scenario, st: SimState, aux) -> Array:
        return INF

    def post(self, scn: Scenario, st: SimState, ev: StepEvent, aux):
        return st, aux

    def finalize(self, scn: Scenario, st: SimState, aux) -> dict:
        return {}


@pytree_dataclass
class SensorInstrument(Instrument):
    """Periodic stale-by-design load sensing (paper §2.3, the CIS Sensor).

    ``pre``: refresh ``sensed_load`` when a tick is due.  ``bound``: the next
    tick is a clock stop so the loop never jumps across a refresh.
    """

    # class attrs, unannotated on purpose: not dataclass/pytree fields
    name = "sensor"
    bound_kind = K_TICK

    def pre(self, scn: Scenario, st: SimState, aux):
        pol = scn.policy
        tick_due = pol.federation & (st.t >= st.last_tick + pol.sensor_interval)
        st = st.replace(
            sensed_load=jnp.where(
                tick_due, provision.sense_load(scn, st), st.sensed_load
            ),
            last_tick=jnp.where(tick_due, st.t, st.last_tick),
        )
        return st, aux

    def bound(self, scn: Scenario, st: SimState, aux) -> Array:
        pol = scn.policy
        return jnp.where(pol.federation, st.last_tick + pol.sensor_interval, INF)


@pytree_dataclass
class MarketInstrument(Instrument):
    """Per-interval market accrual (paper §3.3): CPU-seconds while executing,
    bandwidth at cloudlet IO edges.  (RAM/storage are billed at VM creation
    inside the provisioner — a placement decision, not an interval integral.)
    """

    name = "market"

    def post(self, scn: Scenario, st: SimState, ev: StepEvent, aux):
        cls = scn.cloudlets
        # Bill against the dispatched assignment (== cls.vm for fixed rows);
        # unassigned rows are never active and never hit an IO edge.
        dc_of_cl = st.vm_dc[jnp.clip(st.cl_vm, 0, scn.vms.n_vms - 1)]
        run_cost = jnp.where(
            ev.active, ev.dt * scn.market.cost_per_cpu_sec[dc_of_cl], 0.0
        )
        io_mb = jnp.where(ev.newly_started, cls.input_mb, 0.0) + jnp.where(
            ev.newly_finished, cls.output_mb, 0.0
        )
        io_cost = io_mb * scn.market.cost_per_bw_mb[dc_of_cl]
        dc_seg = jnp.clip(dc_of_cl, 0, scn.hosts.n_dc - 1)
        st = st.replace(
            cpu_cost=st.cpu_cost.at[dc_seg].add(run_cost),
            bw_cost=st.bw_cost.at[dc_seg].add(io_cost),
        )
        return st, aux


@pytree_dataclass
class EnergyInstrument(Instrument):
    """Integrate P(t)·dt per DC under the linear power model (energy.py).

    No-op when ``Scenario.power`` is None — energy stays exactly zero.
    """

    name = "energy"

    def post(self, scn: Scenario, st: SimState, ev: StepEvent, aux):
        if scn.power is None:
            return st, aux
        from repro.core import energy as energy_mod

        watts = energy_mod.power_draw(scn, st, vm_mips=ev.vm_mips)
        return st.replace(energy_j=st.energy_j + watts * ev.dt), aux


@pytree_dataclass
class AutoscaleInstrument(Instrument):
    """Threshold-based horizontal scaling over the pre-declared VM pool.

    Every ``sensor_interval`` (a ``K_SCALE`` clock stop, so the loop never
    jumps across an evaluation) the autoscaler reads per-DC *demand*
    utilization (``provision.demand_load`` — queued work counts fully, so
    the signal is run-queue pressure, not allocation):

    * **scale up** — demand above ``scale_up_thresh`` at two consecutive
      ticks (i.e. sustained for a full sensor interval) activates the
      lowest-index inactive pool VM of that DC; the provisioner places it in
      the same step and it boots with the usual fixed creation latency.
    * **scale down** — demand below ``scale_down_thresh`` releases one
      idle (booted, no outstanding work) pool VM of that DC.  Release is
      terminal: inactive -> activating -> active -> released (DESIGN.md §7).

    All decisions are traced data (``Policy.autoscale`` gates everything), so
    one compilation serves autoscaled and static runs alike and campaigns
    vmap over arrival-rate x threshold grids.  The tick count depends on the
    traced horizon, so scenarios attaching this instrument must set
    ``Scenario.max_steps`` explicitly, like the federation builders do.
    """

    name = "autoscale"
    bound_kind = K_SCALE

    def init(self, scn: Scenario):
        D = scn.hosts.n_dc
        return (
            jnp.asarray(0.0, jnp.float32),   # last evaluation time
            jnp.zeros((D,), bool),           # was over-threshold at last tick
            jnp.asarray(0, jnp.int32),       # activations
            jnp.asarray(0, jnp.int32),       # releases
        )

    def pre(self, scn: Scenario, st: SimState, aux):
        last_t, over_prev, n_up, n_down = aux
        pol, vms = scn.policy, scn.vms
        V, D = vms.n_vms, scn.hosts.n_dc
        due = pol.autoscale & (st.t >= last_t + pol.sensor_interval)
        util = provision.demand_load(scn, st)                           # [D]
        over = util > pol.scale_up_thresh
        under = util < pol.scale_down_thresh
        rows = jnp.arange(V)

        # scale up: sustained pressure activates one inactive pool row per DC
        want_up = due & over & over_prev                                # [D]
        cand_up = (
            vms.pool & vms.exists & ~st.pool_active & ~st.vm_placed
            & ~st.vm_failed & want_up[vms.dc]
        )
        first_up = jnp.full((D,), V).at[vms.dc].min(
            jnp.where(cand_up, rows, V)
        )
        act = cand_up & (rows == first_up[vms.dc])

        # scale down: one idle booted pool row per under-pressure DC
        dc_now = jnp.clip(st.vm_dc, 0, D - 1)
        seg = jnp.where(scn.cloudlets.exists & (st.cl_vm >= 0), st.cl_vm, V)
        busy = segments.segment_sum(
            (~policies.cloudlet_finished(st)).astype(jnp.float32), seg, V
        ) > 0
        cand_down = (
            vms.pool & st.pool_active & st.vm_placed & ~st.vm_released
            & (st.vm_avail_t <= st.t) & ~busy & (due & under)[dc_now]
        )
        first_down = jnp.full((D,), V).at[dc_now].min(
            jnp.where(cand_down, rows, V)
        )
        rel = cand_down & (rows == first_down[dc_now])

        st = provision.release_pool_vms(scn, st, rel)
        st = st.replace(pool_active=st.pool_active | act)
        aux = (
            jnp.where(due, st.t, last_t),
            jnp.where(due, over, over_prev),
            n_up + jnp.sum(act.astype(jnp.int32)),
            n_down + jnp.sum(rel.astype(jnp.int32)),
        )
        return st, aux

    def bound(self, scn: Scenario, st: SimState, aux) -> Array:
        pol = scn.policy
        return jnp.where(pol.autoscale, aux[0] + pol.sensor_interval, INF)

    def finalize(self, scn: Scenario, st: SimState, aux) -> dict:
        return {"n_scale_up": aux[2], "n_scale_down": aux[3]}


@pytree_dataclass
class MigrationInstrument(Instrument):
    """Runtime (live) VM migration across federated datacenters — the
    CloudCoordinator policy layer the paper's abstract promises beyond the
    creation-time Table-1 rule (DESIGN.md §8).

    At every federation sensor tick (a ``K_TICK`` clock stop, so the loop
    never jumps across an evaluation) the coordinator reads per-DC *demand*
    utilization (``provision.demand_load``) and commits at most ONE move:

    * **load balancing** (loaded -> spare) — the most-loaded DC above
      ``migrate_balance_thresh`` sheds its VM with the most outstanding work
      to the least-loaded feasible peer, but only when the move strictly
      shrinks the pair's utilization spread — the improvement rule that
      makes ping-pong impossible.
    * **energy consolidation** (spare -> loaded) — the least-loaded DC below
      ``migrate_consolidate_thresh`` drains its VM with the *least*
      outstanding work (idle images first) toward the busiest strictly-busier
      feasible peer, emptying hosts for idle power-gating (energy.py).

    Balance outranks consolidation within a tick.  The commit itself is
    ``provision.live_migrate``: source slot released, destination slot
    occupied in the same event, transfer billed on the inter-DC bandwidth
    meter, and the VM unavailable for ``migration_fixed_s + image/bw`` via
    the existing ``vm_avail_t`` / ``K_MIGRATION`` machinery — in-flight
    cloudlets keep their accrued progress.

    Everything is traced (``Policy.federation & Policy.live_migration`` gate
    it all), so a migration run and its static control share one compiled
    program and campaigns vmap over threshold grids.  Attach the instrument
    statically; sweep the flags/thresholds as data.  The tick count depends
    on the traced horizon, so scenarios attaching this must set
    ``Scenario.max_steps`` explicitly, like the federation builders do.
    """

    name = "migration"
    bound_kind = K_TICK

    def init(self, scn: Scenario):
        return (
            jnp.asarray(0.0, jnp.float32),   # last evaluation time
            jnp.asarray(0, jnp.int32),       # balance moves committed
            jnp.asarray(0, jnp.int32),       # consolidation moves committed
        )

    def pre(self, scn: Scenario, st: SimState, aux):
        last_t, n_bal, n_con = aux
        pol, vms = scn.policy, scn.vms
        V, D = vms.n_vms, scn.hosts.n_dc
        enabled = pol.federation & pol.live_migration
        due = enabled & (st.t >= last_t + pol.sensor_interval)

        st = _clear_arrived_moves(st)

        util = provision.demand_load(scn, st)                      # [D]
        cap = jnp.maximum(provision.dc_capacity_mips(scn), 1e-9)   # [D]
        outstanding = policies.vm_outstanding_mi(scn, st)          # [V]
        demand = policies.vm_demand_mips(scn, st)                  # [V]
        movable = (
            vms.exists & st.vm_placed & ~st.vm_failed & ~st.vm_released
            & (st.vm_avail_t <= st.t)
        )
        dc_of = jnp.clip(st.vm_dc, 0, D - 1)
        has_movable = jnp.zeros((D,), jnp.float32).at[dc_of].add(
            movable.astype(jnp.float32)) > 0
        dcs = jnp.arange(D)

        # --- load balancing: loaded source sheds its busiest VM ---
        src_ok_b = has_movable & (util > pol.migrate_balance_thresh)
        src_b = jnp.argmax(jnp.where(src_ok_b, util, -jnp.inf))
        v_b = jnp.argmax(jnp.where(
            movable & (dc_of == src_b), outstanding, -jnp.inf))
        dst_ok_b = (
            jnp.any(provision.slot_feasible(scn, st, v_b), axis=1)
            & (dcs != src_b)
        )
        dst_b = jnp.argmin(jnp.where(dst_ok_b, util, jnp.inf))
        # improvement rule: the move must strictly shrink the pair's spread
        spread_after = jnp.maximum(
            util[src_b] - demand[v_b] / cap[src_b],
            util[dst_b] + demand[v_b] / cap[dst_b],
        )
        bal_ok = (
            due & jnp.any(src_ok_b) & jnp.any(dst_ok_b)
            & (spread_after < util[src_b] - 1e-6)
        )

        # --- consolidation: idle source drains toward a busier peer ---
        src_ok_c = has_movable & (util < pol.migrate_consolidate_thresh)
        src_c = jnp.argmin(jnp.where(src_ok_c, util, jnp.inf))
        v_c = jnp.argmin(jnp.where(
            movable & (dc_of == src_c), outstanding, jnp.inf))
        dst_ok_c = (
            jnp.any(provision.slot_feasible(scn, st, v_c), axis=1)
            & (dcs != src_c)
            & (util > util[src_c] + 1e-6)   # strictly busier: terminates
        )
        dst_c = jnp.argmax(jnp.where(dst_ok_c, util, -jnp.inf))
        con_ok = due & jnp.any(src_ok_c) & jnp.any(dst_ok_c) & ~bal_ok

        v = jnp.where(bal_ok, v_b, v_c)
        dst = jnp.where(bal_ok, dst_b, dst_c)
        st, moved = provision.live_migrate(scn, st, v, dst, bal_ok | con_ok)
        aux = (
            jnp.where(due, st.t, last_t),
            n_bal + (moved & bal_ok).astype(jnp.int32),
            n_con + (moved & con_ok).astype(jnp.int32),
        )
        return st, aux

    def bound(self, scn: Scenario, st: SimState, aux) -> Array:
        pol = scn.policy
        return jnp.where(
            pol.federation & pol.live_migration,
            aux[0] + pol.sensor_interval, INF,
        )

    def finalize(self, scn: Scenario, st: SimState, aux) -> dict:
        return {"n_balance": aux[1], "n_consolidate": aux[2]}


def _clear_arrived_moves(st: SimState) -> SimState:
    """Reset the pending-move marker for transfers that have landed — shared
    bookkeeping for every instrument that commits ``provision.live_migrate``
    moves (MigrationInstrument, ReliabilityInstrument)."""
    return st.replace(vm_mig_src=jnp.where(
        (st.vm_mig_src >= 0) & (st.vm_avail_t <= st.t),
        -1, st.vm_mig_src))


def _evac_candidate(scn: Scenario, st: SimState):
    """(v, dst_dc, safe, ok) — the next proactive evacuation the coordinator
    would commit right now: the usable VM with the most outstanding work on
    a *doomed* host (scheduled to fail within ``evac_lead_s``), bound for
    the least-loaded federation peer with a safe free slot (``safe`` is the
    ``[D, H]`` landing mask).  Shared by ``ReliabilityInstrument.pre`` (the
    commit) and ``.bound`` (the clock stop that keeps the drain going), so
    they can never disagree.
    """
    pol, vms, hosts = scn.policy, scn.vms, scn.hosts
    D = hosts.n_dc
    nf = scn.outages.next_fail_after(st.t)                      # [D,H]
    doomed = hosts.exists & st.host_up & (nf <= st.t + pol.evac_lead_s)
    d = jnp.clip(st.vm_dc, 0, D - 1)
    h = jnp.clip(st.vm_host, 0, hosts.n_hosts - 1)
    cand = (
        vms.exists & st.vm_placed & ~st.vm_released & ~st.vm_failed
        & (st.vm_avail_t <= st.t) & doomed[d, h]
    )
    outstanding = policies.vm_outstanding_mi(scn, st)
    v = jnp.argmax(jnp.where(cand, outstanding, -jnp.inf))
    # destination: a peer DC with a free slot on a host that is neither down
    # nor itself about to fail — evacuating into the blast radius is not a
    # rescue; the commit passes ``safe`` to live_migrate so the landing
    # host choice honours it too
    safe = provision.slot_feasible(scn, st, v) & ~doomed
    dst_ok = jnp.any(safe, axis=1) & (jnp.arange(D) != jnp.clip(
        st.vm_dc[v], 0, D - 1))
    util = provision.demand_load(scn, st)
    dst = jnp.argmin(jnp.where(dst_ok, util, jnp.inf))
    enabled = pol.federation & pol.evacuation
    ok = enabled & jnp.any(cand) & jnp.any(dst_ok)
    return v, dst, safe, ok


@pytree_dataclass
class ReliabilityInstrument(Instrument):
    """Proactive evacuation ahead of scheduled host failures (DESIGN.md §9).

    The failure *semantics* — K_FAILURE/K_REPAIR edges, eviction, checkpoint
    rollback, downtime accrual — live in the engine (``provision.apply_outages``
    + event_step), because revocation changes what happened, not what was
    observed.  The failure *policy* rides the PR-1 hooks like the autoscaler
    and the migration coordinator:

    * ``bound()`` contributes an evacuation *alarm* — ``Policy.evac_lead_s``
      before each host's next scheduled failure — as a clock stop, and while
      a usable VM still sits on a doomed host with a feasible federation
      peer, keeps the clock stopped (zero-length events) so the drain
      commits one move per event.
    * ``pre()`` commits that move through ``provision.live_migrate`` — the
      §8 stop-and-copy machinery: progress preserved, source slot freed,
      destination slot taken in the same event, transfer window through
      ``vm_avail_t``, image billed on the inter-DC meter — and counts it in
      ``SimState.n_evacuations``.

    VMs with no feasible peer are left to the failure edge: eviction +
    rollback + re-queue through the creation path.  Everything is gated by
    ``Policy.federation & Policy.evacuation`` (both traced), so an
    evacuating run and its fatalist control are one compiled program and
    campaigns vmap MTBF x ckpt-interval x policy grids (tests/
    test_reliability.py).  Statically a no-op when ``Scenario.outages`` is
    None.  Alarm counts depend on the traced schedule, so scenarios
    attaching this set ``Scenario.max_steps`` explicitly, like the
    federation builders do.
    """

    name = "reliability"

    def init(self, scn: Scenario):
        return ()

    def pre(self, scn: Scenario, st: SimState, aux):
        if scn.outages is None:
            return st, aux
        st = _clear_arrived_moves(st)
        v, dst, safe, ok = _evac_candidate(scn, st)
        st, moved = provision.live_migrate(scn, st, v, dst, ok, host_ok=safe)
        return st.replace(
            n_evacuations=st.n_evacuations + moved.astype(jnp.int32)
        ), aux

    def bound(self, scn: Scenario, st: SimState, aux) -> Array:
        if scn.outages is None:
            return INF
        pol, hosts = scn.policy, scn.hosts
        nf = jnp.where(
            hosts.exists & st.host_up,
            scn.outages.next_fail_after(st.t), INF)
        alarm = jnp.min(jnp.where(nf < INF / 2, nf - pol.evac_lead_s, INF))
        future = jnp.where(alarm > st.t, alarm, INF)
        # more to drain right now -> stop the clock (dt = 0); each event
        # moves one VM, so the stop clears in at most |residents| events
        _, _, _, ok_now = _evac_candidate(scn, st)
        return jnp.where(
            pol.federation & pol.evacuation,
            jnp.where(ok_now, st.t, future), INF)


@pytree_dataclass
class TraceInstrument(Instrument):
    """Per-cloudlet progress fractions at ``sample_ts`` — a pure observer.

    Rates are piecewise-constant, so mid-interval progress interpolates
    *exactly*: rem(s) = rem(t0) − rate·(s − t0) for s in [t0, t1].  No clock
    stop is added, hence a traced run's event stream — and every ``SimResult``
    field, including cost and energy — is bit-identical to the untraced run
    (DESIGN.md §2; tests/test_trace_equivalence.py).  Rows of the output align
    with ``sample_ts`` as given.
    """

    name = "trace"

    sample_ts: Array   # [S] f32 absolute sample times

    def init(self, scn: Scenario):
        S = self.sample_ts.shape[0]
        C = scn.cloudlets.n_cloudlets
        return (
            jnp.zeros((S, C), jnp.float32),   # progress fractions
            jnp.zeros((S,), bool),            # recorded mask
        )

    def post(self, scn: Scenario, st: SimState, ev: StepEvent, aux):
        prog, recorded = aux
        ts = self.sample_ts
        length = scn.cloudlets.length_mi
        dt_s = jnp.clip(ts - ev.t0, 0.0, ev.dt)                       # [S]
        depleted = ev.rate[None, :] * dt_s[:, None]                    # [S, C]
        rem_s = jnp.where(
            ev.active[None, :],
            jnp.maximum(ev.rem_before[None, :] - depleted, 0.0),
            ev.rem_before[None, :],
        )
        frac = 1.0 - rem_s / jnp.maximum(length, 1e-9)[None, :]
        hit = ~recorded & (ts <= ev.t1)
        prog = jnp.where(hit[:, None], frac, prog)
        return st, (prog, recorded | hit)

    def finalize(self, scn: Scenario, st: SimState, aux) -> dict:
        prog, recorded = aux
        # Samples past the last event see the frozen final state exactly.
        final = 1.0 - st.rem_mi / jnp.maximum(scn.cloudlets.length_mi, 1e-9)
        return {"progress": jnp.where(recorded[:, None], prog, final[None, :])}


@pytree_dataclass
class UtilizationTimelineInstrument(Instrument):
    """Per-DC utilization sampled at ``sample_ts`` — the Figure 9/10-style
    observable the pre-instrument engine could not produce without a fork.
    """

    name = "utilization"

    sample_ts: Array   # [S] f32

    def init(self, scn: Scenario):
        S = self.sample_ts.shape[0]
        return (
            jnp.zeros((S, scn.hosts.n_dc), jnp.float32),
            jnp.zeros((S,), bool),
        )

    def post(self, scn: Scenario, st: SimState, ev: StepEvent, aux):
        util_tl, recorded = aux
        from repro.core import energy as energy_mod

        util = energy_mod.dc_utilization(scn, st, vm_mips=ev.vm_mips)  # [D]
        hit = ~recorded & (self.sample_ts <= ev.t1)
        util_tl = jnp.where(hit[:, None], util[None, :], util_tl)
        return st, (util_tl, recorded | hit)

    def finalize(self, scn: Scenario, st: SimState, aux) -> dict:
        util_tl, recorded = aux
        from repro.core import energy as energy_mod

        final = energy_mod.dc_utilization(scn, st)
        return {
            "utilization": jnp.where(recorded[:, None], util_tl, final[None, :])
        }


def default_instruments() -> tuple[Instrument, ...]:
    """The always-on observables — the semantics ``simulate`` ships with."""
    return (SensorInstrument(), MarketInstrument(), EnergyInstrument())


@pytree_dataclass(static=("advance",))
class StepContext:
    """Loop-invariant context resolved once per driver.

    ``advance`` is static (it keys the jit cache: jnp vs Pallas); the
    instrument tuple is traced data, so campaigns may vmap over it.  (Ready
    times are *state* now — ``SimState.cl_ready_t`` — because service-routed
    rows learn theirs only at dispatch.)
    """

    instruments: tuple             # tuple[Instrument, ...]
    advance: Callable = None


def instruments_for(
    scn: Scenario, extra_instruments: tuple = ()
) -> tuple[Instrument, ...]:
    """The full instrument tuple a driver threads through the loop.

    Order — defaults, then ``Scenario.instruments``, then driver extras — is
    the accrual order inside each step.  The batch-major step rebuilds this
    inside its vmapped phase closures, so per-row instrument leaves (a
    campaign sweeping instrument fields) map correctly while driver extras
    stay captured unbatched.
    """
    return default_instruments() + tuple(scn.instruments) + tuple(
        extra_instruments
    )


def init_aux(scn: Scenario, extra_instruments: tuple = ()) -> tuple:
    """Initial instrument aux states (vmapped per row by the batch drivers)."""
    return tuple(
        ins.init(scn) for ins in instruments_for(scn, extra_instruments)
    )


def make_context(
    scn: Scenario, extra_instruments: tuple = ()
) -> tuple[StepContext, tuple]:
    """Build the step context + initial instrument aux states for a driver.

    Instrument order — defaults, then ``Scenario.instruments``, then driver
    extras — is the accrual order inside each step.
    """
    instruments = instruments_for(scn, extra_instruments)
    names = [ins.name for ins in instruments]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(
            f"duplicate instrument name(s) {sorted(dupes)}: outputs are keyed "
            "by name — give each instance a distinct `name` class attr"
        )
    ctx = StepContext(
        instruments=instruments,
        advance=resolve_advance(scn),
    )
    aux = tuple(ins.init(scn) for ins in instruments)
    return ctx, aux


# ---------------------------------------------------------------------------
# the event-step phases (DESIGN.md §10)
#
# ``event_step`` is decomposed into phase functions so the batch-major step
# can vmap each phase over the scenario axis while keeping the expensive
# phases (the provisioning scan, broker dispatch) behind *scalar*
# ``lax.cond``s on batch-global predicates.  Under vmap a batched-predicate
# cond degrades to a select (both branches execute); a scalar predicate on
# the whole batch genuinely skips the phase — the structural advantage the
# batch-major path has over vmap-of-``simulate``.  Each skipped phase is an
# exact identity whenever its predicate is False (every write inside is
# gated by the same ``due`` mask the predicate reduces), so skipping
# preserves bitwise identity.
# ---------------------------------------------------------------------------


def _provision_needed(scn: Scenario, st: SimState) -> Array:
    """Any due, unplaced, unfailed VM request (the exact ``due`` mask of
    ``provision.provision_due_vms``) — includes failure-evicted rows, which
    retry at every event."""
    vms = scn.vms
    due = (
        vms.exists & ~st.vm_placed & ~st.vm_failed
        & (vms.request_t <= st.t) & (~vms.pool | st.pool_active)
    )
    return jnp.any(due)


def _dispatch_needed(scn: Scenario, st: SimState) -> Array:
    """Any submitted service-routed cloudlet still unbound (the exact ``due``
    mask of ``provision.dispatch_cloudlets``)."""
    cls = scn.cloudlets
    return jnp.any(cls.exists & (st.cl_vm < 0) & (cls.submit_t <= st.t))


def _phase_prologue(
    scn: Scenario, st: SimState, aux: tuple, instruments: tuple
) -> tuple[SimState, tuple]:
    """Outage edges, instrument ``pre`` hooks, release of drained VMs."""
    # --- host failure/repair edges (Scenario.outages), before anything may
    #     observe or use the dead hosts: evict residents, roll back work ---
    st = provision.apply_outages(scn, st)

    # --- close arrived/cancelled transfers so their link slots are free
    #     before this event's migration commits and stage-in opens ---
    if scn.topology is not None:
        st = provision.settle_transfers(scn, st)

    # --- instrument pre hooks (Sensor tick refreshes sensed_load) ---
    aux = list(aux)
    for i, ins in enumerate(instruments):
        st, aux[i] = ins.pre(scn, st, aux[i])

    # --- VM lifecycle: destroy drained VMs (placement happens next phase) ---
    st = provision.release_done_vms(scn, st)
    return st, tuple(aux)


def _cand_kinds(scn: Scenario, instruments: tuple) -> Array:
    """Static event-kind classification aligned with ``_phase_bound``'s
    candidate times (same per scenario row — shapes and instrument tuples
    are static across a campaign)."""
    cand_k = [K_READY, K_READY, K_VM_REQUEST, K_MIGRATION, K_SERVING]
    if scn.topology is not None:
        cand_k.append(K_STAGE)
    if scn.outages is not None:
        cand_k += [K_FAILURE, K_REPAIR]
    cand_k += [ins.bound_kind for ins in instruments]
    cand_k.append(K_HORIZON)
    return jnp.asarray(cand_k, jnp.int32)


def _phase_bound(
    scn: Scenario, st: SimState, aux: tuple, instruments: tuple
) -> tuple[Array, Array, Array, Array, Array]:
    """Policy sweep + next-event bound: (rate, vm_mips, active, bound_dt,
    cand_ts)."""
    pol, cls, vms = scn.policy, scn.cloudlets, scn.vms

    # --- the updateVMsProcessing sweep: rates for every task unit ---
    rate, vm_mips = policies.cloudlet_rates(scn, st)
    active = rate > 0

    # --- next event bound from non-completion sources ---
    unready = cls.exists & (st.cl_ready_t > st.t)
    undispatched = cls.exists & (st.cl_vm < 0) & (cls.submit_t > st.t)
    # evicted rows' request_t is in the past — they retry at *every* event
    # (and wake on K_REPAIR / completions), so they contribute no bound
    unplaced = (
        vms.exists & ~st.vm_placed & ~st.vm_failed & ~st.vm_evicted
        & (~vms.pool | st.pool_active)
    )
    migrating = vms.exists & st.vm_placed & (st.vm_avail_t > st.t)
    cand_t = [
        _min_where(st.cl_ready_t, unready),
        _min_where(cls.submit_t, undispatched),
        _min_where(vms.request_t, unplaced),
        _min_where(st.vm_avail_t, migrating),
        # decoding requests stop the clock at KV-block boundaries so cache
        # growth — and preemption-on-exhaustion — lands on exact edges
        kvserve.serving_bound(scn, st, rate),
    ]
    if scn.topology is not None:
        # a bound network stage-in submitted in the future must wake the
        # loop at its submit time so the transfer phase can open it
        staging = (
            cls.exists & (cls.input_dc >= 0) & (st.cl_vm >= 0)
            & (st.cl_xfer_dst < 0) & (st.cl_ready_t >= INF / 2)
            & (cls.submit_t > st.t)
        )
        cand_t.append(_min_where(cls.submit_t, staging))
    if scn.outages is not None:
        ex = scn.hosts.exists
        cand_t.append(jnp.min(jnp.where(
            ex, scn.outages.next_fail_after(st.t), INF)))
        cand_t.append(jnp.min(jnp.where(
            ex, scn.outages.next_repair_after(st.t), INF)))
    for i, ins in enumerate(instruments):
        cand_t.append(ins.bound(scn, st, aux[i]))
    cand_t.append(pol.horizon)
    cand_ts = jnp.stack(cand_t)
    bound_t = jnp.min(cand_ts)
    bound_dt = jnp.maximum(bound_t - st.t, 0.0)
    return rate, vm_mips, active, bound_dt, cand_ts


def _phase_commit(
    scn: Scenario,
    st: SimState,
    aux: tuple,
    instruments: tuple,
    rate: Array,
    vm_mips: Array,
    active: Array,
    cand_ts: Array,
    dt: Array,
    new_rem: Array,
) -> tuple[tuple[SimState, tuple], StepEvent]:
    """State update after the advance sweep + instrument ``post`` hooks."""
    cls = scn.cloudlets
    t_next = st.t + dt

    newly_started = active & ~st.started
    newly_fin = active & (new_rem <= _eps_mi(cls.length_mi))
    new_rem = jnp.where(newly_fin, 0.0, new_rem)

    kind = jnp.where(
        jnp.any(newly_fin),
        K_COMPLETION,
        _cand_kinds(scn, instruments)[jnp.argmin(cand_ts)],
    )
    ev = StepEvent(
        t0=st.t,
        t1=t_next,
        dt=dt,
        kind=kind,
        rate=rate,
        active=active,
        rem_before=st.rem_mi,
        newly_started=newly_started,
        newly_finished=newly_fin,
        vm_mips=vm_mips,
    )

    st = st.replace(
        t=t_next,
        step=st.step + 1,
        rem_mi=new_rem,
        started=st.started | newly_started,
        start_t=jnp.where(newly_started, st.t, st.start_t),
        finish_t=jnp.where(newly_fin, t_next, st.finish_t),
        cpu_time=st.cpu_time + jnp.where(active, dt, 0.0),
    )
    if scn.outages is not None:
        # downtime integral: a VM is down while evicted and not yet usable
        # again (intervals never span a recovery edge: vm_avail_t is a
        # K_MIGRATION clock stop and apply_outages clears on arrival)
        vm_down = st.vm_evicted & ~(st.vm_placed & (st.vm_avail_t <= ev.t0))
        st = st.replace(
            vm_downtime=st.vm_downtime + jnp.where(vm_down, dt, 0.0)
        )

    # --- instrument post hooks (market, energy, observers) ---
    aux = list(aux)
    for i, ins in enumerate(instruments):
        st, aux[i] = ins.post(scn, st, ev, aux[i])

    return (st, tuple(aux)), ev


def event_step(
    scn: Scenario, carry: tuple[SimState, tuple], ctx: StepContext
) -> tuple[tuple[SimState, tuple], StepEvent]:
    """Advance the world by one event batch.  THE event-loop body.

    ``carry`` is ``(SimState, instrument aux tuple)``; returns the stepped
    carry plus the emitted ``StepEvent``.  Pure, jittable, vmappable; every
    driver — while_loop or scan — wraps exactly this function (the
    batch-major drivers wrap ``batch_event_step``, which composes the same
    phases over a ``[B, ...]`` scenario axis).

    The provisioning scan and broker dispatch sit behind scalar
    ``lax.cond``s: most events have no due VM request and no unbound
    cloudlet, and both phases are exact identities then, so skipping them is
    free throughput at bitwise-identical results.  (Under vmap the conds
    lower to selects — both branches run — which is exactly the pre-refactor
    cost; the batch-major path keeps the predicates batch-global and scalar,
    so *it* genuinely skips.)
    """
    st, aux = carry
    instruments = ctx.instruments

    with jax.named_scope(SCOPE_PROLOGUE):
        st, aux = _phase_prologue(scn, st, aux, instruments)

    # --- VM placement + broker dispatch, skipped when nothing is due ---
    with jax.named_scope(SCOPE_PROVISION):
        st = jax.lax.cond(
            _provision_needed(scn, st),
            lambda s: provision.provision_due_vms(scn, s)[0],
            lambda s: s,
            st,
        )
    with jax.named_scope(SCOPE_DISPATCH):
        st = jax.lax.cond(
            _dispatch_needed(scn, st),
            lambda s: provision.dispatch_cloudlets(scn, s),
            lambda s: s,
            st,
        )

    # --- contention-aware transfer phase: open due stage-ins, re-time
    #     in-flight transfers on occupancy-changed links (DESIGN.md §13) ---
    if scn.topology is not None:
        with jax.named_scope(SCOPE_TRANSFER):
            st = jax.lax.cond(
                provision.transfer_needed(scn, st),
                lambda s: provision.transfer_phase(scn, s),
                lambda s: s,
                st,
            )

    # --- KV-block ledger sweep: release / growth / eviction / admission
    #     for LLM-serving rows; skipped (and bitwise inert) without any ---
    with jax.named_scope(SCOPE_SERVING):
        st = jax.lax.cond(
            kvserve.serving_needed(scn, st),
            lambda s: kvserve.serving_phase(scn, s),
            lambda s: s,
            st,
        )

    with jax.named_scope(SCOPE_BOUND):
        rate, vm_mips, active, bound_dt, cand_ts = _phase_bound(
            scn, st, aux, instruments
        )

    # --- fused advance: completion min-reduce + work depletion ---
    with jax.named_scope(SCOPE_ADVANCE):
        dt, new_rem = ctx.advance(st.rem_mi, rate, active, bound_dt)

    with jax.named_scope(SCOPE_COMMIT):
        return _phase_commit(
            scn, st, aux, instruments, rate, vm_mips, active, cand_ts, dt,
            new_rem,
        )


# ---------------------------------------------------------------------------
# batch-major step: the campaign dimension inside the program (DESIGN.md §10)
# ---------------------------------------------------------------------------


def batch_live(scn_b: Scenario, st_b: SimState, max_steps: int) -> Array:
    """[B] per-row loop-continuation mask — ``step_cond`` vmapped over the
    scenario axis.  The batch drivers' loop condition is ``any(live)``."""
    return jax.vmap(lambda scn, st: step_cond(scn, st, max_steps))(
        scn_b, st_b
    )


def _freeze(live: Array, new, old):
    """Per-leaf row select: live rows take the stepped value, finished rows
    stay bitwise frozen at their final state (early-exit masking)."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            live.reshape(live.shape + (1,) * (a.ndim - 1)), a, b
        ),
        new,
        old,
    )


def batch_event_step(
    scn_b: Scenario,
    carry: tuple[SimState, tuple],
    ctx: StepContext,
    extra_instruments: tuple,
    max_steps: int,
) -> tuple[tuple[SimState, tuple], StepEvent, Array]:
    """Advance a ``[B, ...]`` batch of scenarios by one event batch each.

    The same phases as ``event_step``, vmapped over the scenario axis, with
    three batch-major specifics:

    * **phase skipping** — the provisioning scan and broker dispatch run
      under *scalar* ``lax.cond``s on batch-global predicates
      (``any(needed & live)``), so an event where no live row has work for
      the phase skips it for the whole batch — the cost structure
      vmap-of-``simulate`` cannot express (its conds lower to selects).
    * **batch-grid advance** — the advance sweep is called *outside* the
      vmapped phases on the full ``[B, C]`` block, so ``sweep_impl="pallas"``
      lands on the fused batch-grid kernel (one grid step per scenario row).
    * **early-exit masking** — rows whose ``step_cond`` is already False are
      frozen: every state/aux write is row-gated by ``live``, so a finished
      scenario's trajectory is bitwise that of its solo run no matter how
      long the batch keeps looping.

    Instruments are rebuilt per row inside the vmapped closures
    (``instruments_for``), so batched ``Scenario.instruments`` leaves map
    per-row while driver ``extra_instruments`` stay captured unbatched.
    Returns ``(carry', event batch, live)`` — dead rows' event fields are
    garbage and must be masked with ``live`` by observers.
    """
    st_b, aux_b = carry
    extras = tuple(extra_instruments)
    with jax.named_scope(SCOPE_LOOP_COND):
        live = batch_live(scn_b, st_b, max_steps)

    def prologue(scn, st, aux):
        return _phase_prologue(scn, st, aux, instruments_for(scn, extras))

    with jax.named_scope(SCOPE_PROLOGUE):
        st1, aux1 = jax.vmap(prologue)(scn_b, st_b, aux_b)

    # --- VM placement + broker dispatch: batch-global skip predicates ---
    with jax.named_scope(SCOPE_PROVISION):
        need_prov = jnp.any(jax.vmap(_provision_needed)(scn_b, st1) & live)
        st2 = jax.lax.cond(
            need_prov,
            lambda s: jax.vmap(
                lambda scn, st: provision.provision_due_vms(scn, st)[0]
            )(scn_b, s),
            lambda s: s,
            st1,
        )
    with jax.named_scope(SCOPE_DISPATCH):
        need_disp = jnp.any(jax.vmap(_dispatch_needed)(scn_b, st2) & live)
        st3 = jax.lax.cond(
            need_disp,
            lambda s: jax.vmap(provision.dispatch_cloudlets)(scn_b, s),
            lambda s: s,
            st2,
        )

    if scn_b.topology is not None:
        with jax.named_scope(SCOPE_TRANSFER):
            need_xfer = jnp.any(
                jax.vmap(provision.transfer_needed)(scn_b, st3) & live
            )
            st3 = jax.lax.cond(
                need_xfer,
                lambda s: jax.vmap(provision.transfer_phase)(scn_b, s),
                lambda s: s,
                st3,
            )

    with jax.named_scope(SCOPE_SERVING):
        need_srv = jnp.any(
            jax.vmap(kvserve.serving_needed)(scn_b, st3) & live
        )
        st3 = jax.lax.cond(
            need_srv,
            lambda s: jax.vmap(kvserve.serving_phase)(scn_b, s),
            lambda s: s,
            st3,
        )

    def bound(scn, st, aux):
        return _phase_bound(scn, st, aux, instruments_for(scn, extras))

    with jax.named_scope(SCOPE_BOUND):
        rate, vm_mips, active, bound_dt, cand_ts = jax.vmap(bound)(
            scn_b, st3, aux1
        )

    # --- batch-grid advance on the whole [B, C] block (outside the vmap) ---
    with jax.named_scope(SCOPE_ADVANCE):
        dt, new_rem = ctx.advance(st3.rem_mi, rate, active, bound_dt)

    def commit(scn, st, aux, rate, vm_mips, active, cand_ts, dt, new_rem):
        return _phase_commit(
            scn, st, aux, instruments_for(scn, extras),
            rate, vm_mips, active, cand_ts, dt, new_rem,
        )

    with jax.named_scope(SCOPE_COMMIT):
        (st4, aux2), ev = jax.vmap(commit)(
            scn_b, st3, aux1, rate, vm_mips, active, cand_ts, dt, new_rem
        )

    with jax.named_scope(SCOPE_FREEZE):
        carry2 = _freeze(live, (st4, aux2), (st_b, aux_b))
    return carry2, ev, live


def finalize_outputs_for(
    scn: Scenario, st: SimState, aux: tuple, extra_instruments: tuple = ()
) -> dict:
    """Collect instrument outputs keyed by name, rebuilding the instrument
    tuple from the (per-row) scenario — the batch drivers' vmapped twin of
    ``finalize_outputs``."""
    out: dict = {}
    for ins, a in zip(instruments_for(scn, extra_instruments), aux):
        o = ins.finalize(scn, st, a)
        if o:
            out[ins.name] = o
    return out


def _masked_pct(x: Array, mask: Array, q: float) -> Array:
    """Nearest-rank percentile of ``x`` over ``mask`` rows; INF when empty."""
    xs = jnp.sort(jnp.where(mask, x, INF))
    k = jnp.sum(mask.astype(jnp.int32))
    idx = jnp.clip(
        jnp.ceil(q * k.astype(jnp.float32)).astype(jnp.int32) - 1,
        0, x.shape[0] - 1,
    )
    return jnp.where(k > 0, xs[idx], INF)


def finalize_result(scn: Scenario, st: SimState) -> SimResult:
    """Assemble the reported outcome from a final state (shared by drivers)."""
    cls = scn.cloudlets
    fin = policies.cloudlet_finished(st) & cls.exists
    tat = jnp.where(fin, st.finish_t - cls.submit_t, INF)
    n_fin = jnp.sum(fin.astype(jnp.int32))
    mean_tat = jnp.sum(jnp.where(fin, tat, 0.0)) / jnp.maximum(n_fin, 1)
    makespan = jnp.max(jnp.where(fin, st.finish_t, -INF), initial=-INF)
    total_cost = jnp.sum(
        st.cpu_cost + st.ram_cost + st.storage_cost + st.bw_cost
    )
    # serving tail latency (DESIGN.md §14): TTFT is queueing + KV admission
    # delay until the first decode step; TPOT the observed per-token pace
    # including any preemption stalls.  INF marks "no finished serving rows".
    sfin = fin & (cls.prompt_tokens > 0.0)
    ttft = jnp.where(sfin, st.start_t - cls.submit_t, INF)
    tpot = jnp.where(
        sfin,
        (st.finish_t - st.start_t) / jnp.maximum(cls.max_new_tokens, 1.0),
        INF,
    )
    return SimResult(
        finish_t=st.finish_t,
        start_t=st.start_t,
        cl_vm=st.cl_vm,
        turnaround=tat,
        makespan=makespan,
        mean_turnaround=mean_tat,
        n_finished=n_fin,
        n_events=st.step,
        n_migrations=jnp.sum(st.vm_migrations),
        vm_placed=st.vm_placed,
        vm_dc=st.vm_dc,
        vm_failed=st.vm_failed,
        cpu_cost=st.cpu_cost,
        ram_cost=st.ram_cost,
        storage_cost=st.storage_cost,
        bw_cost=st.bw_cost,
        energy_j=st.energy_j,
        total_cost=total_cost,
        end_t=st.t,
        sla_violations=jnp.sum(
            policies.sla_violation_mask(scn, st).astype(jnp.int32)),
        downtime=jnp.sum(st.vm_downtime),
        n_evacuations=st.n_evacuations,
        ttft_p50=_masked_pct(ttft, sfin, 0.50),
        ttft_p99=_masked_pct(ttft, sfin, 0.99),
        tpot_p50=_masked_pct(tpot, sfin, 0.50),
        tpot_p99=_masked_pct(tpot, sfin, 0.99),
    )


def finalize_outputs(
    scn: Scenario, st: SimState, ctx: StepContext, aux: tuple
) -> dict:
    """Collect instrument outputs keyed by instrument name."""
    out: dict = {}
    for ins, a in zip(ctx.instruments, aux):
        o = ins.finalize(scn, st, a)
        if o:
            out[ins.name] = o
    return out
