"""Simulation drivers over the ``event_step`` kernel (paper §4.1).

CloudSim advances the world between *events*: rates are piecewise-constant,
so each ``updateVMsProcessing()`` sweep returns the next expected completion
time and the clock jumps straight to the earliest one.  The loop body is
composed once, in ``core/step.py``; this module writes each loop form once,
over the row mapping (``step.Rows``) that the input's rank chooses: one
scenario, or a stacked ``[B]`` campaign run batch-major (``is_batched``).
Init, the loop condition, the step and finalize all go through it.

* ``simulate``          — ``lax.while_loop`` until horizon/completion; the
                          production path, pure/jittable/vmappable.
* ``simulate_trace``    — same loop with a ``TraceInstrument`` observer
                          attached: per-cloudlet progress at sample times,
                          reconstructed *exactly* by interpolation under the
                          piecewise-constant rates — the event stream (and so
                          every ``SimResult`` field, including cost/energy)
                          is bit-identical to ``simulate``.
* ``simulate_history``  — fixed-length ``lax.scan`` emitting the full
                          per-event log (time, kind, per-DC utilization /
                          cost / energy snapshots): the scenario-analysis
                          surface for Figure 9/10-style timelines.

Equivalence argument (DESIGN.md §2): for CloudSim's model class — linear
work depletion under piecewise-constant allocations, with all state changes
triggered by the event kinds in step.py — jumping to the min of those bounds
and re-running the two-level policy sweep produces the same trajectory as
SimJava's event queue, without materializing a queue at all.

The whole loop is jittable, differentiable in the rates (not used), and
vmappable: a *campaign* of thousands of simulations runs as one program
(see campaign.py), which is this paper's "repeatable, free-of-cost
experimentation" scaled to a pod.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from repro.core import consolidate, energy, policies
from repro.core import step as step_mod
from repro.core.entities import (
    INF,
    Scenario,
    SimResult,
    SimState,
)
from repro.core.pytree import pytree_dataclass
from repro.core.step import (  # re-exported: the kernel surface
    Instrument,
    StepContext,
    StepEvent,
    TraceInstrument,
    UtilizationTimelineInstrument,
    default_max_steps,
    event_step,
    finalize_result,
    make_context,
)


def init_state(scn: Scenario) -> SimState:
    hosts, vms, cls = scn.hosts, scn.vms, scn.cloudlets
    D, H = hosts.cores.shape
    V, C = vms.n_vms, cls.n_cloudlets
    f32, i32 = jnp.float32, jnp.int32
    ready0 = jnp.where(cls.vm >= 0, step_mod.ready_times(scn), INF)
    if scn.topology is not None:
        # network stage-ins (input_dc >= 0) wait for the transfer phase to
        # open them on the link ledger (DESIGN.md §13); an idle ledger grants
        # each link its full bandwidth to its first transfer
        ready0 = jnp.where(cls.input_dc >= 0, INF, ready0)
        link_share0 = jnp.asarray(scn.topology.bw_mbps, f32)
    else:
        link_share0 = jnp.zeros((D, D), f32)
    return SimState(
        t=jnp.asarray(0.0, f32),
        step=jnp.asarray(0, i32),
        vm_host=jnp.full((V,), -1, i32),
        vm_dc=vms.dc.astype(i32),
        vm_placed=jnp.zeros((V,), bool),
        vm_failed=jnp.zeros((V,), bool),
        vm_evicted=jnp.zeros((V,), bool),
        vm_avail_t=jnp.full((V,), INF, f32),
        vm_released=jnp.zeros((V,), bool),
        vm_migrations=jnp.zeros((V,), i32),
        vm_mig_src=jnp.full((V,), -1, i32),
        pool_active=jnp.zeros((V,), bool),
        # a schedule that starts down (fail_t[k] <= 0) flips this at the
        # first event, before anything is placed
        host_up=jnp.asarray(hosts.exists),
        free_ram=jnp.where(hosts.exists, hosts.ram_mb, 0.0),
        free_storage=jnp.where(hosts.exists, hosts.storage_mb, 0.0),
        free_bw=jnp.where(hosts.exists, hosts.bw_mbps, 0.0),
        free_cores=jnp.where(hosts.exists, hosts.cores.astype(f32), 0.0),
        free_kv=jnp.where(hosts.exists, hosts.kv_blocks, 0.0),
        cl_vm=cls.vm.astype(i32),
        cl_ready_t=ready0,
        cl_admitted=jnp.zeros((C,), bool),
        cl_kv=jnp.zeros((C,), f32),
        rem_mi=jnp.where(cls.exists, cls.length_mi, 0.0),
        cl_rollback_mi=jnp.zeros((C,), f32),
        started=jnp.zeros((C,), bool),
        start_t=jnp.full((C,), INF, f32),
        finish_t=jnp.where(cls.exists, INF, -INF),  # ghosts count as finished
        cpu_time=jnp.zeros((C,), f32),
        sensed_load=jnp.zeros((D,), f32),
        last_tick=jnp.asarray(0.0, f32),
        cpu_cost=jnp.zeros((D,), f32),
        ram_cost=jnp.zeros((D,), f32),
        storage_cost=jnp.zeros((D,), f32),
        bw_cost=jnp.zeros((D,), f32),
        energy_j=jnp.zeros((D,), f32),
        vm_downtime=jnp.zeros((V,), f32),
        n_evacuations=jnp.asarray(0, i32),
        link_busy=jnp.zeros((D, D), i32),
        link_share=link_share0,
        vm_xfer_src=jnp.full((V,), -1, i32),
        vm_xfer_dst=jnp.full((V,), -1, i32),
        vm_xfer_rem=jnp.zeros((V,), f32),
        vm_xfer_share=jnp.zeros((V,), f32),
        cl_xfer_dst=jnp.full((C,), -1, i32),
        cl_xfer_rem=jnp.zeros((C,), f32),
        cl_xfer_share=jnp.zeros((C,), f32),
        consol=(None if scn.dynamic_consolidation is None
                else consolidate.init_power_state(scn)),
    )


def is_batched(scn: Scenario) -> bool:
    """Batch-major detection by rank (DESIGN.md §10): ``hosts.cores`` is
    ``[D, H]`` for one scenario, ``[B, D, H]`` for a stacked campaign.
    Under ``jax.vmap`` the per-row view is rank-2 again, so
    ``vmap(simulate)`` still composes with the single-scenario form — which
    is what keeps it an honest baseline for the batch-major form."""
    return jnp.ndim(scn.hosts.cores) == 3


def scenario_row(scn: Scenario, i: int = 0) -> Scenario:
    """One row of a stacked campaign (static fields pass through)."""
    return jax.tree.map(lambda x: x[i], scn)


def _start(scn: Scenario, extras: tuple):
    """What both loop forms start from: the row mapping chosen by rank, the
    step context, the initial carry and the step budget.

    ``make_context`` / ``resolve_max_steps`` read only static shape and
    instrument-structure information, so a batch's row 0 stands in for
    every row (``stack_scenarios`` enforces static-field agreement).
    """
    rows = step_mod.Rows(batched=is_batched(scn))
    with jax.named_scope(step_mod.SCOPE_INIT):
        one = scenario_row(scn) if rows.batched else scn
        ctx = make_context(one, extras)
        carry = (rows.map(init_state)(scn),
                 rows.map(lambda s: step_mod.init_aux(s, extras))(scn))
    return rows, ctx, carry, step_mod.resolve_max_steps(one, ctx.instruments)


def _step(rows, scn, carry, ctx, extras, max_steps, masked):
    """One event step by the public name of the input's form, so a patched
    ``event_step`` or ``batch_event_step`` is what runs.

    Returns ``(carry', event, live)``.  A batch is always masked: its
    finished rows stay frozen.  One scenario is masked only where asked: a
    while loop steps it only while it runs (``live`` None), a fixed-length
    scan also past its end.
    """
    if rows.batched:
        return step_mod.batch_event_step(scn, carry, ctx, extras, max_steps)
    if not masked:
        return (*event_step(scn, carry, ctx), None)
    live = rows.live(scn, carry[0], max_steps)
    stepped, ev = event_step(scn, carry, ctx)
    return step_mod.freeze(live, stepped, carry), ev, live


def simulate_instrumented(
    scn: Scenario, extra_instruments: tuple = ()
) -> tuple[SimResult, dict]:
    """Run one simulation and collect instrument outputs (by instrument name).

    Instruments = step defaults + ``Scenario.instruments`` + ``extra_instruments``.
    The one ``while_loop`` driver: a stacked campaign (``is_batched``) loops
    while any row is live, every row advanced natively by the batch-major
    step, finished rows frozen, per-row results bitwise those of the solo
    runs.
    """
    extras = tuple(extra_instruments)
    rows, ctx, carry0, max_steps = _start(scn, extras)

    def cond(carry) -> Array:
        with jax.named_scope(step_mod.SCOPE_LOOP_COND):
            return rows.any(rows.live(scn, carry[0], max_steps))

    def body(carry):
        return _step(rows, scn, carry, ctx, extras, max_steps, False)[0]

    st, aux = jax.lax.while_loop(cond, body, carry0)
    with jax.named_scope(step_mod.SCOPE_FINALIZE):
        res = rows.map(finalize_result)(scn, st)
        out = rows.map(
            lambda s, f, a: step_mod.finalize_outputs(s, f, a, extras)
        )(scn, st, aux)
    return res, out


def entry_points() -> dict:
    """The engine's public driver surface, by stable name.

    ``analysis/simlint.py`` traces exactly these (plus the campaign chunk
    runner, its shard_map-sharded twin, and the Pallas advance kernel) when
    verifying the structural invariants of the compiled program — a new
    driver added here is linted automatically.  Each driver covers both
    row mappings of the one composition: handed a stacked campaign it steps
    through ``batch_event_step``, else through ``event_step`` (see
    ``is_batched``).
    """
    return {
        "simulate": simulate,
        "simulate_trace": simulate_trace,
        "simulate_history": simulate_history,
    }


def simulate(scn: Scenario) -> SimResult:
    """Run one complete simulation; pure, jittable, vmappable.

    A stacked campaign (leading scenario axis, see ``is_batched``) runs
    batch-major: one compiled step advances every row, with early-exit
    masking and batch-global phase skipping — same per-row results, bitwise
    (DESIGN.md §10).
    """
    res, _ = simulate_instrumented(scn)
    return res


def simulate_trace(scn: Scenario, sample_ts: Array) -> tuple[SimResult, Array]:
    """Simulation + progress trace: fraction of work done per cloudlet at each
    ``sample_ts`` point — used by the Figure 9/10 reproduction.

    The trace is a pure observer (``TraceInstrument``): rates are
    piecewise-constant, so mid-interval progress interpolates exactly and no
    extra clock stop is needed.  The returned ``SimResult`` is therefore
    bit-identical to ``simulate(scn)`` — cost and energy included.  Rows of
    the progress matrix follow ``sample_ts`` in ascending order.
    """
    ts = jnp.sort(jnp.asarray(sample_ts, jnp.float32))
    tracer = TraceInstrument(sample_ts=ts)
    res, out = simulate_instrumented(scn, (tracer,))
    return res, out["trace"]["progress"]


@pytree_dataclass
class History:
    """Fixed-length per-event log, leading axis = ``max_steps``.

    Rows past the simulation's end are zero-filled with ``valid=False`` and
    ``kind=-1`` (the fixed shape is what lets a campaign vmap histories).
    """

    t: Array            # [T] f32  clock after each event
    dt: Array           # [T] f32  interval length
    kind: Array         # [T] i32  step.K_* classification (-1: padding)
    valid: Array        # [T] bool event actually happened
    n_finished: Array   # [T] i32  cloudlets finished so far
    utilization: Array  # [T, D] f32 per-DC utilization during the interval
    cpu_cost: Array     # [T, D] f32 accrued CPU cost after the event
    bw_cost: Array      # [T, D] f32 accrued bandwidth cost after the event
    energy_j: Array     # [T, D] f32 accrued energy after the event


def _record(scn: Scenario, st: SimState, ev: StepEvent, live: Array) -> History:
    """One scenario's ``History`` row for an event (zeros unless live)."""
    util = energy.dc_utilization(scn, st, vm_mips=ev.vm_mips)
    n_fin = jnp.sum(
        (policies.cloudlet_finished(st) & scn.cloudlets.exists).astype(jnp.int32)
    )
    return History(
        t=jnp.where(live, ev.t1, 0.0),
        dt=jnp.where(live, ev.dt, 0.0),
        kind=jnp.where(live, ev.kind, -1),
        valid=live,
        n_finished=jnp.where(live, n_fin, 0),
        utilization=jnp.where(live, util, 0.0),
        cpu_cost=jnp.where(live, st.cpu_cost, 0.0),
        bw_cost=jnp.where(live, st.bw_cost, 0.0),
        energy_j=jnp.where(live, st.energy_j, 0.0),
    )


def simulate_history(scn: Scenario) -> tuple[SimResult, History]:
    """Run one simulation emitting the full per-event log.

    The one fixed-length ``lax.scan`` driver: iterations past the end carry
    the final state unchanged and emit invalid rows, so the result is
    bit-identical to ``simulate`` while exposing the whole trajectory — the
    scenario-analysis surface (per-DC utilization/cost/energy timelines) the
    while-loop driver cannot produce.  A stacked campaign emits
    ``[T, B, ...]`` records through the batch-major step (rows frozen once
    finished, exactly like their solo logs).
    """
    rows, ctx, carry0, max_steps = _start(scn, ())

    def body(carry, _):
        carry, ev, live = _step(rows, scn, carry, ctx, (), max_steps, True)
        return carry, rows.map(_record)(scn, carry[0], ev, live)

    (st, _), hist = jax.lax.scan(body, carry0, None, length=max_steps)
    return rows.map(finalize_result)(scn, st), hist
