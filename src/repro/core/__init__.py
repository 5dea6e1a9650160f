"""repro.core — the paper's contribution: a tensorized CloudSim.

Discrete-event simulation of virtualized datacenters (Datacenter -> Host ->
VM -> Cloudlet) with two-level space/time-shared scheduling, FCFS/best-fit VM
provisioning, federation with sensor-driven migration, and market accounting
— as one pure, jittable, vmappable JAX program (see DESIGN.md).

The event-loop body is composed exactly once, over one scenario or a
stacked ``[B]`` batch as the input's rank chooses (``step.event_step`` and
``step.batch_event_step`` are thin over it); ``simulate``, ``simulate_trace``
and ``simulate_history`` are thin drivers, one per loop form, and
cross-cutting observables (energy, market accrual, federation sensing, trace
sampling, …) are composable ``step.Instrument``s.
"""
from repro.core.entities import (
    INF,
    SPACE_SHARED,
    TIME_SHARED,
    Cloudlets,
    Hosts,
    Market,
    Outages,
    Policy,
    Scenario,
    SimResult,
    SimState,
    VMRequests,
    finished_mask,
)
from repro.core.engine import (
    History,
    init_state,
    is_batched,
    scenario_row,
    simulate,
    simulate_history,
    simulate_instrumented,
    simulate_trace,
)
from repro.core.step import (
    AutoscaleInstrument,
    Instrument,
    MigrationInstrument,
    ReliabilityInstrument,
    StepEvent,
    TraceInstrument,
    UtilizationTimelineInstrument,
    event_step,
)
from repro.core.campaign import (
    broadcast_campaign,
    run_campaign,
    run_campaign_sharded,
    stack_scenarios,
)
from repro.core.reducers import (
    ArgBestReducer,
    CampaignReducer,
    HistogramReducer,
    MeanReducer,
    SumReducer,
    ValuesReducer,
)
from repro.core import (
    energy,
    policies,
    provision,
    scenarios,
    search,
    segments,
    step,
    workload,
)

__all__ = [
    "INF", "SPACE_SHARED", "TIME_SHARED",
    "Cloudlets", "Hosts", "Market", "Outages", "Policy", "Scenario",
    "SimResult", "SimState", "VMRequests", "finished_mask",
    "AutoscaleInstrument", "History", "Instrument", "MigrationInstrument",
    "ReliabilityInstrument",
    "StepEvent", "TraceInstrument", "UtilizationTimelineInstrument",
    "init_state", "event_step", "is_batched", "scenario_row",
    "simulate", "simulate_history", "simulate_instrumented", "simulate_trace",
    "broadcast_campaign", "run_campaign", "run_campaign_sharded",
    "stack_scenarios",
    "ArgBestReducer", "CampaignReducer", "HistogramReducer", "MeanReducer",
    "SumReducer", "ValuesReducer",
    "energy", "policies", "provision", "scenarios", "search", "segments",
    "step", "workload",
]
