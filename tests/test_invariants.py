"""Simulation-invariant suite: property-style conservation checks swept over
every scenario constructor in ``scenarios.py``.

Four families, each phrased against the public Instrument/driver surface so
they hold for *any* engine change, not one code path:

* **work conservation** — integrating the piecewise-constant rates over the
  emitted events reproduces each cloudlet's depleted work *plus* whatever
  checkpoint rollbacks re-queued (``SimState.cl_rollback_mi`` — zero without
  failures, so the classic equality is the special case); finished rows
  integrate to their full ``length_mi`` + re-done work (within the engine's
  documented float32 finish tolerance).
* **capacity** — granted host MIPS never exceeds host capacity at any event,
  and the free-resource ledgers (RAM/storage/bandwidth — cores too under
  ``core_reserving``) never go negative — including through failure
  revocation and re-placement (DESIGN.md §9).
* **time** — event times are non-decreasing with non-negative intervals
  (``simulate_history`` rows).
* **federation gate** — ``n_migrations == 0`` whenever federation is off.
* **reliability gate** — ``n_evacuations == 0`` and ``downtime == 0``
  whenever the outage schedule is all-INF padding (MTBF = ∞).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SPACE_SHARED,
    TIME_SHARED,
    scenarios,
    simulate,
    simulate_history,
    simulate_instrumented,
    step,
)
from repro.core import energy as energy_mod
from repro.core.pytree import pytree_dataclass

pytestmark = pytest.mark.tier1


def _all_scenarios():
    """One small instance per scenario constructor in scenarios.py."""
    key = jax.random.PRNGKey(0)
    return [
        ("fig4_ss", scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)),
        ("fig4_tt", scenarios.fig4_scenario(TIME_SHARED, TIME_SHARED)),
        ("fig7_8", scenarios.fig7_8_scenario(32)),
        ("fig9_10", scenarios.fig9_10_scenario(
            TIME_SHARED, n_hosts=40, n_vms=4, n_groups=2)),
        ("table1_fed", scenarios.table1_scenario(True)),
        ("table1_nofed", scenarios.table1_scenario(False)),
        ("generated", scenarios.generated_scenario(
            key, kind="poisson", n_cloudlets=16, n_vms=4, n_hosts=4,
            rate=0.2, median_mi=10_000.0)),
        ("autoscale", scenarios.autoscale_scenario(
            key, scale_down_thresh=0.05)),
        ("consolidation", scenarios.consolidation_scenario()),
        ("balance", scenarios.balance_scenario()),
        ("reliability", scenarios.reliability_scenario(
            key, evacuation=True, ckpt_interval=25_000.0)),
        ("reliability_inf", scenarios.reliability_scenario(
            None, evacuation=True)),
        ("evacuation", scenarios.evacuation_scenario()),
        ("evacuation_ctrl", scenarios.evacuation_scenario(
            evacuation=False, ckpt_interval=3.0e38)),
        ("staging", scenarios.staging_scenario(n_cloudlets=24)),
        ("staging_loc", scenarios.staging_scenario(
            n_cloudlets=24, locality_dispatch=True)),
    ]


_IDS = [name for name, _ in _all_scenarios()]


def _run_instrumented(scn, extra):
    # private jit target: jax.jit caches per underlying function object, so
    # jitting simulate_instrumented directly would pollute the cache-size
    # assertions other test modules make about their own wrappers
    return simulate_instrumented(scn, extra)


@pytree_dataclass
class _ConservationInstrument(step.Instrument):
    """Per-cloudlet integral of rate·dt over the emitted events."""

    name = "conservation"

    def init(self, scn):
        return jnp.zeros((scn.cloudlets.n_cloudlets,), jnp.float32)

    def post(self, scn, st, ev, aux):
        return st, aux + jnp.where(ev.active, ev.rate * ev.dt, 0.0)

    def finalize(self, scn, st, aux):
        return {
            "executed_mi": aux,
            "rem_mi": st.rem_mi,
            "rollback_mi": st.cl_rollback_mi,
        }


@pytree_dataclass
class _CapacityInstrument(step.Instrument):
    """Worst-case (over events) host over-grant and ledger undershoot."""

    name = "capacity"

    def init(self, scn):
        z = jnp.asarray(0.0, jnp.float32)
        return (z, z, z)  # max over-grant, min free resource, min free cores

    def post(self, scn, st, ev, aux):
        over, min_free, min_cores = aux
        granted = energy_mod.host_granted_mips(scn, st, vm_mips=ev.vm_mips)
        cap = scn.hosts.cores.astype(jnp.float32) * scn.hosts.mips
        over = jnp.maximum(
            over,
            jnp.max(jnp.where(scn.hosts.exists, granted - cap, -jnp.inf)),
        )
        free = jnp.minimum(
            jnp.minimum(jnp.min(st.free_ram), jnp.min(st.free_storage)),
            jnp.min(st.free_bw),
        )
        return st, (
            over,
            jnp.minimum(min_free, free),
            jnp.minimum(min_cores, jnp.min(st.free_cores)),
        )

    def finalize(self, scn, st, aux):
        return {
            "max_over_grant": aux[0],
            "min_free": aux[1],
            "min_free_cores": aux[2],
        }


@pytest.mark.parametrize("name,scn", _all_scenarios(), ids=_IDS)
def test_conservation_and_capacity(name, scn):
    res, out = jax.jit(_run_instrumented)(
        scn, (_ConservationInstrument(), _CapacityInstrument()))

    # --- work conservation (modulo rollback): integral of rates ==
    #     depleted work + MI re-queued by failure rollbacks (exactly zero
    #     for every scenario without an outage schedule) ---
    executed = np.array(out["conservation"]["executed_mi"])
    rem = np.array(out["conservation"]["rem_mi"])
    rollback = np.array(out["conservation"]["rollback_mi"])
    length = np.array(scn.cloudlets.length_mi)
    exists = np.array(scn.cloudlets.exists)
    if scn.outages is None:
        assert (rollback == 0).all(), f"{name}: rollback without outages"
    assert (rollback >= 0).all(), f"{name}: negative rollback"
    np.testing.assert_allclose(
        executed[exists], (length - rem + rollback)[exists],
        rtol=1e-4, atol=1.0,
        err_msg=f"{name}: rate·dt integral != depleted + rolled-back work")
    fin = np.isfinite(np.array(res.finish_t)) & (
        np.array(res.finish_t) < 1e30)
    # finished rows executed their full submitted work plus whatever the
    # rollbacks made them re-do (within the engine's documented finish
    # tolerance, step._eps_mi)
    np.testing.assert_allclose(
        executed[fin], (length + rollback)[fin], rtol=2e-3, atol=1.0,
        err_msg=f"{name}: finished cloudlets lost work")

    # --- capacity: grants bounded, ledgers non-negative ---
    assert float(out["capacity"]["max_over_grant"]) <= 0.5, name
    assert float(out["capacity"]["min_free"]) >= -1e-3, name
    if bool(scn.policy.core_reserving):
        assert float(out["capacity"]["min_free_cores"]) >= -1e-3, name

    # --- federation gate ---
    if not bool(scn.policy.federation):
        assert int(res.n_migrations) == 0, name


@pytest.mark.parametrize(
    "name,scn",
    [s for s in _all_scenarios()
     if s[0] in ("fig4_ss", "table1_fed", "autoscale", "consolidation",
                 "reliability", "evacuation")],
    ids=["fig4_ss", "table1_fed", "autoscale", "consolidation",
         "reliability", "evacuation"],
)
def test_event_times_monotone(name, scn):
    res, hist = jax.jit(simulate_history)(scn)
    v = np.array(hist.valid)
    t = np.array(hist.t)[v]
    dt = np.array(hist.dt)[v]
    assert (dt >= 0).all(), name
    assert (np.diff(t) >= -1e-6).all(), name
    assert int(res.n_events) == int(v.sum()), name


@pytest.mark.parametrize("name,scn", _all_scenarios(), ids=_IDS)
def test_no_migrations_with_federation_off(name, scn):
    """Forcing the traced federation flag off zeroes migrations everywhere —
    creation-time overflow, the live MigrationInstrument, and proactive
    evacuation alike."""
    scn = scn.replace(policy=scn.policy.replace(
        federation=jnp.asarray(False)))
    res = jax.jit(simulate)(scn)
    assert int(res.n_migrations) == 0, name
    assert int(res.n_evacuations) == 0, name


def _neutral_topology_scenarios():
    """Scenarios where no two transfers ever share a link: the regime where
    attaching a *neutral* topology (uniform bandwidth equal to the flat
    ``interdc_bw_mbps`` divisor, zero latency) must be bitwise invisible.
    Contended scenarios are excluded by design — fair sharing on a shared
    link is exactly the behavior the ledger is meant to change
    (tests/test_network.py pins those numbers)."""
    key = jax.random.PRNGKey(0)
    return [
        ("fig4_ss", scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)),
        ("fig4_tt", scenarios.fig4_scenario(TIME_SHARED, TIME_SHARED)),
        ("fig7_8", scenarios.fig7_8_scenario(16)),
        ("generated", scenarios.generated_scenario(
            key, kind="poisson", n_cloudlets=16, n_vms=4, n_hosts=4,
            rate=0.2, median_mi=10_000.0)),
        ("single_overflow", scenarios.table1_scenario(True, n_vms=8)),
        ("balance", scenarios.balance_scenario()),
        # consolidation_scenario is intentionally absent: its sensor tick
        # commits two live migrations over one link in the same event, so
        # the fair-share recompute (correctly) diverges from the flat path
    ]


_NEUTRAL_IDS = [name for name, _ in _neutral_topology_scenarios()]


@pytest.mark.parametrize(
    "name,scn", _neutral_topology_scenarios(), ids=_NEUTRAL_IDS)
def test_neutral_topology_is_bitwise_flat(name, scn):
    """The topology-vs-flat equivalence lock (DESIGN.md §13): a uniform
    topology with ``bw_mbps == Policy.interdc_bw_mbps`` and zero latency
    yields a bit-identical ``SimResult`` to ``topology=None`` — through the
    plain, traced, and batch-major drivers."""
    import dataclasses

    from repro.core import simulate_trace, stack_scenarios

    topo = energy_mod.Topology.uniform(
        scn.hosts.n_dc, latency_s=0.0,
        bw_mbps=float(scn.policy.interdc_bw_mbps))
    scn_t = scn.replace(topology=topo)
    res = jax.jit(simulate)(scn)
    res_t = jax.jit(simulate)(scn_t)
    for f in dataclasses.fields(res):
        np.testing.assert_array_equal(
            np.array(getattr(res, f.name)), np.array(getattr(res_t, f.name)),
            err_msg=f"{name}: SimResult.{f.name} diverged (plain)")
    ts = jnp.asarray(np.arange(0.0, 3000.0, 401.0, dtype=np.float32))
    res_tr, _ = simulate_trace(scn_t, ts)
    for f in dataclasses.fields(res):
        np.testing.assert_array_equal(
            np.array(getattr(res, f.name)),
            np.array(getattr(res_tr, f.name)),
            err_msg=f"{name}: SimResult.{f.name} diverged (trace)")
    res_b = jax.jit(simulate)(stack_scenarios([scn_t, scn_t]))
    for f in dataclasses.fields(res):
        a, b = getattr(res, f.name), getattr(res_b, f.name)
        if a is None:   # an optional result group (SimResult.power) not attached
            assert b is None, f"{name}: SimResult.{f.name} appeared (batch-major)"
            continue
        np.testing.assert_array_equal(
            np.array(a), np.array(b)[0],
            err_msg=f"{name}: SimResult.{f.name} diverged (batch-major)")


@pytest.mark.parametrize("name,scn", _all_scenarios(), ids=_IDS)
def test_no_failures_without_outage_windows(name, scn):
    """MTBF = ∞ (an all-INF schedule — or no schedule at all) means the
    reliability subsystem never fires: no evacuations, no downtime, no
    rollback, even with the evacuation policy armed."""
    if scn.outages is not None and bool(
            np.any(np.array(scn.outages.fail_t) < 1e30)):
        pytest.skip("scenario schedules real outages")
    res, out = jax.jit(_run_instrumented)(scn, (_ConservationInstrument(),))
    assert int(res.n_evacuations) == 0, name
    assert float(res.downtime) == 0.0, name
    assert (np.array(out["conservation"]["rollback_mi"]) == 0).all(), name
