"""Auto-scaling subsystem: pool lifecycle, K_SCALE events, the acceptance
demo (bursty workload: autoscaled beats static fleet), and the 64-point
arrival-rate x threshold grid in one vmap."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    broadcast_campaign,
    run_campaign,
    scenarios,
    simulate,
    simulate_history,
    simulate_instrumented,
    step,
    workload,
)

pytestmark = pytest.mark.tier1


def _autoscale_off(scn):
    return scn.replace(
        policy=scn.policy.replace(autoscale=jnp.asarray(False)))


def test_autoscale_improves_bursty_turnaround():
    """THE demo (ISSUE acceptance): under a bursty generated workload the
    autoscaled pool beats the same scenario with the pool disabled, all work
    finishing in both — and both runs are the same compiled program (the
    autoscale flag is traced, no Python branching on load)."""
    # a fresh callable: jit caches pool per underlying function, so another
    # test's jax.jit(simulate_instrumented) in this process would count here
    fn = jax.jit(lambda s: simulate_instrumented(s))
    results = {}
    for name, scn in (
        ("on", scenarios.autoscale_scenario(jax.random.PRNGKey(0))),
        ("off", _autoscale_off(scenarios.autoscale_scenario(jax.random.PRNGKey(0)))),
    ):
        res, out = fn(scn)
        assert int(res.n_finished) == scn.cloudlets.n_cloudlets, name
        results[name] = (res, out)
    assert fn._cache_size() == 1, "on/off must share one compilation"
    res_on, out_on = results["on"]
    res_off, out_off = results["off"]
    assert int(out_on["autoscale"]["n_scale_up"]) > 0
    assert int(out_off["autoscale"]["n_scale_up"]) == 0
    assert float(res_on.mean_turnaround) < 0.9 * float(res_off.mean_turnaround)
    # the static fleet never touches the pool rows
    assert np.array(res_off.vm_placed).sum() == 4
    assert np.array(res_on.vm_placed).sum() == 8


def test_scale_up_lifecycle_and_boot_latency():
    """Activated pool VMs boot with the fixed creation latency before doing
    work: K_SCALE events appear in the history, and activations are gradual
    (one per DC per tick)."""
    scn = scenarios.autoscale_scenario(jax.random.PRNGKey(3))
    res, hist = jax.jit(simulate_history)(scn)
    v = np.array(hist.valid)
    kinds = np.array(hist.kind)[v]
    assert (kinds == step.K_SCALE).any(), "autoscaler ticks must be events"
    assert (kinds == step.K_COMPLETION).any()
    # scale tick period is respected: consecutive K_SCALE events >= interval
    ts = np.array(hist.t)[v][kinds == step.K_SCALE]
    assert (np.diff(ts) >= float(scn.policy.sensor_interval) - 1e-3).all()


def test_scale_down_releases_idle_pool():
    """With a scale-down threshold, pool VMs activated for burst 1 are
    released in the following lull (terminal: inactive -> activating ->
    active -> released), returning their host resources."""
    scn = scenarios.autoscale_scenario(
        jax.random.PRNGKey(1), scale_down_thresh=0.05)
    res, out = jax.jit(simulate_instrumented)(scn)
    assert int(out["autoscale"]["n_scale_up"]) > 0
    assert int(out["autoscale"]["n_scale_down"]) > 0
    assert int(res.n_finished) == scn.cloudlets.n_cloudlets


def test_pool_row_reactivates_across_bursts():
    """Pool rows are re-activatable (ROADMAP follow-up): with a single pool
    row and a scale-down threshold over a bursty trace, the same row must
    activate -> release -> re-activate (n_scale_up >= 2 with n_pool=1 can
    only mean the one row cycled the lifecycle), finishing all work."""
    scn = scenarios.autoscale_scenario(
        jax.random.PRNGKey(0), n_pool=1, scale_down_thresh=0.05)
    res, out = jax.jit(simulate_instrumented)(scn)
    assert int(out["autoscale"]["n_scale_up"]) >= 2
    assert int(out["autoscale"]["n_scale_down"]) >= 1
    assert int(res.n_finished) == scn.cloudlets.n_cloudlets
    # the recycled row ends the run placed again (its final activation)
    assert np.array(res.vm_placed).sum() == 5


def test_pool_invisible_without_autoscale():
    """A scenario whose pool is never activated is bit-identical to one with
    no pool rows at all: spare rows are dead weight, not a perturbation."""
    scn = _autoscale_off(scenarios.autoscale_scenario(jax.random.PRNGKey(5)))
    res = jax.jit(simulate)(scn)
    # same infra, but the pool hosts exist and stay empty: all 48 cloudlets
    # keep to the 4 base VMs
    vm_of = np.array(res.vm_placed)
    assert vm_of[:4].all() and not vm_of[4:].any()
    assert int(res.n_finished) == 48


def test_service_routing_balances_load():
    """Broker dispatch spreads arrivals across the active fleet instead of
    piling onto one VM: final assignments (SimResult.cl_vm) are balanced."""
    scn = _autoscale_off(scenarios.autoscale_scenario(jax.random.PRNGKey(2)))
    res = jax.jit(simulate)(scn)
    cl_vm = np.array(res.cl_vm)
    assert (cl_vm >= 0).all(), "every service row must have been dispatched"
    counts = np.bincount(cl_vm, minlength=8)
    assert (counts[:4] >= 6).all(), counts      # 48 rows over 4 base VMs
    assert not counts[4:].any()                 # pool never activated


def test_grid_campaign_64_points_one_vmap():
    """ISSUE acceptance: run_campaign sweeps an 8 arrival-rate x 8 threshold
    grid (64 scenarios: vmapped generated workloads + swept traced policy)
    in one vmap, every cell finishing all work."""
    template = scenarios.autoscale_scenario(jax.random.PRNGKey(0))
    K = 64
    rates = jnp.tile(jnp.linspace(0.05, 0.2, 8), 8)
    ups = jnp.repeat(jnp.linspace(0.3, 1.0, 8), 8)
    keys = jax.random.split(jax.random.PRNGKey(7), K)
    cls = jax.vmap(lambda k, r: workload.generate_cloudlets(
        k, 48, kind="bursty", n_bursts=3, rate=r, off_gap_mean=800.0,
        median_mi=60_000.0, sigma_mi=0.3, n_vms=None))(keys, rates)
    pol = jax.vmap(
        lambda u: template.policy.replace(scale_up_thresh=u))(ups)
    batched = broadcast_campaign(template, K, cloudlets=cls, policy=pol)
    res = run_campaign(batched)
    assert (np.array(res.n_finished) == 48).all()
    tat = np.array(res.mean_turnaround)
    assert np.isfinite(tat).all() and (tat > 0).all()
    # thresholds bite: the permissive half of the grid scales earlier and
    # beats the restrictive half on average over the same arrival rates
    lo = tat[np.array(ups) <= 0.6].mean()
    hi = tat[np.array(ups) > 0.6].mean()
    assert lo < hi


def test_broadcast_campaign_validates_leading_dim():
    template = scenarios.autoscale_scenario(jax.random.PRNGKey(0))
    cls = jax.vmap(lambda k: workload.generate_cloudlets(
        k, 48, kind="bursty", n_bursts=3, rate=0.1, n_vms=None)
    )(jax.random.split(jax.random.PRNGKey(1), 8))
    with pytest.raises(ValueError, match="leading dim"):
        broadcast_campaign(template, 16, cloudlets=cls)
