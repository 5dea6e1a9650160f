"""Runtime (live) VM migration (DESIGN.md §8): consolidation + balance
semantics, progress preservation, determinism, and vmapped threshold-grid
campaigns row-matching a Python loop of single runs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    broadcast_campaign,
    run_campaign,
    scenarios,
    simulate,
    simulate_instrumented,
)

pytestmark = pytest.mark.tier1


def _no_live(scn):
    return scn.replace(
        policy=scn.policy.replace(live_migration=jnp.asarray(False)))


def _run_instrumented(scn):
    # private jit target: jax.jit caches per underlying function object, so
    # jitting simulate_instrumented directly would pollute the cache-size
    # assertions other test modules make about their own wrappers
    return simulate_instrumented(scn)


def test_consolidation_saves_idle_energy():
    """THE demo (ISSUE acceptance): live migration drains DC1's idle VMs
    into DC0's spare slots, the emptied hosts power-gate, and energy drops
    measurably vs the no-migration control — same compiled program (the
    flag is traced), zero lost cloudlets, identical end time."""
    fn = jax.jit(_run_instrumented)
    res_on, out_on = fn(scenarios.consolidation_scenario())
    res_off, out_off = fn(_no_live(scenarios.consolidation_scenario()))
    assert fn._cache_size() == 1, "on/off must share one compilation"
    n_cl = scenarios.consolidation_scenario().cloudlets.n_cloudlets
    assert int(res_on.n_finished) == int(res_off.n_finished) == n_cl
    assert int(res_on.n_migrations) == 4          # all 4 spare images moved
    assert int(res_off.n_migrations) == 0
    assert int(out_on["migration"]["n_consolidate"]) == 4
    assert int(out_on["migration"]["n_balance"]) == 0
    # identical work => identical end time; energy is the only divergence
    assert float(res_on.end_t) == float(res_off.end_t)
    e_on = float(np.sum(np.array(res_on.energy_j)))
    e_off = float(np.sum(np.array(res_off.energy_j)))
    assert e_on < 0.5 * e_off, (e_on, e_off)
    # the drained DC's hosts are empty: every VM ends at DC0
    assert (np.array(res_on.vm_dc) == 0).all()
    # the image transfers hit the inter-DC bandwidth meter at the destination
    assert float(np.array(res_on.bw_cost)[0]) > float(
        np.array(res_off.bw_cost)[0])


def test_balance_move_preserves_progress():
    """A worker VM migrates mid-execution: its cloudlet keeps the 50k MI it
    accrued before the move and finishes exactly one transfer-window later
    than its stay-at-home twin — stop-and-copy, not restart."""
    scn = scenarios.balance_scenario()
    res, out = jax.jit(_run_instrumented)(scn)
    assert int(res.n_finished) == 3
    assert int(res.n_migrations) == 1
    assert int(out["migration"]["n_balance"]) == 1
    fin = np.array(res.finish_t)
    # tick at t=100: both workers hold 950k MI. The migrant stalls for
    # 30 + 1024/100 s then runs at full speed; its twin runs from t=100.
    transfer = 30.0 + 1024.0 / 100.0
    np.testing.assert_allclose(fin[2], 100.0 + 950.0, atol=1.0)
    np.testing.assert_allclose(fin[1], 100.0 + transfer + 950.0, atol=1.0)
    # restart-from-zero would land ~1140s later; preserved progress wins
    ctrl = jax.jit(simulate)(_no_live(scenarios.balance_scenario()))
    assert float(res.makespan) < 0.6 * float(ctrl.makespan)
    assert int(ctrl.n_migrations) == 0


def test_balance_improvement_rule_prevents_ping_pong():
    """A lone busy VM never bounces between two idle DCs: moving it cannot
    shrink the utilization spread, so the improvement rule vetoes it."""
    scn = scenarios.balance_scenario(balance_thresh=0.5, bg_mi=1.0)
    # make DC0 hold ONE worker: drop the second worker's cloudlet
    cls = scn.cloudlets.replace(
        exists=jnp.asarray(np.array([True, True, False])))
    res = jax.jit(simulate)(scn.replace(cloudlets=cls))
    # util(DC0)=1.0 > 0.5 with an empty feasible peer, yet no move happens
    assert int(res.n_migrations) == 0
    assert int(res.n_finished) == 2


def test_migration_requires_federation():
    """Live migration is a CloudCoordinator policy: with federation off the
    thresholds may scream but n_migrations stays 0."""
    scn = scenarios.consolidation_scenario()
    scn = scn.replace(policy=scn.policy.replace(
        federation=jnp.asarray(False)))
    res = jax.jit(simulate)(scn)
    assert int(res.n_migrations) == 0
    assert int(res.n_finished) == scn.cloudlets.n_cloudlets


def test_same_scenario_bit_identical():
    """Same key/threshold ⇒ bit-identical SimResult, field by field."""
    fn = jax.jit(simulate)
    a = fn(scenarios.consolidation_scenario())
    b = fn(scenarios.consolidation_scenario())
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.array(getattr(a, f.name)), np.array(getattr(b, f.name)),
            err_msg=f"SimResult.{f.name} not deterministic")


def test_vmapped_threshold_grid_matches_loop():
    """A vmapped consolidate-threshold grid row-matches a Python loop of
    single runs (mirrors test_workload.py's seed-campaign pattern): integer
    and boolean fields exactly, floats to tight tolerance — and the
    thresholds bite (0 disables, high values drain the spare DC)."""
    template = scenarios.consolidation_scenario()
    K = 6
    threshs = jnp.linspace(0.0, 0.9, K)
    pol = jax.vmap(
        lambda u: template.policy.replace(migrate_consolidate_thresh=u)
    )(threshs)
    batched = broadcast_campaign(template, K, policy=pol)
    res = run_campaign(batched)

    fn = jax.jit(simulate)
    singles = [
        fn(template.replace(policy=template.policy.replace(
            migrate_consolidate_thresh=threshs[i])))
        for i in range(K)
    ]
    for f in dataclasses.fields(res):
        if getattr(res, f.name) is None:   # an optional result group
            assert all(getattr(s, f.name) is None for s in singles), f.name
            continue
        got = np.array(getattr(res, f.name))
        want = np.stack([np.array(getattr(s, f.name)) for s in singles])
        if got.dtype.kind in "biu":
            np.testing.assert_array_equal(
                got, want, err_msg=f"SimResult.{f.name} grid != loop")
        else:
            np.testing.assert_allclose(
                got, want, rtol=1e-5, atol=1e-3,
                err_msg=f"SimResult.{f.name} grid != loop")
    n_mig = np.array(res.n_migrations)
    assert n_mig[0] == 0, "threshold 0 must disable consolidation"
    assert (n_mig[1:] == 4).all(), "positive thresholds drain the spare DC"
    assert (np.array(res.n_finished) == template.cloudlets.n_cloudlets).all()


def test_table1_live_migration_knob():
    """The knob on the existing federation builder attaches the instrument
    and leaves the published Table-1 numbers untouched when off."""
    base = jax.jit(simulate)(scenarios.table1_scenario(True))
    knob_off = scenarios.table1_scenario(True, live_migration=True)
    knob_off = _no_live(knob_off)
    res = jax.jit(simulate)(knob_off)
    # instrument attached but gated off: same federation outcome
    assert int(res.n_migrations) == int(base.n_migrations) == 10
    np.testing.assert_allclose(
        float(res.mean_turnaround), float(base.mean_turnaround), rtol=1e-6)
