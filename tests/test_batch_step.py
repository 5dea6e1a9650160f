"""Batch-major step loop (DESIGN.md §10): a stacked ``[B, ...]`` campaign
through ``simulate`` must be a *perf* path, never a semantic fork.

Four families:

* **bitwise identity** — every row of the batch-major result equals a
  Python loop of per-scenario ``simulate``, bit for bit, across scenario
  constructors (policies, federation, outages, autoscaling pools).
* **early-exit masking** — rows with wildly different event counts
  (federated table1 vs non-federated: ~100 vs ~4 events) stay frozen at
  their own final state while the longest row keeps stepping.
* **conservation through the batch path** — the invariant suite's
  rate·dt-integral instrument, re-run per-row inside the batch loop,
  still balances depleted work on a mixed done/live batch.
* **driver equivalence** — ``simulate_trace`` / ``simulate_history``
  through the batch path reproduce their per-row outputs.

Plus the kernel-level contract the engine relies on: rank-2 (batch-major)
``advance_sweep`` inputs match a vmap of the rank-1 kernel on both
routings, and the ``advance_block`` tile heuristic respects its
floor/cap bounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_invariants as ti
from repro.core import (
    SPACE_SHARED,
    TIME_SHARED,
    scenarios,
    simulate_history,
    simulate_instrumented,
    simulate_trace,
    stack_scenarios,
)
from repro.core.engine import is_batched, scenario_row
from repro.kernels import ops, ref

pytestmark = pytest.mark.tier1


@jax.jit
def _run(scn):
    # one private jit target for single AND stacked scenarios: the driver
    # picks the batch-major loop by rank (engine.is_batched), so each shape
    # is its own cache entry but the traced source is identical
    return simulate_instrumented(scn)[0]


def _row(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _assert_trees_bitwise(name, got, want):
    mism = [
        jax.tree_util.keystr(path)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree.leaves(want),
        )
        if not bool(jnp.array_equal(a, b))
    ]
    assert not mism, f"{name}: batch != single at {mism}"


def _assert_rows_bitwise(name, batched_out, single_outs):
    for i, single in enumerate(single_outs):
        _assert_trees_bitwise(f"{name} row {i}", _row(batched_out, i), single)


def _scenario_batches():
    """Stackable row groups, one per scenario-constructor family, with
    rows varied along a traced axis (policy flags, workload, RNG key)."""
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    return [
        ("fig4_policies", [
            scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED),
            scenarios.fig4_scenario(TIME_SHARED, TIME_SHARED),
            scenarios.fig4_scenario(SPACE_SHARED, TIME_SHARED),
        ]),
        ("fig9_10_lengths", [
            scenarios.fig9_10_scenario(
                TIME_SHARED, n_hosts=40, n_vms=4, n_groups=2,
                task_mi=mi)
            for mi in (600_000.0, 1_200_000.0)
        ]),
        ("table1_mixed", [
            scenarios.table1_scenario(True),
            scenarios.table1_scenario(False),
        ]),
        ("generated_keys", [
            scenarios.generated_scenario(
                k, kind="poisson", n_cloudlets=16, n_vms=4, n_hosts=4,
                rate=0.2, median_mi=10_000.0)
            for k in (k1, k2)
        ]),
        ("autoscale", [
            scenarios.autoscale_scenario(k1, scale_down_thresh=0.05),
            scenarios.autoscale_scenario(k2, scale_down_thresh=0.05),
        ]),
        ("reliability", [
            scenarios.reliability_scenario(k1, evacuation=True,
                                           ckpt_interval=25_000.0),
            scenarios.reliability_scenario(k2, evacuation=True,
                                           ckpt_interval=25_000.0),
        ]),
        ("evacuation", [
            scenarios.evacuation_scenario(),
            scenarios.evacuation_scenario(evacuation=False,
                                          ckpt_interval=3.0e38),
        ]),
    ]


_BATCH_IDS = [name for name, _ in _scenario_batches()]


@pytest.mark.parametrize("name,rows", _scenario_batches(), ids=_BATCH_IDS)
def test_batch_rows_bitwise_identical(name, rows):
    batched = stack_scenarios(rows)
    assert is_batched(batched) and not is_batched(rows[0])
    res_b = _run(batched)
    singles = [_run(r) for r in rows]
    _assert_rows_bitwise(name, res_b, singles)


def test_early_exit_freezes_finished_rows():
    """Rows finishing at different event counts: once a row's step_cond
    drops, the live mask must freeze it bitwise while others continue."""
    rows = [scenarios.table1_scenario(True), scenarios.table1_scenario(False)]
    res_b = _run(stack_scenarios(rows))
    n_ev = np.array(res_b.n_events)
    # premise: the batch genuinely mixes a long row with a short one
    assert n_ev[0] >= n_ev[1] + 10, f"rows not heterogeneous: {n_ev}"
    singles = [_run(r) for r in rows]
    _assert_rows_bitwise("table1_mixed", res_b, singles)


def test_batch_conservation_mixed():
    """Work conservation on a mixed done/live batch: each row's rate·dt
    integral (accumulated inside the batch loop, so frozen rows must stop
    accruing) balances its depleted work."""
    rows = [scenarios.table1_scenario(True), scenarios.table1_scenario(False)]
    batched = stack_scenarios(rows)
    res, out = simulate_instrumented(batched, (ti._ConservationInstrument(),))
    executed = np.array(out["conservation"]["executed_mi"])
    rem = np.array(out["conservation"]["rem_mi"])
    rollback = np.array(out["conservation"]["rollback_mi"])
    assert (rollback == 0).all()  # no outage schedule in table1
    for i, scn in enumerate(rows):
        length = np.array(scn.cloudlets.length_mi)
        exists = np.array(scn.cloudlets.exists)
        np.testing.assert_allclose(
            executed[i][exists], (length - rem[i])[exists],
            rtol=1e-4, atol=1.0,
            err_msg=f"row {i}: rate·dt integral != depleted work")


def test_trace_equivalence_through_batch_path():
    ts = jnp.asarray([0.0, 900.0, 1800.0, 3600.0], jnp.float32)
    rows = [
        scenarios.fig9_10_scenario(TIME_SHARED, n_hosts=40, n_vms=4,
                                   n_groups=2, task_mi=mi)
        for mi in (600_000.0, 1_200_000.0)
    ]
    res_b, prog_b = simulate_trace(stack_scenarios(rows), ts)
    assert prog_b.shape == (len(rows), ts.shape[0], rows[0].cloudlets.n_cloudlets)
    for i, scn in enumerate(rows):
        res_i, prog_i = simulate_trace(scn, ts)
        _assert_trees_bitwise(f"trace row {i}", _row(res_b, i), res_i)
        assert bool(jnp.array_equal(prog_b[i], prog_i))


def test_history_through_batch_path():
    rows = [
        scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED),
        scenarios.fig4_scenario(TIME_SHARED, TIME_SHARED),
    ]
    res_b, hist_b = simulate_history(stack_scenarios(rows))
    for i, scn in enumerate(rows):
        res_i, hist_i = simulate_history(scn)
        _assert_trees_bitwise(f"history result row {i}", _row(res_b, i), res_i)
        # History stacks along axis 1: leaves are [T, B, ...] (the event
        # axis stays leading so per-event slicing is uniform)
        got = jax.tree.map(lambda x: x[:, i], hist_b)
        _assert_trees_bitwise(f"history log row {i}", got, hist_i)


def test_scenario_row_roundtrip():
    rows = [scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)] * 2
    batched = stack_scenarios(rows)
    row0 = scenario_row(batched)
    assert not is_batched(row0)
    assert jax.tree.structure(row0) == jax.tree.structure(rows[0])
    for a, b in zip(jax.tree.leaves(row0), jax.tree.leaves(rows[0])):
        assert bool(jnp.array_equal(a, b))


# ---------------------------------------------------------------------------
# kernel-level batch contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("b,c", [(4, 30), (8, 257)])
def test_advance_rank2_matches_vmap_of_rank1(impl, b, c):
    rng = np.random.default_rng(7)
    rem = jnp.asarray(rng.uniform(0.0, 1e5, (b, c)).astype(np.float32))
    rate = jnp.asarray(rng.uniform(0.0, 1e3, (b, c)).astype(np.float32))
    active = rate > 100.0
    bound = jnp.asarray(rng.uniform(1.0, 1e3, (b,)).astype(np.float32))

    advance = ops.resolve_advance(impl)
    dt2, rem2 = advance(rem, rate, active, bound)
    dt1, rem1 = jax.vmap(ref.advance_sweep_ref)(rem, rate, active, bound)
    assert dt2.shape == (b,) and rem2.shape == (b, c)
    if impl == "jnp":
        assert bool(jnp.array_equal(dt2, dt1))
        assert bool(jnp.array_equal(rem2, rem1))
    else:
        np.testing.assert_allclose(np.array(dt2), np.array(dt1),
                                   rtol=1e-6, atol=1e-4)
        np.testing.assert_allclose(np.array(rem2), np.array(rem1),
                                   rtol=1e-6, atol=1e-2)


def test_advance_block_heuristic():
    assert ops.advance_block(1) == 128          # floor: one lane-width tile
    assert ops.advance_block(128) == 128
    assert ops.advance_block(129) == 256        # next pow2 covering the row
    assert ops.advance_block(20_000) == 1 << 15
    assert ops.advance_block(100_000) == ops._MAX_BLOCK
    assert ops.advance_block(1 << 20) == ops._MAX_BLOCK  # cap
