"""The event_step kernel + Instrument layer + simulate_history driver."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SPACE_SHARED,
    TIME_SHARED,
    Instrument,
    UtilizationTimelineInstrument,
    scenarios,
    simulate,
    simulate_history,
    simulate_instrumented,
    step,
)
from repro.core.energy import PowerModel
from repro.core.pytree import pytree_dataclass

pytestmark = pytest.mark.tier1


def _results_identical(res_a, res_b):
    for f in dataclasses.fields(res_a):
        np.testing.assert_array_equal(
            np.array(getattr(res_a, f.name)), np.array(getattr(res_b, f.name)),
            err_msg=f"SimResult.{f.name} diverged")


# ---------------------------------------------------------------------------
# simulate_history: the fixed-length scan driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hp,vp", [(SPACE_SHARED, SPACE_SHARED),
                                   (TIME_SHARED, TIME_SHARED)])
def test_history_result_matches_simulate(hp, vp):
    scn = scenarios.fig4_scenario(hp, vp)
    res = jax.jit(simulate)(scn)
    res_h, hist = jax.jit(simulate_history)(scn)
    _results_identical(res, res_h)
    valid = np.array(hist.valid)
    assert valid.sum() == int(res.n_events)
    # padding rows are inert
    assert (np.array(hist.kind)[~valid] == -1).all()
    assert (np.array(hist.t)[~valid] == 0.0).all()


def test_history_log_contents_fig4():
    """Space/space fig4: 4 completion events at 400/800/1200/1600; the one
    2-core host is fully utilized until the last completion."""
    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)
    _, hist = jax.jit(simulate_history)(scn)
    v = np.array(hist.valid)
    np.testing.assert_allclose(np.array(hist.t)[v],
                               [400.0, 800.0, 1200.0, 1600.0], rtol=1e-5)
    assert (np.array(hist.kind)[v] == step.K_COMPLETION).all()
    np.testing.assert_allclose(np.array(hist.utilization)[v][:, 0], 1.0,
                               atol=1e-6)
    # accrued CPU cost is monotone along the event log
    cpu = np.array(hist.cpu_cost)[v].sum(axis=1)
    assert (np.diff(cpu) > 0).all()
    # finished counter counts up to 8
    assert np.array(hist.n_finished)[v].tolist() == [2, 4, 6, 8]


def test_history_event_kinds_federation():
    """Federated table1 run must log sensor ticks and migration completions."""
    scn = scenarios.table1_scenario(True)
    _, hist = jax.jit(simulate_history)(scn)
    v = np.array(hist.valid)
    kinds = np.array(hist.kind)[v]
    assert (kinds == step.K_TICK).any()
    assert (kinds == step.K_COMPLETION).any()
    assert (kinds == step.K_MIGRATION).any() or (kinds == step.K_READY).any()


def test_history_energy_snapshots():
    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED).replace(
        power=PowerModel.uniform(1))
    res_h, hist = jax.jit(simulate_history)(scn)
    v = np.array(hist.valid)
    e = np.array(hist.energy_j)[v].sum(axis=1)
    assert (np.diff(e) > 0).all()
    np.testing.assert_allclose(e[-1], float(np.sum(np.array(res_h.energy_j))),
                               rtol=1e-6)


def test_history_vmappable():
    """A campaign of histories: fixed shapes make the event log vmappable."""
    from repro.core import stack_scenarios

    scns = [scenarios.fig4_scenario(hp, vp) for hp in (0, 1) for vp in (0, 1)]
    batched = stack_scenarios(scns)
    res, hist = jax.jit(jax.vmap(simulate_history))(batched)
    assert np.array(hist.valid).shape[0] == 4
    for i in range(4):
        np.testing.assert_array_equal(
            np.array(hist.valid[i]).sum(), int(np.array(res.n_events[i])))


# ---------------------------------------------------------------------------
# Instruments: composability
# ---------------------------------------------------------------------------

def test_utilization_timeline_instrument():
    """The Figure 9/10-style per-DC utilization observable: one class, no
    engine fork."""
    ts = jnp.asarray(np.arange(0.0, 2000.0, 100.0, dtype=np.float32))
    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED).replace(
        instruments=(UtilizationTimelineInstrument(sample_ts=ts),))
    res, out = simulate_instrumented(scn)
    util = np.array(out["utilization"]["utilization"])
    assert util.shape == (len(ts), 1)
    # busy until 1600 (fig4a), idle after
    assert np.allclose(util[np.array(ts) < 1600.0, 0], 1.0, atol=1e-6)
    assert np.allclose(util[np.array(ts) > 1600.0, 0], 0.0, atol=1e-6)
    # attaching an observer does not perturb the simulation
    _results_identical(res, jax.jit(simulate)(scn.replace(instruments=())))


def test_custom_instrument_one_small_class():
    """A new observable is one small class: count events by kind."""

    @pytree_dataclass
    class EventKindCounter(Instrument):
        name = "kind_counter"

        def init(self, scn):
            return jnp.zeros((7,), jnp.int32)

        def post(self, scn, st, ev, aux):
            return st, aux.at[ev.kind].add(1)

        def finalize(self, scn, st, aux):
            return {"counts": aux}

    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED).replace(
        instruments=(EventKindCounter(),))
    res, out = jax.jit(simulate_instrumented)(scn)
    counts = np.array(out["kind_counter"]["counts"])
    assert counts.sum() == int(res.n_events)
    assert counts[step.K_COMPLETION] == 4


def test_instrument_bound_is_a_clock_stop():
    """An instrument bound() must split intervals without changing results."""

    @pytree_dataclass
    class ClockStop(Instrument):
        name = "clock_stop"
        stop_every: jax.Array

        def bound(self, scn, st, aux):
            # next multiple of stop_every strictly after t
            k = jnp.floor(st.t / self.stop_every) + 1
            return k * self.stop_every

        def extra_steps(self, scn):
            # bound() adds clock stops: grow the driver's step budget so the
            # loop cannot silently truncate (step.resolve_max_steps)
            return 64

    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)
    res = jax.jit(simulate)(scn)
    scn_s = scn.replace(instruments=(
        ClockStop(stop_every=jnp.asarray(150.0, jnp.float32)),))
    res_s = jax.jit(simulate)(scn_s)
    # more events (the stops), same physics and same total accrual
    assert int(res_s.n_events) > int(res.n_events)
    np.testing.assert_allclose(np.array(res.finish_t), np.array(res_s.finish_t),
                               rtol=1e-5)
    np.testing.assert_allclose(float(res.total_cost), float(res_s.total_cost),
                               rtol=1e-5)


def test_duplicate_instrument_names_rejected():
    """Outputs are keyed by name: a silent collision would drop results."""
    ts = jnp.arange(4.0)
    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED).replace(
        instruments=(UtilizationTimelineInstrument(sample_ts=ts),
                     UtilizationTimelineInstrument(sample_ts=ts * 2)))
    with pytest.raises(ValueError, match="duplicate instrument name"):
        simulate_instrumented(scn)


def test_bound_instrument_extra_steps_prevents_truncation():
    """A tight-period clock-stop instrument must not exhaust max_steps."""

    @pytree_dataclass
    class TightStop(Instrument):
        name = "tight_stop"
        stop_every: jax.Array

        def bound(self, scn, st, aux):
            return (jnp.floor(st.t / self.stop_every) + 1) * self.stop_every

        def extra_steps(self, scn):
            return 2000

    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)
    scn_s = scn.replace(instruments=(
        TightStop(stop_every=jnp.asarray(1.0, jnp.float32)),))
    res = jax.jit(simulate)(scn_s)
    # ~1600 stop events + 4 completions: all work still finishes
    assert int(res.n_finished) == 8
    np.testing.assert_allclose(
        np.array(res.finish_t),
        np.array(jax.jit(simulate)(scn).finish_t), rtol=1e-4)


def test_event_step_is_the_only_loop_body():
    """Guard the one composition: the phases are wired up in exactly one
    function of step.py, the conditioned-phase table is where simlint R1's
    scopes come from, and engine.py writes each loop form once and calls
    no phase of its own.
    """
    import ast
    import inspect

    from repro.core import engine

    tree = ast.parse(inspect.getsource(step))
    callers: dict = {}
    for fn in tree.body:
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                callers.setdefault(node.func.id, set()).add(fn.name)
    for phase in ("_phase_prologue", "_phase_bound", "_phase_commit"):
        assert callers.get(phase) == {"_compose"}, (phase, callers.get(phase))
    assert callers["_compose"] == {"event_step", "batch_event_step"}

    assert step.PHASE_SCOPES == tuple(
        row[0] for row in step._CONDITIONED) + (step.SCOPE_CONSOLIDATE,)

    src = inspect.getsource(engine)
    assert src.count("while_loop(") == 1
    assert src.count("scan(") == 1
    assert "_phase_" not in src
    assert "cloudlet_rates" not in src
    assert "advance(" not in src
