"""The plain reference against the paper's analytic anchors, and against the
engine's two front doors (``simulate``, ``run_campaign``) at small sizes on
the CPU."""
import json

import jax
import numpy as np
import pytest

from bench.harness import spec, traffic
from bench.harness.spec import BENCH
from bench.reference import fold, sim
from repro.core import simulate

pytestmark = pytest.mark.tier1


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def _mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


TASKS = spec.family(_config("fig4"))


def _small_fig9_10():
    return TASKS.small(_config("fig9_10"), {"front_door": "simulate"})[0]


def _params(hp, vp, scale):
    return {"host_policy": np.asarray(hp, np.int32),
            "vm_policy": np.asarray(vp, np.int32),
            "length_scale": np.asarray(scale, np.float32)}


@pytest.mark.parametrize("hp,vp,times,events", [
    (0, 0, [400, 800, 1200, 1600], 4),
    (0, 1, [800, 1600], 2),
    (1, 0, [800, 1600], 2),
    (1, 1, [1600], 1),
])
def test_fig4_analytic_finish_times(hp, vp, times, events):
    """Figure 4 (a)-(d): L = 4000 MI / 10 MIPS = 400 s per task."""
    ref = TASKS.reference(_config("fig4"), _params([hp], [vp], [1.0]))
    np.testing.assert_allclose(np.unique(ref["finish_t"][0]), times, rtol=1e-12)
    assert ref["n_finished"][0] == 8
    assert ref["n_events"][0] == events and ref["event_slack"][0] == 0


def test_fig9_10_space_shared_tasks_take_1200_s():
    """Section 5: dedicated cores, so every 1.2e6 MI task runs exactly 1200 s,
    and the 10 groups queue on each VM: the last finishes at 12,000.003 s."""
    ref = TASKS.reference(_config("fig9_10"), _params([0], [0], [1.0]))
    run = ref["finish_t"][0] - ref["start_t"][0]
    np.testing.assert_allclose(run, 1200.0, rtol=1e-12)
    assert ref["n_finished"][0] == 500
    np.testing.assert_allclose(ref["makespan"][0], 12000.003, rtol=1e-12)


def test_fig9_10_time_shared_slows_every_task():
    ref = TASKS.reference(_config("fig9_10"), _params([0], [1], [1.0]))
    assert ref["n_finished"][0] == 500
    assert (ref["finish_t"][0] - ref["start_t"][0] >= 1200.0 - 1e-9).all()
    assert ref["mean_turnaround"][0] > 1200.0


def test_simulate_matches_reference_on_fig9_10():
    """Both VM policies and drawn task lengths, through ``simulate``."""
    cfg = _small_fig9_10()
    p = _params([0, 0, 0, 0], [0, 1, 0, 1], [1.0, 1.0, 1.17, 1.43])
    ref = TASKS.reference(cfg, p)
    outs = []
    for i in range(4):
        scn = TASKS.build_one(cfg, p, i, {"sweep_impl": "jnp"})
        outs.append((i, jax.jit(simulate)(scn)))
    worst, failed = TASKS.compare_simulate(
        TASKS.answers(outs, {"front_door": "simulate"}), ref)
    assert failed == 0, worst
    assert worst["time_err"] < 1e-6


@pytest.mark.parametrize("mesh_devices", [0, 1])
def test_run_campaign_fold_matches_reference_on_fig4(mesh_devices):
    """Folded ``run_campaign`` answers over a drawn grid, three chunks, on
    one device and through the ``data`` mesh path."""
    cfg = _config("fig4")
    mix = _mix("sweep_n16384")
    mix.update(rows=96, chunk_size=32, mesh_devices=mesh_devices)
    door = traffic.CampaignDoor(TASKS, cfg, mix, seed=2**31 + 11)
    _, out = door.call()
    ref = TASKS.reference(cfg, door.params)
    worst, failed = TASKS.compare_campaign(
        TASKS.answers([(0, out)], mix), ref, door.params, mix)
    assert failed == 0, worst
    assert door.iterations([(0, out)]) == 3 * 4      # 3 chunks, 4 events max


def test_draw_gives_every_seed_the_same_work():
    cfg, mix = _config("fig4"), _mix("sweep_n16384")
    a = TASKS.draw(cfg, mix, 1000, traffic.rng_for(5))
    b = TASKS.draw(cfg, mix, 1000, traffic.rng_for(2**31 + 99))
    pairs = lambda d: sorted(zip(d["host_policy"].tolist(), d["vm_policy"].tolist()))
    assert pairs(a) == pairs(b)
    assert not np.array_equal(a["host_policy"], b["host_policy"])
    c = TASKS.draw(cfg, mix, 1000, traffic.rng_for(5))
    assert all(np.array_equal(a[k], c[k]) for k in a)


def test_histogram_range_allows_only_rows_near_an_edge():
    v = np.array([10.0, 124.99, 125.01, 300.0])
    certain, possible = fold.histogram_range(v, 0.0, 1000.0, 8, 1e-3)
    assert certain.tolist() == [1, 0, 1, 0, 0, 0, 0, 0]
    assert possible.tolist() == [3, 2, 1, 0, 0, 0, 0, 0]
    exact = np.bincount(fold.bin_index(v, 0.0, 1000.0, 8), minlength=8)
    assert fold.histogram_excess(exact, v, 0.0, 1000.0, 8, 1e-3) == 0
    moved = exact.copy()
    moved[2] -= 1
    moved[3] += 1
    assert fold.histogram_excess(moved, v, 0.0, 1000.0, 8, 1e-3) == 2


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    for mod in (sim, fold):
        tree = ast.parse(inspect.getsource(mod))
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert all(not (n or "").startswith(("repro", "bench.harness", "jax"))
                   for n in names), names


def test_control_in_bfloat16_fails_the_time_limit():
    """The reference computed one precision below the stated float32,
    in the program's place, reads far outside the limit."""
    import ml_dtypes

    cfg = _small_fig9_10()
    p = _params([0] * 4, [0, 1, 0, 1], [1.0, 1.0, 1.17, 1.43])
    ref = TASKS.reference(cfg, p)
    ctl = TASKS.reference(cfg, p, dtype=ml_dtypes.bfloat16)
    answers = [(i, {k: ctl[k][i] for k in ("start_t", "finish_t",
                                            "n_finished", "n_events")})
               for i in range(4)]
    worst, failed = TASKS.compare_simulate(answers, ref)
    assert failed > 0
    assert worst["time_err"] > 10 * TASKS.LIMITS["time_err"]


def test_control_in_bfloat16_fails_the_sweep_check():
    """The control's folded answers over a drawn grid, at a small size."""
    import control

    bench = spec.load()
    w = spec.cell(bench, "fig4.sweep")
    mix = spec.traffic(w["traffic"])
    mix.update(rows=2048)
    worst = control.readings(spec.config(bench, w["config"]), mix, 2**31 + 5)
    assert worst["events_off"] > 0 and worst["hist_excess_rows"] > 0
