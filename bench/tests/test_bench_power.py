"""The power-aware consolidation family (``bench/families/cloudsim_power.py``)
and the ``planetlab_power.sweep`` cell: a whole run through ``cell.run`` at
the family's small size is correct, and with a planted fault in the
program it is not; the seeded day has the published statistics; the cell's
two per-layer metrics read their scopes; and simlint R1 finds the
consolidation phase on a real ``conditional``."""
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell, spec, traffic
from bench.harness import trace as tr
from bench.harness.cell import Reading
from bench.harness.spec import metric_reader

pytestmark = pytest.mark.tier1

BENCH = spec.load()
CELL = "planetlab_power.sweep"


def _small():
    w = spec.cell(BENCH, CELL)
    cfg = spec.config(BENCH, w["config"])
    return spec.family(cfg).small(cfg, spec.traffic(w["traffic"]))


@contextlib.contextmanager
def _planted(fault: str):
    """A fault in the program: PABFD that ignores the power increase (the
    first allowed host), or hosts that stay on, drawing power, when they
    hold no VM."""
    from repro.core import consolidate

    saved = []

    def patch(name, fn):
        saved.append((name, getattr(consolidate, name)))
        setattr(consolidate, name, fn)

    if fault == "first_fit":
        def first_fit(hc, vc, B, C, plan, v, allowed):
            _, D, ram, bw, *_ = plan
            vm = consolidate._vm(vc, plan, v)
            dv, rv, bv, _ = vm
            ok = (allowed & (hc["cap"] - D >= dv) & (hc["percore"] >= dv)
                  & (ram >= rv) & (bw >= bv)
                  & ~consolidate._over(B, C, D + dv))
            h = jnp.argmax(ok).astype(jnp.int32)
            return h, ok[h], consolidate._power_scaled(
                hc["tenths"], hc["cap"], D + dv), vm

        patch("_best_host", first_fit)
    elif fault == "always_on":
        pieces = consolidate._pieces

        def always_on(scn, st, t0, t1, fold, acc):
            def on(acc, piece):
                return fold(acc, (piece[0], jnp.ones_like(piece[1]),
                                  *piece[2:]))
            return pieces(scn, st, t0, t1, on, acc)

        patch("_pieces", always_on)
    elif fault != "none":
        raise ValueError(fault)
    jax.clear_caches()
    try:
        yield
    finally:
        for name, fn in reversed(saved):
            setattr(consolidate, name, fn)
        jax.clear_caches()


@pytest.mark.parametrize("seed,fault", [
    (1, "none"), (2**31 + 7, "none"),
    (2**31 + 7, "first_fit"), (2**31 + 7, "always_on"),
])
def test_power_cell_runs_and_catches_faults(seed, fault):
    cfg, mix = _small()
    with _planted(fault):
        result, checks = cell.run(BENCH, CELL, seed, 1.0, False,
                                  time.perf_counter(), config=cfg, mix=mix)
    assert result["attempted"] > 0
    assert list(checks) == ["migrations_diff", "events_off",
                            "hist_total_diff", "hist_excess_rows",
                            "best_err", "best_policy_diff"]
    assert result["correct"] is (fault == "none"), checks


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 7])
def test_the_seeded_day_has_the_published_statistics(seed):
    w = spec.cell(BENCH, CELL)
    cfg = spec.config(BENCH, w["config"])
    fam = spec.family(cfg)
    day = fam.make_day(cfg, traffic.rng_for(seed))
    stats = cfg["deployment"]["day"]["stats"]
    assert day.shape == (1052, 288) and day.dtype == np.int32
    assert day.min() >= 0 and day.max() <= 100
    q1, med, q3 = np.percentile(day, [25, 50, 75])
    for got, want in ((day.mean(), stats["mean"]), (day.std(), stats["std"]),
                      (q1, stats["q1"]), (med, stats["median"]),
                      (q3, stats["q3"])):
        assert abs(got - want) <= 1.0, (got, want)


def test_every_seed_asks_for_the_same_twelve_policies():
    w = spec.cell(BENCH, CELL)
    cfg, mix = spec.config(BENCH, w["config"]), spec.traffic(w["traffic"])
    fam = spec.family(cfg)
    pols = [sorted(zip(*(fam.draw(cfg, mix, 12, traffic.rng_for(s))[k]
                         for k in ("detector", "param"))))
            for s in (1, 2**31 + 7)]
    assert pols[0] == pols[1] and len(set(pols[0])) == 12


def control_readings(cfg: dict, mix: dict, seed: int) -> dict:
    """The numbers the check compares with the reference run in bfloat16,
    one precision below the configuration's float32, in the program's
    place: each reducer folded from the control's rows."""
    import ml_dtypes

    from bench.reference import fold

    fam = spec.family(cfg)
    params = fam.draw(cfg, mix, int(mix["rows"]), traffic.rng_for(seed))
    ref = fam.reference(cfg, params)
    ctl = fam.reference(cfg, params, dtype=ml_dtypes.bfloat16)
    answer = {}
    for key, r in mix["reduce"].items():
        v = np.asarray(ctl[r["metric"]], np.float64)
        if r["kind"] == "sum":
            answer[key] = int(v.sum())
        elif r["kind"] == "histogram":
            idx = fold.bin_index(v, float(r["lo"]), float(r["hi"]),
                                 int(r["bins"]))
            answer[key] = {"counts": np.bincount(idx, minlength=int(r["bins"]))}
        else:
            i = fam.best_row(v, r.get("mode", "min"))
            answer[key] = {"value": v[i], "index": i}
    worst, _ = fam.compare([answer], ref, params, mix)
    return worst


def test_the_bfloat16_control_fails_the_check():
    cfg, mix = _small()
    worst = control_readings(cfg, mix, 2**31 + 7)
    limits = spec.family(cfg).LIMITS
    assert any(worst[k] > limits[k] for k in limits), worst


# --- the cell's per-layer metrics ----------------------------------------

def _reading(scopes: dict, n_scenarios=12):
    ops = [tr.Op(0.0, 9.0, "while.1", 1), tr.Op(1.0, 4.0, "conditional.2", 1),
           tr.Op(1.5, 3.5, "while.3", 1), tr.Op(2.0, 2.5, "fusion.4", 1),
           tr.Op(5.0, 6.0, "fusion.5", 1)]
    t = tr.Trace(ops={0: ops}, spans=[("run_campaign", 0.0, 10.0)],
                 scopes={1: {"while.1": "jit(f)/while", **scopes}})
    return Reading(trace=t, devices=[0], lo=0.0, hi=10.0,
                   n_scenarios=n_scenarios, iterations=2)


BODY = "jit(_run_chunk_fold)/while/body"


@pytest.mark.parametrize("place", ["consolidate_place",
                                   "vmap(consolidate_place)"])
def test_consolidation_metrics_read_their_scopes(place):
    r = _reading({
        "conditional.2": f"{BODY}/phase_consolidate/conditional",
        "while.3": f"{BODY}/phase_consolidate/{place}/while",
        "fusion.4": f"{BODY}/phase_consolidate/{place}/while/body/reduce",
        "fusion.5": f"{BODY}/phase_bound/reduce"})
    # 3.0 s of the phase, 2.0 s of it placing, over 12 scenarios
    assert metric_reader("phase_consolidate_us")(r) == pytest.approx(
        1e6 * 3.0 / 12)
    assert metric_reader("consolidate_place_us")(r) == pytest.approx(
        1e6 * 2.0 / 12)


def test_consolidation_metrics_read_nothing_without_their_scopes():
    # a program without the consolidation phase (the parent's, or another
    # cell's)
    r = _reading({"conditional.2": f"{BODY}/phase_provision/conditional",
                  "fusion.5": f"{BODY}/phase_bound/reduce"})
    assert metric_reader("phase_consolidate_us")(r) is None
    assert metric_reader("consolidate_place_us")(r) is None


# --- simlint R1 ------------------------------------------------------------

@pytest.mark.parametrize("entry", ["simulate", "batch"])
def test_simlint_r1_sees_the_consolidation_phase_as_a_conditional(entry):
    from repro.analysis import simlint
    from repro.core import step

    lint = simlint.LintContext(entries=(entry,))
    hlo = lint.hlo(entry)
    assert simlint.check_cond_not_select(
        hlo, (step.SCOPE_CONSOLIDATE,), entry) == []
    conds = [ln for ln in simlint._scoped_lines(hlo, step.SCOPE_CONSOLIDATE)
             if simlint._CONDITIONAL.search(ln)]
    assert conds
