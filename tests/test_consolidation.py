"""Power-aware consolidation (``core/consolidate.py``, DESIGN.md §15).

The program against the plain float64 reference (``bench/reference/power.py``)
on every row of a small deployment (40 hosts, 52 VMs, 48 ticks, 6 rows):
every migration ``(tick, vm, source, destination)``, the counts, energy,
SLATAH and PDM, through ``simulate``, ``vmap`` of it, the batch-major step
and ``run_campaign``.  The detectors, the power tables, MMT, PABFD and the
underload drain on hand-computed cases.  And a scenario without a
consolidation runs the very program it ran before the subsystem existed.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import spec, traffic
from bench.reference import power as ref_power
from repro.core import (
    campaign,
    consolidate as cons,
    scenarios,
    simulate,
    simulate_instrumented,
)
from repro.core import energy, engine, workload
from repro.core.entities import SPACE_SHARED, TIME_SHARED
from repro.core.pytree import pytree_dataclass
from repro.core.step import AutoscaleInstrument, Instrument, MigrationInstrument

pytestmark = pytest.mark.tier1

G4 = [86, 89.4, 92.6, 96, 99.5, 102, 106, 108, 112, 114, 117]
G5 = [93.7, 97, 101, 105, 110, 116, 121, 125, 129, 133, 135]


@pytree_dataclass
class MoveLog(Instrument):
    """Each tick's moves: destination and source host of every VM that
    started a migration at that tick (-1: none)."""

    name = "moves"

    def init(self, scn):
        K, V = scn.dynamic_consolidation.n_ticks, scn.vms.n_vms
        return (jnp.full((K, V), -1, jnp.int32),
                jnp.full((K, V), -1, jnp.int32))

    def post(self, scn, st, ev, aux):
        dst, src = aux
        ps = st.consol
        k = jnp.clip(ps.k - 1, 0, scn.dynamic_consolidation.n_ticks - 1)
        moving = ps.mig_dst >= 0
        host = st.vm_dc * scn.hosts.n_hosts + st.vm_host
        return st, (dst.at[k].set(jnp.where(moving, ps.mig_dst, dst[k])),
                    src.at[k].set(jnp.where(moving, host, src[k])))

    def finalize(self, scn, st, aux):
        return {"dst": aux[0], "src": aux[1]}


def _moves(dst, src) -> list:
    dst, src = np.asarray(dst), np.asarray(src)
    return [(int(k), int(v), int(src[k, v]), int(dst[k, v]))
            for k, v in zip(*np.nonzero(dst >= 0))]


@pytest.fixture(scope="module", params=(7, 2**31 + 11))
def small(request):
    bench = spec.load()
    cfg = spec.config(bench, "planetlab_power")
    fam = spec.family(cfg)
    cfg, mix = fam.small(cfg, spec.traffic("power_sweep_n12"))
    params = fam.draw(cfg, mix, mix["rows"], traffic.rng_for(request.param))
    ref = fam.reference(cfg, params)
    grid = fam.build_rows(cfg, params, mix)
    return fam, cfg, mix, params, ref, grid


# Relative gaps from float32: energy sums long intervals; SLATAH and PDM
# sum the few-second windows of migrations, whose float32 ends sit a few
# ulps of the day's clock (1e-3 s at 4 h) off the exact ones.
RTOL = {"energy_kwh": 1e-5, "slatah": 1e-3, "pdm": 1e-3}


def _check_row(res, moves, ref, i):
    assert moves == sorted(ref["moves"][i]), i
    assert int(res.n_migrations) == ref["n_migrations"][i] == len(moves)
    assert int(res.n_events) == ref["n_events"][i]
    assert int(res.power.n_overloaded) == ref["n_overloaded"][i]
    assert int(res.power.n_place_tries) == ref["n_place_tries"][i]
    for k, rtol in RTOL.items():
        np.testing.assert_allclose(float(getattr(res.power, k)), ref[k][i],
                                   rtol=rtol, atol=1e-12, err_msg=k)


def _row(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


@pytest.mark.parametrize("path", ["simulate", "vmap", "batch"])
def test_program_matches_reference_decision_for_decision(small, path):
    fam, cfg, mix, params, ref, grid = small
    assert sum(ref["n_migrations"]) > 0 and sum(ref["n_overloaded"]) > 0
    run = lambda s: simulate_instrumented(s, (MoveLog(),))  # noqa: E731
    if path == "simulate":
        one = jax.jit(run)
        outs = [one(fam.build_one(cfg, params, i, mix))
                for i in range(mix["rows"])]
    else:
        res, out = jax.jit(jax.vmap(run) if path == "vmap" else run)(grid)
        outs = [(_row(res, i), _row(out, i)) for i in range(mix["rows"])]
    for i, (res, out) in enumerate(outs):
        _check_row(res, _moves(out["moves"]["dst"], out["moves"]["src"]),
                   ref, i)


def test_run_campaign_rows_are_the_batch_rows(small):
    fam, cfg, mix, params, ref, grid = small
    whole = jax.jit(simulate)(grid)
    chunked = campaign.run_campaign(grid, chunk_size=4)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(chunked.n_migrations),
                                  ref["n_migrations"])
    np.testing.assert_allclose(np.asarray(chunked.power.energy_kwh),
                               ref["energy_kwh"], rtol=1e-5)


# --- hand-computed cases ------------------------------------------------

def _hosts(caps, percore):
    """A host-column dict as ``consolidate._hosts`` builds it."""
    n = len(caps)
    cls = [0 if c == 3720 else 1 for c in caps]
    cs = _consolidation(np.array(cls)[None], np.array(caps)[None])
    return {"N": n, "H": n, "cap": jnp.asarray(caps, jnp.int32),
            "percore": jnp.asarray(percore, jnp.int32),
            "ram": jnp.full((n,), 4096, jnp.int32),
            "bw": jnp.full((n,), 1000, jnp.int32),
            "ok": jnp.ones((n,), bool),
            "tenths": cons._rint(10.0 * cs.power.table).reshape(n, 11),
            "mult": cs.key_mult.reshape(n)}


def _consolidation(host_class, caps):
    return cons.Consolidation.build(jnp.zeros((1, 1), jnp.int32), host_class,
                                    [G4, G5], caps, _policy(cons.THR, 0.7),
                                    300.0)


def _vms(d, image=None, rank=None):
    V = len(d)
    return {"d": jnp.asarray(d, jnp.int32), "ram": jnp.zeros((V,), jnp.int32),
            "bw": jnp.zeros((V,), jnp.int32),
            "image": jnp.asarray(image if image else [870] * V, jnp.int32),
            "rank": jnp.asarray(rank if rank else range(V), jnp.int32)}


def _policy(det, s):
    return cons.ConsolidationPolicy(detector=jnp.int32(det),
                                    param=jnp.float32(s))


def _history(values):
    hist = np.zeros((1, cons.HISTORY), np.int32)
    hist[0, cons.HISTORY - len(values):] = values
    return jnp.asarray(hist), jnp.asarray([len(values)], jnp.int32)


TWELVE = list(range(100, 1300, 100))


@pytest.mark.parametrize("det,s,cap,hist,last_ok", [
    # THR(0.8): u > 0.8, i.e. D > 2976 on 3720 MIPS
    (cons.THR, 0.8, 3720, [], 2976),
    # IQR(1.5) of 100..1200: quartiles 325 and 975, IQR 650; D > 5320 - 975
    (cons.IQR, 1.5, 5320, TWELVE, 4345),
    # MAD(2.0): median 650, deviations' median 300; D > 5320 - 600
    (cons.MAD, 2.0, 5320, TWELVE, 4720),
    # 11 samples: the THR(0.7) fallback, D > 3724
    (cons.IQR, 1.5, 5320, TWELVE[:11], 3724),
    (cons.MAD, 2.0, 5320, TWELVE[:11], 3724),
])
def test_detector_thresholds(det, s, cap, hist, last_ok):
    h, n = _history(hist)
    B, C = cons._thresholds(_policy(det, s), jnp.asarray([cap], jnp.int32),
                            h, n)
    over = lambda D: bool(cons._over(B, C, jnp.int32(D))[0])  # noqa: E731
    assert not over(last_ok) and over(last_ok + 1)
    # the reference's own rule agrees on the same numbers
    ref = ref_power._threshold_fn(det, round(s * 100), np.array([cap], float),
                                  np.asarray(h, float), np.asarray(n))
    assert not ref(float(last_ok))[0] and ref(float(last_ok + 1))[0]


@pytest.mark.parametrize("cap,D,watts", [
    (3720, 0, 86.0), (3720, 372, 89.4),        # at the 0% and 10% points
    (3720, 558, 91.0),                          # half-way from 10% to 20%
    (3720, 3720, 117.0), (3720, 5000, 117.0),   # 100%, and above it
    (5320, 2660, 116.0),                        # the G5 table at 50%
    (5320, 4788, 133.0),                        # 90%
    (5320, 5054, 134.0),                        # half-way from 90% to 100%
])
def test_power_table_interpolates_between_the_10pct_points(cap, D, watts):
    hc = _hosts([cap], [cap // 2])
    got = cons._power_scaled(hc["tenths"], hc["cap"], jnp.asarray([D]))
    assert int(got[0]) == round(10 * cap * watts)
    # the energy accounting reads the same table through the one
    # interpolation that energy.power_draw uses
    table = _consolidation([[0 if cap == 3720 else 1]], [[cap]]).power.table
    w = energy.table_watts(table[0], jnp.asarray([D / cap], jnp.float32))
    np.testing.assert_allclose(float(w[0]), watts, rtol=1e-6)


def test_power_keys_share_one_scale():
    cs = _consolidation([[0, 1]], [[3720, 5320]])
    # lcm(3720, 5320) = 494,760 = 3720 * 133 = 5320 * 93
    assert cs.key_mult.tolist() == [[133, 93]]
    assert float(cs.power.table[0, 1, 10]) == 135.0


@pytest.mark.parametrize("gate", [False, True])
def test_power_draw_reads_the_table_form(gate):
    # the Figure 4 host (one 2-core host) under the G4 table, nothing
    # placed yet: the table's 0% point, or 0 W when idle hosts are gated
    scn = scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED)
    pm = energy.PowerModel.from_tables([[0]], [G4])
    if gate:
        pm = pm.replace(gate_idle=jnp.ones((1,), bool))
    scn = scn.replace(power=pm)
    watts = energy.power_draw(scn, engine.init_state(scn))
    assert watts.tolist() == [0.0 if gate else 86.0]


@pytest.mark.parametrize("clash", ["power", "outages", "autoscale",
                                   "migration"])
def test_dynamic_consolidation_rejects_what_it_cannot_share(clash):
    bench = spec.load()
    cfg = spec.config(bench, "planetlab_power")
    fam = spec.family(cfg)
    cfg, mix = fam.small(cfg, spec.traffic("power_sweep_n12"))
    params = fam.draw(cfg, mix, mix["rows"], traffic.rng_for(7))
    scn = fam.build_one(cfg, params, 0, mix)
    jax.eval_shape(simulate, scn)
    N = scn.hosts.n_hosts
    scn = scn.replace(**{
        "power": {"power": energy.PowerModel.uniform(1)},
        "outages": {"outages": workload.host_outages(
            jax.random.PRNGKey(0), 1, N, 2, 1e5, 1e3)},
        "autoscale": {"instruments": (AutoscaleInstrument(),)},
        "migration": {"instruments": (MigrationInstrument(),)},
    }[clash])
    with pytest.raises(ValueError, match="dynamic consolidation"):
        jax.eval_shape(simulate, scn)


def test_mmt_takes_least_ram_first_until_not_overloaded():
    hc = _hosts([3720], [1860])
    # four VMs on host 0: 3,400 MIPS > 0.7 * 3720; least RAM first (613,
    # 613 by index, then 870): taking 1 and 3 leaves 2,500 MIPS
    vc = _vms([1000, 500, 1500, 400], image=[1740, 613, 870, 613])
    plan = cons._plan(hc, vc, jnp.zeros((4,), jnp.int32))
    B, C = cons._thresholds(_policy(cons.THR, 0.7), hc["cap"],
                            *_history([]))
    over = cons._over(B, C, plan[1])
    assert bool(over[0])
    taken = cons._mmt(vc, B, C, plan, over)
    assert np.flatnonzero(np.asarray(taken)).tolist() == [1, 3]


@pytest.mark.parametrize("s,want", [(1.0, 0), (0.9, 1)])
def test_pabfd_least_power_increase_unless_overloaded_after(s, want):
    # host 0 (G5) at 4,800 MIPS, 90.2%: 200 more MIPS cost 20 tenths of a
    # watt per 532 MIPS there, 33 on the empty host 1; but under THR(0.9)
    # 5,000 MIPS would overload host 0
    hc = _hosts([5320, 5320], [2660, 2660])
    vc = _vms([4800, 200])
    plan = cons._plan(hc, vc, jnp.asarray([0, -1], jnp.int32))
    B, C = cons._thresholds(_policy(cons.THR, s), hc["cap"],
                            *(jnp.zeros((2, cons.HISTORY), jnp.int32),
                              jnp.zeros((2,), jnp.int32)))
    h, ok, *_ = cons._best_host(hc, vc, B, C, plan, 1, hc["ok"])
    assert bool(ok) and int(h) == want


@pytest.mark.parametrize("d,found", [(1860, True), (1900, False)])
def test_pabfd_needs_the_per_core_mips(d, found):
    hc = _hosts([3720], [1860])
    vc = _vms([d])
    plan = cons._plan(hc, vc, jnp.asarray([-1], jnp.int32))
    B, C = cons._thresholds(_policy(cons.THR, 1.0), hc["cap"], *_history([]))
    _, ok, *_ = cons._best_host(hc, vc, B, C, plan, 0, hc["ok"])
    assert bool(ok) is found


@pytest.mark.parametrize("y,kept", [(100, True), (200, False)])
def test_underload_drain_is_all_or_nothing(y, kept):
    # THR(0.8) on G5 hosts: at most 4,256 MIPS.  Host 0 (x = 1,500 and y)
    # is the least utilised; x fits on host 1 (2,600 + 1,500), and y then
    # fits there too only when 4,100 + y <= 4,256.  Otherwise nothing of
    # host 0's drain is kept, and no later drain fits.
    hc = _hosts([5320] * 3, [2660] * 3)
    vc = _vms([1500, y, 2600, 4100], rank=[2, 3, 1, 0])
    host = jnp.asarray([0, 0, 1, 2], jnp.int32)
    plan = cons._plan(hc, vc, host)
    B, C = cons._thresholds(_policy(cons.THR, 0.8), hc["cap"],
                            jnp.zeros((3, cons.HISTORY), jnp.int32),
                            jnp.zeros((3,), jnp.int32))
    over = cons._over(B, C, plan[1])
    assert not bool(over.any())
    plan, tries = cons._drain_underloaded(hc, vc, B, C, plan, over,
                                          jnp.int32(0))
    want = [1, 1, 1, 2] if kept else [0, 0, 1, 2]
    assert np.asarray(plan[0]).tolist() == want


# --- programs without a consolidation -----------------------------------

# SimResult leaves and lowered program text of the four Figure-4 policy
# pairs, one at a time and stacked.  The results were recorded from the
# program as it stood before the consolidation subsystem existed; the text
# since the step reads its small tables by one-hot selects
# (``segments.lookup``), which left every result's bits as they were
FIG4_RESULTS = [
    "161d6a9af56f80970805f441e419485c7cdeab042c4af94ff811f93ffc76287c",
    "09209ff5ec50a74d5909a67c24324ff552c17463637fd071a8cda0b95fad9783",
    "e37325c4c3297e49e154e33c93aed2a83ab737563b574195ba110a6748eb1273",
    "0f8e9f95e890304ef2ebb240bdaed4cfaf7a6a7eabedb4aa21cd93e432cd1b79",
]
FIG4_BATCH = "f4ebd8dad2c430ba4f98b588ee5262b9abe90f61b6b8802696287ebfeaf0e8d7"
FIG4_LOWERED = (
    "10daa5b15baa51f418711fb9b10b33e1110f56bf54da92cfd12667f143c6dda5",
    "7ceee2a39d9523010e56801c38860117890aa87ea34ae454a24ca88b43771dd3",
)


def _digest(tree) -> tuple[int, str]:
    h = hashlib.sha256()
    leaves = jax.tree.leaves(tree)
    for leaf in leaves:
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return len(leaves), h.hexdigest()


def test_no_consolidation_runs_the_program_it_ran_before():
    rows = [scenarios.fig4_scenario(hp, vp)
            for hp in (SPACE_SHARED, TIME_SHARED)
            for vp in (SPACE_SHARED, TIME_SHARED)]
    grid = campaign.stack_scenarios(rows)
    for scn, want in zip(rows, FIG4_RESULTS):
        res = jax.jit(simulate)(scn)
        assert res.power is None
        assert _digest(res) == (26, want)
    assert _digest(jax.jit(simulate)(grid)) == (26, FIG4_BATCH)
    for arg, want in zip((rows[0], grid), FIG4_LOWERED):
        text = jax.jit(simulate).lower(arg).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == want
