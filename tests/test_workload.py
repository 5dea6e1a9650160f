"""Seeded workload generators: determinism, validity, vmap over seeds."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import scenarios, simulate, workload

pytestmark = pytest.mark.tier1

KINDS = ("poisson", "diurnal", "bursty")


def _gen(key, kind, n=48, **kw):
    return workload.generate_cloudlets(
        key, n, kind=kind, rate=0.1, n_bursts=4, **kw)


@pytest.mark.parametrize("kind", KINDS)
def test_same_key_bit_identical(kind):
    a = _gen(jax.random.PRNGKey(3), kind)
    b = _gen(jax.random.PRNGKey(3), kind)
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.array(getattr(a, f.name)), np.array(getattr(b, f.name)),
            err_msg=f"Cloudlets.{f.name} not deterministic under {kind}")


@pytest.mark.parametrize("kind", KINDS)
def test_different_keys_differ(kind):
    a = _gen(jax.random.PRNGKey(0), kind)
    b = _gen(jax.random.PRNGKey(1), kind)
    assert not np.allclose(np.array(a.submit_t), np.array(b.submit_t))
    assert not np.allclose(np.array(a.length_mi), np.array(b.length_mi))


@pytest.mark.parametrize("kind", KINDS)
def test_generated_rows_valid(kind):
    cls = _gen(jax.random.PRNGKey(5), kind, io_mb=0.5)
    sub = np.array(cls.submit_t)
    assert (np.diff(sub) >= 0).all(), "rows must be sorted by submit_t"
    assert (sub >= 0).all()
    assert np.isfinite(sub).all()
    assert (np.array(cls.length_mi) > 0).all()
    assert (np.array(cls.input_mb) > 0).all()
    assert (np.array(cls.output_mb) > 0).all()
    assert np.array(cls.exists).all()


def test_routing_modes():
    rr = _gen(jax.random.PRNGKey(2), "poisson", n_vms=4)
    assert set(np.array(rr.vm)) <= {0, 1, 2, 3}
    svc = _gen(jax.random.PRNGKey(2), "poisson", n_vms=None)
    assert (np.array(svc.vm) == -1).all()


def test_poisson_mean_rate():
    """Arrival rate is statistically honest: n arrivals span ~ n/rate."""
    cls = workload.generate_cloudlets(
        jax.random.PRNGKey(11), 512, kind="poisson", rate=0.5)
    span = float(np.array(cls.submit_t)[-1])
    assert 0.8 * 512 / 0.5 < span < 1.25 * 512 / 0.5


def test_diurnal_modulation():
    """Arrivals cluster at the sinusoid peak: peak-phase bins hold more than
    trough-phase bins."""
    period = 200.0
    cls = workload.generate_cloudlets(
        jax.random.PRNGKey(13), 2048, kind="diurnal", rate=1.0,
        amp=0.9, period=period)
    t = np.array(cls.submit_t)
    phase = (t % period) / period
    peak = ((phase > 0.05) & (phase < 0.45)).sum()     # sin > 0 region
    trough = ((phase > 0.55) & (phase < 0.95)).sum()   # sin < 0 region
    assert peak > 1.5 * trough


def test_bursty_gaps_dominate():
    """On/off structure: the n_bursts-1 gaps at burst boundaries are the
    off-gaps, far larger than the within-burst gaps.  One exponential off-gap
    of mean 500 falls below ten within-burst gaps often enough (about one
    key in five) that a single key cannot pin this, so the property is
    checked across 128 keys."""
    keys = jax.random.split(jax.random.PRNGKey(17), 128)
    submit = jax.vmap(lambda k: workload.generate_cloudlets(
        k, 64, kind="bursty", n_bursts=4, rate=1.0,
        off_gap_mean=500.0).submit_t)(keys)
    gaps = np.diff(np.array(submit), axis=1)
    boundary = np.zeros(gaps.shape[1], bool)
    boundary[15::16] = True                      # after each 16-job burst
    off, within = gaps[:, boundary], gaps[:, ~boundary]
    # the boundary gap is the off-gap plus one Exp(1) within-burst draw
    assert abs(off.mean() - 501.0) < 0.15 * 501.0
    assert abs(within.mean() - 1.0) < 0.1
    assert np.median(off) > 10 * np.quantile(within, 0.99)
    # per key, the largest gaps sit on the boundaries in most rows
    top = np.argsort(gaps, axis=1)[:, -3:]
    on_boundary = boundary[top].all(axis=1)
    assert on_boundary.mean() > 0.9


def test_vmap_over_32_seeds_valid_scenarios():
    """A seed campaign: 32 generated workloads in one vmap, all rows valid
    and pairwise distinct, and they simulate end to end."""
    keys = jax.random.split(jax.random.PRNGKey(21), 32)
    cls = jax.vmap(
        lambda k: workload.generate_cloudlets(
            k, 24, kind="bursty", n_bursts=3, rate=0.2, off_gap_mean=300.0,
            median_mi=20_000.0, n_vms=4)
    )(keys)
    sub = np.array(cls.submit_t)
    assert sub.shape == (32, 24)
    assert (np.diff(sub, axis=1) >= 0).all()
    assert np.isfinite(sub).all()
    assert len({tuple(row) for row in sub.round(4).tolist()}) == 32

    from repro.core import broadcast_campaign, run_campaign

    template = scenarios.generated_scenario(
        keys[0], kind="bursty", n_cloudlets=24, n_vms=4, n_hosts=4,
        rate=0.2, n_bursts=3, off_gap_mean=300.0, median_mi=20_000.0)
    batched = broadcast_campaign(template, 32, cloudlets=cls)
    res = run_campaign(batched)
    assert (np.array(res.n_finished) == 24).all()


def test_generated_scenario_simulates():
    for kind in KINDS:
        scn = scenarios.generated_scenario(
            jax.random.PRNGKey(8), kind=kind, n_cloudlets=16, n_vms=4,
            n_hosts=4, rate=0.2, n_bursts=4, median_mi=10_000.0)
        res = jax.jit(simulate)(scn)
        assert int(res.n_finished) == 16, kind
