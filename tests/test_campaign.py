"""Campaign API: trace variant, chunked execution, stacking validation,
sharded execution (4-device subprocess)."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SPACE_SHARED,
    run_campaign,
    scenarios,
    simulate_trace,
    stack_scenarios,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Donation must actually apply: a donated-but-unusable buffer means the
# advertised per-chunk reuse silently regressed to a no-op (see the
# _donate_mask machinery in core/campaign.py).
pytestmark = [
    pytest.mark.tier1,
    pytest.mark.filterwarnings("error:Some donated buffers were not usable"),
]


def test_simulate_trace_progress_curves():
    """Fig 9/10-style progress sampling: fractions are monotone in time and
    reach 1.0 for finished work."""
    scn = scenarios.fig9_10_scenario(SPACE_SHARED, n_hosts=50, n_vms=5,
                                     n_groups=3)
    ts = jnp.asarray(np.arange(0.0, 4000.0, 250.0, dtype=np.float32))
    res, prog = simulate_trace(scn, ts)
    prog = np.array(prog)
    assert prog.shape == (len(ts), scn.cloudlets.n_cloudlets)
    assert (np.diff(prog, axis=0) >= -1e-5).all()          # monotone
    assert np.allclose(prog[-1][np.array(res.finish_t) <= 3750.0], 1.0,
                       atol=1e-3)
    # first group (submit 0): progress at sample t is t/1200 (dedicated cores)
    first = np.array(scn.cloudlets.submit_t) == 0.0
    t_idx = int(np.searchsorted(np.array(ts), 750.0))
    assert np.allclose(prog[t_idx][first], 750.0 / 1200.0, atol=0.02)


def test_chunked_campaign_matches_unchunked():
    """Chunking (with per-chunk buffer donation + trailing-chunk padding)
    must be invisible in the results — including a non-dividing chunk size."""
    base = [scenarios.fig4_scenario(hp, vp) for hp in (0, 1) for vp in (0, 1)]
    batched = stack_scenarios(base * 5)          # 20 scenarios
    whole = run_campaign(batched)
    for chunk in (4, 7, 32):                      # divides / ragged / > n
        chunked = run_campaign(batched, chunk_size=chunk)
        np.testing.assert_array_equal(
            np.array(whole.finish_t), np.array(chunked.finish_t))
        np.testing.assert_array_equal(
            np.array(whole.total_cost), np.array(chunked.total_cost))


def test_chunked_campaign_1024_scenarios():
    """Acceptance: a >=1024-scenario fig4 campaign runs chunked end to end."""
    base = [scenarios.fig4_scenario(hp, vp) for hp in (0, 1) for vp in (0, 1)]
    batched = stack_scenarios(base * 256)         # 1024 scenarios
    res = run_campaign(batched, chunk_size=128)
    fin = np.array(res.n_finished)
    assert fin.shape == (1024,)
    assert (fin == 8).all()


def test_run_campaign_rejects_bad_chunk_size():
    batched = stack_scenarios([scenarios.fig4_scenario(0, 0)] * 2)
    with pytest.raises(ValueError, match="chunk_size"):
        run_campaign(batched, chunk_size=0)


def test_stack_scenarios_validates_static_fields():
    a = scenarios.fig4_scenario(0, 0)
    with pytest.raises(ValueError, match="max_steps"):
        stack_scenarios([a, a.replace(max_steps=512)])
    with pytest.raises(ValueError, match="sweep_impl"):
        stack_scenarios([a, a.replace(sweep_impl="pallas")])
    with pytest.raises(ValueError, match="empty"):
        stack_scenarios([])


def test_stack_scenarios_validates_structure():
    from repro.core.energy import PowerModel

    a = scenarios.fig4_scenario(0, 0)
    b = a.replace(power=PowerModel.uniform(1))
    with pytest.raises(ValueError, match="structure"):
        stack_scenarios([a, b])


def test_run_campaign_sharded_subprocess():
    code = """
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import scenarios, stack_scenarios, run_campaign, run_campaign_sharded

scns = [scenarios.fig4_scenario(hp, vp) for hp in (0,1) for vp in (0,1)] * 2
batched = stack_scenarios(scns)
mesh = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))
local = run_campaign(batched)
sharded = run_campaign_sharded(batched, mesh)
np.testing.assert_allclose(np.array(local.finish_t), np.array(sharded.finish_t), rtol=1e-6)
print("SHARDED_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


# --------------------------------------------------------------------------
# the front door's compiled host work: chunk slicing, carry init, finalize
# --------------------------------------------------------------------------

SLICE_CHUNK = 8


def _distinct_rows(n):
    """``n`` fig4 rows that differ in every row (task length), so a wrong or
    repeated row shows."""
    rows = [scenarios.fig4_scenario(i % 2, (i // 2) % 2,
                                    length_mi=1000.0 + 125.0 * i)
            for i in range(n)]
    return stack_scenarios(rows)


def _eager_chunk(x, lo, chunk):
    """The reference: eager slice, then pad by repeating the last row."""
    c = x[lo:lo + chunk]
    short = chunk - c.shape[0]
    if short:
        pad = jnp.broadcast_to(x[-1:], (short,) + x.shape[1:])
        c = jnp.concatenate([c, pad])
    return c


@pytest.mark.parametrize("n", [SLICE_CHUNK, 2 * SLICE_CHUNK,
                               2 * SLICE_CHUNK + 3, SLICE_CHUNK - 5])
def test_compiled_chunk_slice_matches_eager(n):
    """Every chunk the slice program returns is the eager slice-and-pad bit
    for bit, and its bounds are ``(lo, n)``."""
    import jax

    from repro.core import campaign

    leaves = jax.tree.leaves(_distinct_rows(n))
    for lo in range(0, n, SLICE_CHUNK):
        part, bounds = campaign._slice_chunk(leaves, lo, SLICE_CHUNK)
        assert len(part) == len(leaves)
        for got, x in zip(part, leaves):
            want = np.asarray(_eager_chunk(x, lo, SLICE_CHUNK))
            got = np.asarray(got)
            assert (got.shape, got.dtype) == (want.shape, want.dtype)
            assert got.tobytes() == want.tobytes()
        assert bounds.dtype == jnp.int32
        assert np.asarray(bounds).tolist() == [lo, n]


def test_ragged_reduced_sweep_matches_materialized():
    """A reduced sweep whose last chunk is short (20 = 2 * 8 + 4) folds to
    the materialized run's answers: integer folds bitwise, the mean to
    rounding."""
    from repro.core.reducers import (ArgBestReducer, HistogramReducer,
                                     MeanReducer, SumReducer, ValuesReducer)

    n = 20
    batched = _distinct_rows(n)
    reduce = {
        "events": SumReducer("n_events"),
        "mt": MeanReducer("mean_turnaround"),
        "hist": HistogramReducer("mean_turnaround", 0.0, 8000.0, bins=16),
        "best": ArgBestReducer("mean_turnaround"),
        "vals": ValuesReducer("mean_turnaround", n_slots=n),
    }
    ref = run_campaign(batched)
    out = run_campaign(batched, chunk_size=SLICE_CHUNK, reduce=reduce)
    mt = np.asarray(ref.mean_turnaround)
    assert int(out["events"]) == int(np.asarray(ref.n_events).sum())
    np.testing.assert_array_equal(np.asarray(out["vals"]["values"]), mt)
    assert bool(out["vals"]["filled"].all())
    idx = np.clip((mt / 500.0).astype(np.int32), 0, 15)
    np.testing.assert_array_equal(np.asarray(out["hist"]["counts"]),
                                  np.bincount(idx, minlength=16))
    assert int(out["best"]["index"]) == int(np.argmin(mt))
    assert float(out["best"]["value"]) == mt.min()
    assert int(out["mt"]["n"]) == n
    np.testing.assert_allclose(float(out["mt"]["mean"]), mt.mean(), rtol=1e-5)


def test_warm_sweep_reuses_every_program_and_keeps_the_grid():
    """A second sweep of the same shapes hits the prepare cache, compiles
    nothing (slice, init, finalize and fold programs keep their executable
    counts) and leaves the grid readable: it was not donated."""
    import jax

    from repro.core import campaign
    from repro.core.reducers import ArgBestReducer, SumReducer

    batched = _distinct_rows(2 * SLICE_CHUNK + 3)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(batched)]
    reduce = {"events": SumReducer("n_events"),
              "best": ArgBestReducer("mean_turnaround")}
    programs = (campaign._slice_program, campaign._init_carries,
                campaign._finalize, campaign._run_chunk_fold)

    def sweep():
        return jax.block_until_ready(
            run_campaign(batched, chunk_size=SLICE_CHUNK, reduce=reduce))

    first = sweep()
    hits = campaign._plan.cache_info().hits
    sizes = [p._cache_size() for p in programs]
    second = sweep()
    assert campaign._plan.cache_info().hits > hits
    assert [p._cache_size() for p in programs] == sizes
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(second)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for x, want in zip(jax.tree.leaves(batched), before):
        np.testing.assert_array_equal(np.asarray(x), want)
