"""Streaming reductions: folded summaries vs the materialized [N, ...]
reference on a 1024-point grid — bitwise for integer folds (counts,
histogram bins, argbest, values tables), tolerance-bounded for float means
and percentile sketches; chunk-size invariance; the sharded fold path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import run_campaign, scenarios, simulate, stack_scenarios
from repro.core.reducers import (
    ArgBestReducer,
    HistogramReducer,
    LatencyHistogramReducer,
    MeanReducer,
    SumReducer,
    ValuesReducer,
)

pytestmark = [
    pytest.mark.tier1,
    pytest.mark.filterwarnings("error:Some donated buffers were not usable"),
]

N = 1024
HIST_LO, HIST_HI, HIST_BINS = 0.0, 8000.0, 64

# one reducer dict reused everywhere: reducers are static jit args, so every
# test folding with these at the same chunk size shares ONE compiled program
REDUCE = {
    "events": SumReducer("n_events"),
    "mt": MeanReducer("mean_turnaround"),
    "hist": HistogramReducer("mean_turnaround", HIST_LO, HIST_HI,
                             bins=HIST_BINS, qs=(0.5, 0.9, 0.99)),
    "best": ArgBestReducer("mean_turnaround"),
    "vals": ValuesReducer("mean_turnaround", n_slots=N),
}


@pytest.fixture(scope="module")
def grid():
    """1024-point fig4 grid: policy combos x workload scale, with the
    materialized reference results."""
    base = [scenarios.fig4_scenario(hp, vp) for hp in (0, 1) for vp in (0, 1)]
    rows = [
        s.replace(cloudlets=s.cloudlets.replace(
            length_mi=s.cloudlets.length_mi * (1.0 + 0.02 * (i % 37))
        ))
        for i, s in enumerate(base * (N // 4))
    ]
    batched = stack_scenarios(rows)
    ref = run_campaign(batched, chunk_size=128)
    return batched, ref


def _ref_hist_counts(values):
    width = (HIST_HI - HIST_LO) / HIST_BINS
    idx = np.clip(((values - HIST_LO) / width).astype(np.int32),
                  0, HIST_BINS - 1)
    return np.bincount(idx, minlength=HIST_BINS).astype(np.int32)


def test_folded_matches_materialized(grid):
    batched, ref = grid
    out = run_campaign(batched, chunk_size=128, reduce=REDUCE)
    mt = np.array(ref.mean_turnaround)

    # integer folds are bitwise
    assert int(out["events"]) == int(np.array(ref.n_events).sum())
    np.testing.assert_array_equal(np.array(out["vals"]["values"]), mt)
    assert bool(out["vals"]["filled"].all())
    np.testing.assert_array_equal(np.array(out["hist"]["counts"]),
                                  _ref_hist_counts(mt))

    # argbest: value + index + the winning policy row itself
    best = int(np.argmin(mt))
    assert int(out["best"]["index"]) == best
    assert float(out["best"]["value"]) == mt[best]
    want_row = jax.tree.map(lambda l: l[best], batched.policy)
    for got, want in zip(jax.tree.leaves(out["best"]["policy"]),
                         jax.tree.leaves(want_row)):
        np.testing.assert_array_equal(np.array(got), np.array(want))

    # float mean/std to rounding; histogram quantiles to one bin width
    assert int(out["mt"]["n"]) == N
    np.testing.assert_allclose(float(out["mt"]["mean"]), mt.mean(), rtol=1e-5)
    np.testing.assert_allclose(float(out["mt"]["std"]), mt.std(), rtol=1e-3)
    width = (HIST_HI - HIST_LO) / HIST_BINS
    for q in (0.5, 0.9, 0.99):
        assert abs(float(out["hist"][f"q{q:g}"]) - np.quantile(mt, q)) <= width


def test_chunk_size_invariance(grid):
    """Integer folds must be bitwise identical for any chunking — including
    a ragged trailing chunk (1024 = 5*192 + 64)."""
    batched, _ = grid
    a = run_campaign(batched, chunk_size=128, reduce=REDUCE)
    b = run_campaign(batched, chunk_size=192, reduce=REDUCE)
    assert int(a["events"]) == int(b["events"])
    np.testing.assert_array_equal(np.array(a["vals"]["values"]),
                                  np.array(b["vals"]["values"]))
    np.testing.assert_array_equal(np.array(a["hist"]["counts"]),
                                  np.array(b["hist"]["counts"]))
    assert int(a["best"]["index"]) == int(b["best"]["index"])
    assert float(a["best"]["value"]) == float(b["best"]["value"])
    np.testing.assert_allclose(float(a["mt"]["mean"]), float(b["mt"]["mean"]),
                               rtol=1e-6)


def test_sharded_fold_matches(grid):
    """The shard_map fold on a 1-device mesh is bitwise the local fold."""
    from jax.sharding import Mesh

    batched, ref = grid
    mesh = Mesh(jax.devices()[:1], ("data",))
    out = run_campaign(batched, chunk_size=128, mesh=mesh, reduce=REDUCE)
    np.testing.assert_array_equal(np.array(out["vals"]["values"]),
                                  np.array(ref.mean_turnaround))
    np.testing.assert_array_equal(
        np.array(out["hist"]["counts"]),
        _ref_hist_counts(np.array(ref.mean_turnaround)),
    )
    assert int(out["best"]["index"]) == int(np.argmin(
        np.array(ref.mean_turnaround)))


def test_single_reducer_form():
    """A bare reducer (not a dict) returns its summary directly."""
    batched = stack_scenarios([scenarios.fig4_scenario(0, 0)] * 4)
    out = run_campaign(batched, reduce=SumReducer("n_finished"))
    assert int(out) == 4 * 8


def test_argbest_max_mode(grid):
    batched, ref = grid
    out = run_campaign(batched, chunk_size=128,
                       reduce=ArgBestReducer("mean_turnaround", mode="max"))
    mt = np.array(ref.mean_turnaround)
    assert int(out["index"]) == int(np.argmax(mt))
    assert float(out["value"]) == mt.max()


def test_reducer_validation():
    batched = stack_scenarios([scenarios.fig4_scenario(0, 0)] * 2)
    with pytest.raises(ValueError, match="unknown SimResult field"):
        run_campaign(batched, reduce=SumReducer("not_a_field"))
    with pytest.raises(ValueError, match="one scalar per scenario row"):
        run_campaign(batched, reduce=SumReducer(lambda r: r.turnaround))
    with pytest.raises(ValueError, match="empty histogram range"):
        HistogramReducer("makespan", 1.0, 1.0)
    with pytest.raises(ValueError, match="mode"):
        ArgBestReducer("makespan", mode="best")
    with pytest.raises(TypeError, match="CampaignReducer"):
        run_campaign(batched, reduce={"x": jnp.sum})


def test_argbest_fold_leaves_simulate_working():
    """The fold donates its reducer carries.  ArgBest's initial best used to
    be the module-level ``entities.INF`` device array itself, so the first
    fold deleted that constant and every later ``simulate`` in the process
    failed with "Array has been deleted"."""
    batched = stack_scenarios([scenarios.fig4_scenario(0, 0)] * 4)
    reducer = ArgBestReducer("mean_turnaround")
    for _ in range(2):
        out = run_campaign(batched, chunk_size=2, reduce=reducer)
        assert int(out["index"]) == 0
    res = jax.jit(simulate)(scenarios.fig4_scenario(0, 0))
    assert int(res.n_finished) == 8


def test_module_constants_are_not_device_arrays():
    """No module of the package holds a device array: importing it touches
    no backend, and no donating call can delete a shared constant."""
    import sys

    from repro.core import entities
    from repro.kernels import ref

    assert not isinstance(entities.INF, jax.Array)
    assert not isinstance(ref.INF, jax.Array)
    held = [
        f"{name}.{attr}"
        for name, mod in list(sys.modules.items()) if name.startswith("repro")
        for attr, value in vars(mod).items() if isinstance(value, jax.Array)
    ]
    assert held == []


def test_importing_the_engine_touches_no_backend():
    """A fresh process imports ``repro.core`` and the kernel router without
    bringing up any JAX backend: a module-level device array would."""
    import os
    import subprocess
    import sys

    code = ("import repro.core, repro.core.reducers, repro.kernels.ops\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_reducer_carries_are_fresh_buffers():
    """Every ``init`` builds new buffers: the fold donates them, so a carry
    leaf that something else still holds would be deleted under it."""
    batched = stack_scenarios([scenarios.fig4_scenario(0, 0)] * 2)
    res_avals = jax.eval_shape(simulate, batched)
    for r in (*REDUCE.values(), LatencyHistogramReducer("ttft", 0.0, 10.0)):
        a, b = (jax.tree.leaves(r.init(batched, res_avals)) for _ in range(2))
        assert all(x is not y for x, y in zip(a, b)), r
