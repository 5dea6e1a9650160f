"""Search driver: knob-space helpers, campaign building, random search vs
the exhaustive reference, and successive halving's one-compiled-program
property (the runtime counterpart of simlint R5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import campaign, run_campaign, scenarios
from repro.core.reducers import ValuesReducer
from repro.core.search import (
    build_campaign,
    grid_params,
    random_search,
    sample_params,
    successive_halving,
)

pytestmark = [
    pytest.mark.tier1,
    pytest.mark.filterwarnings("error:Some donated buffers were not usable"),
]


def test_grid_params_cartesian():
    g = grid_params({"a": [1.0, 2.0], "b": [10.0, 20.0, 30.0]})
    assert all(v.shape == (6,) for v in g.values())
    combos = set(zip(np.array(g["a"]).tolist(), np.array(g["b"]).tolist()))
    assert combos == {(a, b) for a in (1.0, 2.0) for b in (10.0, 20.0, 30.0)}
    with pytest.raises(ValueError, match="empty"):
        grid_params({})


def test_sample_params_support_and_determinism():
    space = {"x": [1.0, 2.0, 4.0], "y": [0, 1]}
    a = sample_params(jax.random.PRNGKey(3), space, 64)
    b = sample_params(jax.random.PRNGKey(3), space, 64)
    assert set(np.array(a["x"]).tolist()) <= {1.0, 2.0, 4.0}
    assert set(np.array(a["y"]).tolist()) <= {0, 1}
    np.testing.assert_array_equal(np.array(a["x"]), np.array(b["x"]))


def test_build_campaign_policy_knobs():
    tmpl = scenarios.fig4_scenario(0, 0)
    params = {"host_policy": jnp.asarray([0, 0, 1, 1]),
              "vm_policy": jnp.asarray([0, 1, 0, 1])}
    batched = build_campaign(tmpl, params)
    np.testing.assert_array_equal(np.array(batched.policy.host_policy),
                                  [0, 0, 1, 1])
    # untouched template leaves broadcast along the campaign axis
    assert jax.tree.leaves(batched.cloudlets)[0].shape[0] == 4


def test_build_campaign_extras_need_instantiate():
    tmpl = scenarios.fig4_scenario(0, 0)
    params = {"length_scale": jnp.asarray([1.0, 2.0])}
    with pytest.raises(ValueError, match="instantiate"):
        build_campaign(tmpl, params)

    def instantiate(template, extras, n, key):
        cl = jax.vmap(
            lambda s: template.cloudlets.replace(
                length_mi=template.cloudlets.length_mi * s)
        )(extras["length_scale"])
        return {"cloudlets": cl}

    batched = build_campaign(tmpl, params, instantiate=instantiate)
    res = run_campaign(batched)
    # doubling cloudlet length doubles fig4 turnaround
    np.testing.assert_allclose(np.array(res.mean_turnaround)[1],
                               2 * np.array(res.mean_turnaround)[0],
                               rtol=1e-6)


def test_random_search_matches_exhaustive_reference():
    tmpl = scenarios.fig4_scenario(0, 0)
    space = {"host_policy": [0, 1], "vm_policy": [0, 1]}
    out = random_search(tmpl, space, key=jax.random.PRNGKey(0), n=16,
                        metric="mean_turnaround", chunk_size=8)
    ref = run_campaign(
        build_campaign(tmpl, out["params"]), chunk_size=8,
        reduce=ValuesReducer("mean_turnaround", n_slots=16),
    )
    np.testing.assert_array_equal(np.array(out["values"]),
                                  np.array(ref["values"]))
    assert out["best_index"] == int(np.argmin(np.array(out["values"])))
    assert float(out["best_value"]) == np.array(out["values"]).min()
    # fig4: space/space dominates — the best draw must be one of its rows
    assert int(out["best_params"]["host_policy"]) == 0
    assert int(out["best_params"]["vm_policy"]) == 0


def test_successive_halving_finds_optimum_and_reuses_program():
    tmpl = scenarios.fig4_scenario(0, 0)
    space = {"host_policy": [0, 1], "vm_policy": [0, 1]}
    kw = dict(n0=8, fidelities=(4000.0, 8000.0), eta=2,
              metric="mean_turnaround", chunk_size=4)
    size = campaign._run_chunk_fold._cache_size
    before = size()
    out = successive_halving(tmpl, space, key=jax.random.PRNGKey(1), **kw)
    first = size() - before
    assert first <= 1, "rungs forked the compiled fold program"
    # a fresh search with different candidate values compiles nothing new
    out2 = successive_halving(tmpl, space, key=jax.random.PRNGKey(9), **kw)
    assert size() - before == first, "knob values leaked into the jit cache"

    # The winner is the best combo the search SAMPLED.  Eight uniform draws
    # from four combos need not include space/space (0, 0): under jax's
    # threefry-partitionable random bits, key 9 draws vm_policy == 1 eight
    # times.  Score every combo exhaustively and compare against the sample.
    combos = grid_params(space)
    full = np.array(run_campaign(build_campaign(tmpl, combos)).mean_turnaround)
    score = {(int(h), int(v)): t for h, v, t in zip(
        np.array(combos["host_policy"]), np.array(combos["vm_policy"]), full)}
    for res in (out, out2):
        sampled = set(zip(np.array(res["params"]["host_policy"]).tolist(),
                          np.array(res["params"]["vm_policy"]).tolist()))
        best = (int(res["best_params"]["host_policy"]),
                int(res["best_params"]["vm_policy"]))
        assert best in sampled
        assert score[best] == min(score[c] for c in sampled)
    # key 1 samples space/space, fig4's optimum, and the search finds it
    assert (0, 0) in set(zip(np.array(out["params"]["host_policy"]).tolist(),
                             np.array(out["params"]["vm_policy"]).tolist()))
    assert (int(out["best_params"]["host_policy"]),
            int(out["best_params"]["vm_policy"])) == (0, 0)
    ns = [r["candidates"].shape[0] for r in out["rungs"]]
    assert ns == [8, 4]
    assert [r["fidelity"] for r in out["rungs"]] == [4000.0, 8000.0]
    # survivors of rung 0 are its top half
    v0 = np.array(out["rungs"][0]["values"])
    picked = set(np.array(out["rungs"][1]["candidates"]).tolist())
    assert picked == set(np.argsort(v0)[:4].tolist())


def test_successive_halving_validation():
    tmpl = scenarios.fig4_scenario(0, 0)
    space = {"host_policy": [0, 1]}
    with pytest.raises(ValueError, match="not a Policy field"):
        successive_halving(tmpl, space, key=jax.random.PRNGKey(0), n0=4,
                           fidelities=(1.0,), fidelity_knob="mtbf")
    with pytest.raises(ValueError, match="cannot also be"):
        successive_halving(tmpl, {"horizon": [1.0]},
                           key=jax.random.PRNGKey(0), n0=4, fidelities=(1.0,))
    with pytest.raises(ValueError, match="cannot halve"):
        successive_halving(tmpl, space, key=jax.random.PRNGKey(0), n0=2,
                           fidelities=(1.0, 2.0, 3.0))
