"""``segments.lookup`` and the permutations of ``segment_prefix_sum`` give
plain indexing's bits, in the one-hot form and the gather form alike.

The form is chosen from the table's static length against
``segments.ONE_HOT_MAX``; lengths just below, at and just above it take
each side of the rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import segments

pytestmark = pytest.mark.tier1

T = segments.ONE_HOT_MAX
LENGTHS = (1, 2, 50, T - 1, T, T + 1)
SPECIAL = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-45, -3.5, 1e30],
                   np.float32)


def _table(rng, dtype, n):
    if dtype == np.float32:
        t = rng.standard_normal(n).astype(np.float32)
        t[: min(n, SPECIAL.size)] = SPECIAL[: min(n, SPECIAL.size)]
        return rng.permutation(t)
    if dtype == np.int32:
        return rng.integers(-2**31, 2**31 - 1, n, dtype=np.int32, endpoint=True)
    return rng.random(n) < 0.5


@pytest.mark.parametrize("batched", [False, True], ids=["row", "vmap"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.bool_],
                         ids=["f32", "i32", "bool"])
def test_lookup_is_plain_indexing_bit_for_bit(dtype, n, batched):
    rng = np.random.default_rng(n)
    B, C = 3, 2 * n + 5
    tables = np.stack([_table(rng, dtype, n) for _ in range(B)])
    # indices clipped into range, as every call site clips them
    idx = np.clip(rng.integers(-3, n + 3, (B, C)), 0, n - 1).astype(np.int32)
    want = np.stack([t[i] for t, i in zip(tables, idx)])
    if batched:
        got = jax.jit(jax.vmap(segments.lookup))(tables, idx)
    else:
        got = np.stack([jax.jit(segments.lookup)(t, i)
                        for t, i in zip(tables, idx)])
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("n", (2, T + 1))
def test_sibling_tables_share_one_index(n):
    rng = np.random.default_rng(7)
    f, i, b = (_table(rng, d, n) for d in (np.float32, np.int32, np.bool_))
    idx = rng.integers(0, n, (4, 6)).astype(np.int32)
    got = jax.jit(segments.lookup)((f, i, b), idx)
    assert isinstance(got, tuple) and len(got) == 3
    for g, t in zip(got, (f, i, b)):
        assert np.asarray(g).tobytes() == t[idx].tobytes()


def _prefix_ref(values, seg, num_segments):
    """Exclusive prefix sum per segment in row order, the loop version."""
    seg = np.clip(seg, 0, num_segments)
    out = np.zeros_like(values)
    run = {}
    for i, (v, s) in enumerate(zip(values, seg)):
        out[i] = run.get(s, 0.0)
        run[s] = run.get(s, 0.0) + v
    return out


@pytest.mark.parametrize("n", LENGTHS)
def test_segment_prefix_sum_either_form(n):
    rng = np.random.default_rng(n + 1)
    # small whole numbers: every partial sum is exact in float32, so the
    # loop reference fixes the bits whatever the summation order
    values = rng.integers(0, 4, n).astype(np.float32)
    seg = rng.integers(-1, 7, n).astype(np.int32)
    want = _prefix_ref(values, seg, 6)
    got = jax.jit(segments.segment_prefix_sum, static_argnums=2)(values, seg, 6)
    assert np.asarray(got).tobytes() == want.tobytes()
    batched = jax.jit(jax.vmap(segments.segment_prefix_sum, (0, 0, None)),
                      static_argnums=2)(np.stack([values, values[::-1]]),
                                        np.stack([seg, seg[::-1]]), 6)
    assert np.asarray(batched[0]).tobytes() == want.tobytes()
    assert np.asarray(batched[1]).tobytes() == _prefix_ref(
        values[::-1], seg[::-1], 6).tobytes()


@pytest.mark.parametrize("n", (2, 50))
def test_forms_agree_on_the_engine_prefix_sums(n, monkeypatch):
    """The one-hot permutation and the gather/scatter one give the same bits
    on float sums that round, where the order of summation shows."""
    rng = np.random.default_rng(n + 2)
    values = (rng.random(n) * 1e3).astype(np.float32)
    seg = rng.integers(0, 5, n).astype(np.int32)
    one_hot = segments.segment_prefix_sum(values, seg, 5)
    monkeypatch.setattr(segments, "ONE_HOT_MAX", 0)
    gathered = segments.segment_prefix_sum(values, seg, 5)
    assert np.asarray(one_hot).tobytes() == np.asarray(gathered).tobytes()
