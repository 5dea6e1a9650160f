"""Per-kernel interpret-mode vs pure-jnp-oracle allclose, swept over
shapes/dtypes (the (c) deliverable contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.vm_update import advance_sweep_pallas

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- vm_update
@pytest.mark.parametrize("c", [1, 7, 100, 1000, 4096])
@pytest.mark.parametrize("block", [128, 1024])
def test_advance_sweep_shapes(c, block):
    rem = jnp.asarray(RNG.uniform(0.1, 100, c).astype(np.float32))
    rate = jnp.asarray(RNG.uniform(0, 5, c).astype(np.float32))
    active = jnp.asarray(RNG.random(c) > 0.3)
    bound = jnp.float32(RNG.uniform(0.1, 50))
    dt0, nr0 = ref.advance_sweep_ref(rem, rate, active, bound)
    dt1, nr1 = advance_sweep_pallas(rem, rate, active, bound, block=block)
    np.testing.assert_allclose(float(dt0), float(dt1), rtol=1e-6)
    np.testing.assert_allclose(np.array(nr0), np.array(nr1), rtol=1e-5,
                               atol=1e-5)


# deterministic property sweep (hypothesis is absent in the container image;
# each seed derives a random cloudlet count, covering the same space)
@pytest.mark.parametrize("seed", range(20))
def test_advance_sweep_property(seed):
    rng = np.random.default_rng(seed)
    c = int(rng.integers(1, 301))
    rem = jnp.asarray(rng.uniform(0.01, 10, c).astype(np.float32))
    rate = jnp.asarray(rng.uniform(0, 2, c).astype(np.float32))
    active = jnp.asarray(rng.random(c) > 0.5)
    bound = jnp.float32(rng.uniform(0.01, 5))
    dt, nr = advance_sweep_pallas(rem, rate, active, bound, block=128)
    # dt never exceeds the bound; no remaining work goes negative; at least
    # one active cloudlet hits zero if dt < bound
    assert float(dt) <= float(bound) + 1e-6
    assert (np.array(nr) >= 0).all()
    act = np.array(active) & (np.array(rate) > 0)
    if act.any() and float(dt) < float(bound) - 1e-6:
        assert np.isclose(np.array(nr)[act].min(), 0.0, atol=1e-3)


# --------------------------------------------- vm_update ragged-row fallback
#
# Rows longer than one tile take the two-phase sub-grid (B, 2, nb).  A
# non-power-of-two nb (e.g. 3 tiles) is the raggedest case: the reduction
# crosses tile seams that don't align with any power-of-two split.  Contract:
#   * dt is BITWISE equal to the jnp oracle — f32 min is order-exact, so
#     tiling the reduction may not change a single bit;
#   * rem' is BITWISE equal to the fused single-tile kernel — falling back
#     must not change the kernel's math — and within 1 ULP of the oracle
#     (XLA contracts the oracle's rem - rate*dt into an FMA; the kernel's
#     separate mul/sub rounds the product, so exactly-finishing cloudlets
#     can land 1 ULP apart; this is the only permitted divergence).

def _advance_case(rng, b, c):
    rem = jnp.asarray(rng.uniform(0.1, 100, (b, c)).astype(np.float32))
    rate = jnp.asarray(rng.uniform(0, 5, (b, c)).astype(np.float32))
    active = jnp.asarray(rng.random((b, c)) > 0.3)
    bound = jnp.asarray(rng.uniform(0.1, 50, (b,)).astype(np.float32))
    return rem, rate, active, bound


@pytest.mark.parametrize("c,block,nb", [(300, 128, 3), (1280, 256, 5)])
def test_advance_ragged_tiles_parity(c, block, nb):
    from repro.kernels.vm_update import kernel_plan

    plan = kernel_plan(2, c, block)
    assert plan["variant"] == "two_phase" and plan["nb"] == nb

    rem, rate, active, bound = _advance_case(np.random.default_rng(c), 2, c)
    dt0, nr0 = ref.advance_sweep_ref(rem, rate, active, bound)
    dt1, nr1 = advance_sweep_pallas(rem, rate, active, bound, block=block)
    # same inputs through the FUSED kernel (block covering the whole row):
    # the fallback's sliced reduction must reproduce it bit-for-bit
    dt2, nr2 = advance_sweep_pallas(rem, rate, active, bound, block=2048)
    np.testing.assert_array_equal(np.array(dt0), np.array(dt1))
    np.testing.assert_array_equal(np.array(dt1), np.array(dt2))
    np.testing.assert_array_equal(np.array(nr1), np.array(nr2))
    np.testing.assert_allclose(np.array(nr0), np.array(nr1),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.tier1
def test_advance_resolver_fallback_frontier():
    """Through ``ops.resolve_advance`` the two-phase path only engages past
    the tile cap: C = 3 * ``_MAX_BLOCK`` is the smallest non-pow-2-nb row the
    resolver can actually produce (nb = 3)."""
    from repro.kernels import ops
    from repro.kernels.vm_update import kernel_plan

    c = 3 * ops._MAX_BLOCK
    assert ops.advance_block(c) == ops._MAX_BLOCK
    plan = kernel_plan(1, c, ops.advance_block(c))
    assert plan["variant"] == "two_phase" and plan["nb"] == 3

    rng = np.random.default_rng(17)
    rem, rate, active, bound = _advance_case(rng, 1, c)
    # rank-1 (single-scenario) through the resolver, both impls
    args = (rem[0], rate[0], active[0], bound[0])
    dt0, nr0 = ops.resolve_advance("jnp")(*args)
    dt1, nr1 = ops.resolve_advance("pallas")(*args)
    assert np.array(dt1).shape == ()
    np.testing.assert_array_equal(np.array(dt0), np.array(dt1))
    np.testing.assert_allclose(np.array(nr0), np.array(nr1),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.tier1
def test_advance_resolver_batch_major_fallback():
    from repro.kernels import ops

    c = 3 * ops._MAX_BLOCK
    rem, rate, active, bound = _advance_case(np.random.default_rng(18), 2, c)
    dt0, nr0 = ops.resolve_advance("jnp")(rem, rate, active, bound)
    dt1, nr1 = ops.resolve_advance("pallas")(rem, rate, active, bound)
    assert np.array(dt1).shape == (2,)
    np.testing.assert_array_equal(np.array(dt0), np.array(dt1))
    np.testing.assert_allclose(np.array(nr0), np.array(nr1),
                               rtol=1e-6, atol=1e-5)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hk,sq,sk,d",
    [
        (1, 2, 2, 64, 64, 32),     # MHA
        (2, 4, 2, 128, 128, 64),   # GQA
        (1, 8, 1, 96, 224, 64),    # MQA, ragged kv / padding path
        (1, 4, 4, 1, 256, 64),     # decode-like single query
    ],
)
def test_flash_attention_shapes(b, hq, hk, sq, sk, d, dtype):
    q = jnp.asarray(RNG.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hk, sk, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hk, sk, d)), dtype)
    o0 = ref.attention_ref(q, k, v, causal=True)
    o1 = flash_attention_pallas(q, k, v, causal=True, bq=64, bk=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.array(o0, np.float32), np.array(o1, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "kw",
    [
        dict(causal=False),
        dict(causal=True, window=32),
        dict(causal=True, softcap=20.0),
        dict(causal=True, window=48, softcap=50.0),
    ],
)
def test_flash_attention_variants(kw):
    b, hq, hk, s, d = 2, 4, 2, 160, 32
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hk, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hk, s, d)).astype(np.float32))
    o0 = ref.attention_ref(q, k, v, **kw)
    o1 = flash_attention_pallas(q, k, v, bq=64, bk=64, **kw)
    np.testing.assert_allclose(np.array(o0), np.array(o1), atol=2e-5, rtol=2e-5)


def test_flash_vs_xla_flash():
    """The model's XLA online-softmax path == oracle too."""
    from repro.models.attention import flash_xla

    b, hq, hk, s, d = 1, 4, 2, 200, 32
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hk, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hk, s, d)).astype(np.float32))
    o0 = ref.attention_ref(q, k, v, causal=True, window=64)
    o1 = flash_xla(q, k, v, causal=True, window=64, softcap=0.0,
                   scale=d ** -0.5, chunk=64)
    np.testing.assert_allclose(np.array(o0), np.array(o1), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [
        (1, 64, 2, 16, 1, 16, 32),
        (2, 128, 4, 32, 2, 32, 64),
        (1, 96, 2, 16, 1, 32, 32),   # padding path (96 % 64 != 0 w/ chunk 32)
    ],
)
def test_ssd_scan_shapes(b, s, h, p, g, n, chunk):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)).astype(np.float32)) * 0.5
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)).astype(np.float32))
    A = jnp.asarray(-RNG.uniform(0.5, 2, h).astype(np.float32))
    Bm = jnp.asarray(RNG.standard_normal((b, s, g, n)).astype(np.float32)) * 0.3
    Cm = jnp.asarray(RNG.standard_normal((b, s, g, n)).astype(np.float32)) * 0.3
    D = jnp.asarray(RNG.uniform(0, 1, h).astype(np.float32))
    y_seq = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y_chunk = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D,
                                  chunk=min(chunk, s))
    y_pl = ssd_scan_pallas(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.array(y_seq), np.array(y_chunk),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.array(y_seq), np.array(y_pl),
                               atol=2e-4, rtol=2e-4)


def test_ssd_chunked_final_state():
    """return_state must equal the sequential scan's final hidden state."""
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 16
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)).astype(np.float32)) * 0.5
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)).astype(np.float32))
    A = jnp.asarray(-RNG.uniform(0.5, 2, h).astype(np.float32))
    Bm = jnp.asarray(RNG.standard_normal((b, s, g, n)).astype(np.float32)) * 0.3
    Cm = jnp.asarray(RNG.standard_normal((b, s, g, n)).astype(np.float32)) * 0.3
    D = jnp.zeros((h,), jnp.float32)
    _, h_chunk = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=32,
                                     return_state=True)
    # sequential reference state
    import jax

    Bh = jnp.repeat(Bm, h // g, axis=2)

    def step(hs, t):
        decay = jnp.exp(dt[:, t] * A)[..., None, None]
        upd = (dt[:, t][..., None, None] * x[:, t][..., None]) * Bh[:, t][:, :, None, :]
        return decay * hs + upd, None

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    h_seq, _ = jax.lax.scan(step, h0, jnp.arange(s))
    np.testing.assert_allclose(np.array(h_seq), np.array(h_chunk),
                               atol=2e-4, rtol=2e-4)
