"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantic ground truth: each kernel's interpret-mode output is
asserted allclose against these over a shape/dtype sweep (tests/test_kernels).
They are also the *production CPU path*: the engine and the models call these
unless explicitly configured for the Pallas variants (TPU target).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

# numpy scalar, not a device array (see core/entities.py INF)
INF = np.float32(3.0e38)


# ---------------------------------------------------------------------------
# advance sweep (the simulator's updateVMsProcessing hot loop)
# ---------------------------------------------------------------------------

def advance_sweep_ref(
    rem: Array, rate: Array, active: Array, bound_dt: Array
) -> tuple[Array, Array]:
    """dt to next completion (capped by ``bound_dt``) + work depletion.

    Rank-polymorphic over a leading scenario axis: ``[C]`` inputs with a
    scalar bound reduce to a scalar ``dt``; batch-major ``[B, C]`` inputs
    with a ``[B]`` bound reduce per row to ``dt [B]`` — bitwise the same
    per-row math as ``vmap`` of the rank-1 form (the batch engine's
    bit-identity contract, DESIGN.md §10).
    """
    dt_fin = jnp.where(active & (rate > 0), rem / jnp.maximum(rate, 1e-30), INF)
    dt = jnp.minimum(jnp.min(dt_fin, axis=-1, initial=INF), bound_dt)
    new_rem = jnp.where(
        active, jnp.maximum(rem - rate * dt[..., None], 0.0), rem
    )
    return dt, new_rem


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attn_mask(sq: int, sk: int, causal: bool, window: int | None) -> Array:
    """[sq, sk] bool. Rows are aligned to the *end* of the key axis (standard
    decode/prefill alignment: query i attends keys <= i + (sk - sq))."""
    row = jnp.arange(sq)[:, None] + (sk - sq)
    col = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= col <= row
    if window is not None:
        mask &= col > row - window
    return mask


def attention_ref(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    scale: float | None = None,
) -> Array:
    """Dense softmax attention with GQA, sliding window and logit softcap.

    q: [B, Hq, Sq, D]; k, v: [B, Hk, Sk, D] with Hq % Hk == 0.
    """
    B, Hq, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    g = Hq // Hk
    scale = (D ** -0.5) if scale is None else scale
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    mask = _attn_mask(Sq, Sk, causal, window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — sequential-scan oracle
# ---------------------------------------------------------------------------

def ssd_ref(
    x: Array,      # [B, S, H, P]
    dt: Array,     # [B, S, H]   (positive step sizes, post-softplus)
    A: Array,      # [H]         (negative decay rates)
    Bm: Array,     # [B, S, G, N]
    Cm: Array,     # [B, S, G, N]
    D: Array,      # [H]         skip connection
) -> Array:
    """y_t = C_t h_t + D x_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T.

    Plain lax.scan over time; the Pallas twin (ssd_scan.py) is chunk-parallel.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)  # [B,S,H,N]
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        decay = jnp.exp(dtt * A)[..., None, None]          # [B,H,1,1]
        upd = (dtt[..., None, None] * xt[..., :, None]) * bt[..., None, :]
        h = decay * h + upd                                 # [B,H,P,N]
        yt = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, yt

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bh, 1, 0),
        jnp.moveaxis(Ch, 1, 0),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + D[None, None, :, None] * xf
    return y.astype(x.dtype)


def ssd_chunked_ref(
    x: Array, dt: Array, A: Array, Bm: Array, Cm: Array, D: Array,
    chunk: int = 64, return_state: bool = False,
):
    """Chunk-parallel SSD in pure jnp (the math the Pallas kernel implements;
    also the production CPU/XLA path used by the Mamba2 model for training).
    With ``return_state`` also returns the final [B, H, P, N] SSM state
    (prefill needs it to seed decode).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, "sequence must be chunk-padded"
    nc = S // chunk
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dA = dt.astype(jnp.float32) * A[None, None, :]          # [B,S,H]

    # reshape into chunks: [B, nc, Q, ...]
    xc = xf.reshape(Bsz, nc, chunk, H, P)
    dtc = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    dAc = dA.reshape(Bsz, nc, chunk, H)
    Bc = Bh.reshape(Bsz, nc, chunk, H, N)
    Cc = Ch.reshape(Bsz, nc, chunk, H, N)

    cum = jnp.cumsum(dAc, axis=2)                            # [B,nc,Q,H]
    seg = cum[:, :, -1, :]                                   # [B,nc,H]

    # intra-chunk (dual quadratic form)
    CB = jnp.einsum("bcqhn,bckhn->bchqk", Cc, Bc)            # [B,nc,H,Q,Q]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # [B,nc,Q,K,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    W = CB * jnp.moveaxis(L, -1, 2)                          # [B,nc,H,Q,K]
    y_intra = jnp.einsum(
        "bchqk,bckh,bckhp->bcqhp", W, dtc, xc
    )

    # inter-chunk: carry state across chunks with a scan over nc
    w = jnp.exp(seg[:, :, None, :] - cum) * dtc              # [B,nc,Q,H]
    state_in = jnp.einsum("bcqhp,bcqh,bcqhn->bchpn", xc, w, Bc)

    def carry(h, inp):
        s_in, decay = inp                                    # [B,H,P,N], [B,H]
        h_out = h                                            # state BEFORE chunk
        h = decay[..., None, None] * h + s_in
        return h, h_out

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    h_final, h_prev = jax.lax.scan(
        carry,
        h0,
        (jnp.moveaxis(state_in, 1, 0), jnp.moveaxis(jnp.exp(seg), 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)                      # [B,nc,H,P,N]
    y_inter = jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp", Cc, h_prev, jnp.exp(cum)
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    y = y + D[None, None, :, None] * xf
    y = y.astype(x.dtype)
    if return_state:
        return y, h_final
    return y
