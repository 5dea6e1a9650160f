"""GQA attention: XLA flash (online-softmax scan) and Pallas paths + KV cache.

The default ("xla") path is an online-softmax scan over KV chunks — the flash
algorithm expressed in jnp — so activation memory is O(S * chunk) on every
backend and the 32k prefill lowers without an S x S score tensor.  The
"pallas" path calls the hand-tiled TPU kernel (kernels/flash_attention.py).

Supports: GQA (no KV repetition in HBM on the XLA path either — grouped
einsum), causal + sliding window + attention-logit softcap, qk-norm,
RoPE / M-RoPE, cross-attention (whisper), and single-token decode against a
preallocated cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from repro.dist.act_sharding import shard_act
from repro.models import layers


def init_attention(key, cfg, cross: bool = False) -> dict:
    D = cfg.d_model
    q_dim = cfg.n_heads * cfg.d_head
    kv_dim = cfg.n_kv_heads * cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.trunc_normal(ks[0], (D, q_dim)),
        "wk": layers.trunc_normal(ks[1], (D, kv_dim)),
        "wv": layers.trunc_normal(ks[2], (D, kv_dim)),
        "wo": layers.trunc_normal(ks[3], (q_dim, D)),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rms_norm(cfg.d_head)
        p["k_norm"] = layers.init_rms_norm(cfg.d_head)
    return p


def flash_xla(
    q: Array,  # [B, Hq, Sq, D]
    k: Array,  # [B, Hk, Sk, D]
    v: Array,
    *,
    causal: bool,
    window: int | None,
    softcap: float,
    scale: float,
    chunk: int = 512,
) -> Array:
    """Online-softmax scan over KV chunks (flash attention in XLA)."""
    B, Hq, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    g = Hq // Hk
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (Sk + pad) // chunk
    qg = q.reshape(B, Hk, g, Sq, D).astype(jnp.float32)
    kc = k.reshape(B, Hk, nk, chunk, D).astype(jnp.float32)
    vc = v.reshape(B, Hk, nk, chunk, D).astype(jnp.float32)
    row = jnp.arange(Sq)[:, None] + (Sk - Sq)                   # [Sq,1]

    def step(carry, inp):
        acc, m, l = carry
        kj, vj, j = inp                                         # [B,Hk,chunk,D]
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kj) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        col = j * chunk + jnp.arange(chunk)[None, :]            # [1,chunk]
        valid = col < Sk
        if causal:
            valid &= col <= row
        if window is not None:
            valid &= col > row - window
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > -5e29, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vj)
        return (acc, m_new, l), None

    acc0 = shard_act(jnp.zeros((B, Hk, g, Sq, D), jnp.float32),
                     ("batch", "model", None, None, None))
    m0 = shard_act(jnp.full((B, Hk, g, Sq, 1), -1e30, jnp.float32),
                   ("batch", "model", None, None, None))
    l0 = shard_act(jnp.zeros((B, Hk, g, Sq, 1), jnp.float32),
                   ("batch", "model", None, None, None))
    (acc, _, l), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0), jnp.arange(nk)),
    )
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def _sdpa(q, k, v, *, causal, window, softcap, scale, impl):
    if impl == "pallas":
        from repro.kernels import ops

        return ops.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    return flash_xla(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
    )


def _project_qkv(params, cfg, x, kv_x=None):
    """Project and head-split. kv_x: cross-attention source (defaults x)."""
    dt = x.dtype
    B, S, _ = x.shape
    src = x if kv_x is None else kv_x
    Skv = src.shape[1]
    q = (x @ params["wq"].astype(dt)).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = (src @ params["wk"].astype(dt)).reshape(B, Skv, cfg.n_kv_heads, cfg.d_head)
    v = (src @ params["wv"].astype(dt)).reshape(B, Skv, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = shard_act(q, ("batch", None, "model", None))
    k = shard_act(k, ("batch", None, "model", None))
    v = shard_act(v, ("batch", None, "model", None))
    return q, k, v


def attention(
    params: dict,
    cfg,
    x: Array,                       # [B, S, D]
    positions: Array | None = None, # [B, S] (or [3, B, S] for M-RoPE)
    *,
    causal: bool = True,
    window: int | None = None,
    kv_x: Array | None = None,      # cross-attention keys/values source
    rope: bool = True,
) -> Array:
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, kv_x)
    if rope and kv_x is None and cfg.pos_embed == "rope":
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections is not None and positions.ndim == 3:
            q = layers.apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = layers.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            if positions.ndim == 3:
                positions = positions[0]
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
    out = _sdpa(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal and kv_x is None, window=window,
        softcap=cfg.attn_softcap, scale=cfg.d_head ** -0.5, impl=cfg.attn_impl,
    )
    out = jnp.swapaxes(out, 1, 2).reshape(B, S, cfg.n_heads * cfg.d_head)
    return out @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, n_attn_layers: int, dtype):
    shape = (n_attn_layers, batch, cfg.n_kv_heads, max_len, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_prefill(params, cfg, x, positions, *, window=None):
    """Prefill: run attention AND return this layer's (k, v) for the cache."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x)
    if cfg.pos_embed == "rope":
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections is not None and positions.ndim == 3:
            q = layers.apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = layers.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            pos = positions[0] if positions.ndim == 3 else positions
            q = layers.apply_rope(q, pos, cfg.rope_theta)
            k = layers.apply_rope(k, pos, cfg.rope_theta)
    kT, vT = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    out = _sdpa(
        jnp.swapaxes(q, 1, 2), kT, vT,
        causal=True, window=window,
        softcap=cfg.attn_softcap, scale=cfg.d_head ** -0.5, impl=cfg.attn_impl,
    )
    out = jnp.swapaxes(out, 1, 2).reshape(B, S, cfg.n_heads * cfg.d_head)
    return out @ params["wo"].astype(x.dtype), (kT, vT)


def attention_decode(
    params: dict,
    cfg,
    x: Array,          # [B, 1, D]
    k_cache: Array,    # [B, Hk, L, Dh]  (L = max context, zero-padded)
    v_cache: Array,
    pos: Array,        # [B] current write position
    *,
    window: int | None = None,
):
    """One-token decode: write k/v at ``pos``, attend over the valid prefix."""
    B = x.shape[0]
    q, k, v = _project_qkv(params, cfg, x)
    posb = pos[:, None]                                        # [B,1]
    if cfg.pos_embed == "rope":
        if cfg.mrope_sections is not None:
            pos3 = jnp.broadcast_to(posb[None], (3, B, 1))
            q = layers.apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = layers.apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = layers.apply_rope(q, posb, cfg.rope_theta)
            k = layers.apply_rope(k, posb, cfg.rope_theta)
    kT, vT = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)      # [B,Hk,1,Dh]

    # length-sharded cache (kv heads don't divide tp): flash-decoding path
    from repro.dist import act_sharding as _act

    state = _act.current_state()
    if state is not None and state[1].tp is not None:
        mesh, rules, _ = state
        ntp = mesh.shape[rules.tp]
        L_ = k_cache.shape[2]
        if cfg.n_kv_heads % ntp != 0 and L_ % ntp == 0:
            out, (kc, vc) = _decode_flash_lsharded(
                cfg, mesh, rules, jnp.swapaxes(q, 1, 2), kT, vT,
                k_cache, v_cache, pos, window,
            )
            return out @ params["wo"].astype(x.dtype), (kc, vc)

    # scatter the new token into the cache at pos (per-batch dynamic index)
    oh = jax.nn.one_hot(pos, k_cache.shape[2], dtype=k_cache.dtype)  # [B,L]
    k_cache = k_cache * (1 - oh[:, None, :, None]) + oh[:, None, :, None] * kT
    v_cache = v_cache * (1 - oh[:, None, :, None]) + oh[:, None, :, None] * vT

    L = k_cache.shape[2]
    qh = jnp.swapaxes(q, 1, 2)                                 # [B,Hq,1,Dh]
    Hk = cfg.n_kv_heads
    g = cfg.n_heads // Hk
    qg = qh.reshape(B, Hk, g, 1, cfg.d_head).astype(jnp.float32)
    s = shard_act(
        jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache.astype(jnp.float32)),
        ("batch", "model", None, None, None),
    )
    s = s * (cfg.d_head ** -0.5)
    if cfg.attn_softcap > 0.0:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    col = jnp.arange(L)[None, :]
    valid = col <= posb                                        # [B,L]
    if window is not None:
        valid &= col > posb - window
    s = jnp.where(valid[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_cache.astype(jnp.float32))
    out = out.reshape(B, Hk * g, 1, cfg.d_head).astype(x.dtype)
    out = jnp.swapaxes(out, 1, 2).reshape(B, 1, cfg.n_heads * cfg.d_head)
    return out @ params["wo"].astype(x.dtype), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# flash-decoding over a length-sharded KV cache (beyond-paper optimization)
# ---------------------------------------------------------------------------

def _decode_flash_lsharded(cfg, mesh, rules, q, kT, vT, k_cache, v_cache,
                           pos, window):
    """Decode attention with the cache sharded on its LENGTH axis.

    GSPMD's default plan all-gathers the whole KV cache every token (~GB/s
    per step, measured); instead each model-column computes an
    *unnormalized* partial softmax over its own length shard and the shards
    are merged with a log-sum-exp combine over gathered per-shard stats —
    bytes moved per layer drop from O(Hk x L x Dh) to O(Hq x Dh x ntp).

    q: [B, Hq, 1, Dh]; kT/vT: [B, Hk, 1, Dh]; caches [B, Hk, L, Dh].
    Returns (out [B, 1, Hq*Dh] replicated over tp, new caches).
    """
    from jax.sharding import PartitionSpec as P

    tp = rules.tp
    B = q.shape[0]
    Hk, L = k_cache.shape[1], k_cache.shape[2]
    g = cfg.n_heads // Hk
    scale = cfg.d_head ** -0.5
    softcap = cfg.attn_softcap

    # batch axes that divide B (long_500k: B=1 -> replicated)
    baxes = []
    prod = 1
    for a in rules.batch:
        if a in mesh.axis_names and B % (prod * mesh.shape[a]) == 0:
            baxes.append(a)
            prod *= mesh.shape[a]
    bspec = tuple(baxes) if len(baxes) > 1 else (baxes[0] if baxes else None)

    def local(q, kT, vT, kc, vc, pos):
        b_loc = q.shape[0]                                      # B / |batch axes|
        l_loc = kc.shape[2]
        col0 = jax.lax.axis_index(tp) * l_loc
        idx = pos - col0                                        # [B_loc]
        mine = (idx >= 0) & (idx < l_loc)
        oh = jnp.where(
            mine[:, None],
            jax.nn.one_hot(jnp.clip(idx, 0, l_loc - 1), l_loc,
                           dtype=kc.dtype),
            0,
        )                                                       # [B, l_loc]
        kc = kc * (1 - oh[:, None, :, None]) + oh[:, None, :, None] * kT
        vc = vc * (1 - oh[:, None, :, None]) + oh[:, None, :, None] * vT

        qg = q.reshape(b_loc, Hk, g, 1, cfg.d_head).astype(jnp.float32)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                       kc.astype(jnp.float32)) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        col = col0 + jnp.arange(l_loc)[None, :]
        valid = col <= pos[:, None]
        if window is not None:
            valid &= col > pos[:, None] - window
        s = jnp.where(valid[:, None, None, None], s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)              # [B,Hk,g,1,1]
        p = jnp.where(s > -5e29, jnp.exp(s - m_loc), 0.0)
        l_sum = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))

        # merge shards: tiny stat exchange instead of a KV all-gather
        m_all = jax.lax.all_gather(m_loc, tp)                   # [ntp,...]
        l_all = jax.lax.all_gather(l_sum, tp)
        a_all = jax.lax.all_gather(acc, tp)
        m_g = jnp.max(m_all, axis=0)
        w = jnp.exp(m_all - m_g[None])
        out = jnp.sum(a_all * w, axis=0) / jnp.maximum(
            jnp.sum(l_all * w, axis=0), 1e-30
        )
        out = out.reshape(b_loc, Hk * g, 1, cfg.d_head)
        return out.astype(kT.dtype), kc, vc

    # `out` IS replicated over tp (every shard computes the same merge from
    # the gathered stats) — check_vma=False disables the static replication
    # checker, which can't see that
    out, kc, vc = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
            P(bspec, None, tp, None),
            P(bspec, None, tp, None),
            P(bspec),
        ),
        out_specs=(
            P(bspec, None, None, None),
            P(bspec, None, tp, None),
            P(bspec, None, tp, None),
        ),
        check_vma=False,
    )(q, kT, vT, k_cache, v_cache, pos)
    out = jnp.swapaxes(out, 1, 2).reshape(B, 1, cfg.n_heads * cfg.d_head)
    return out, (kc, vc)
