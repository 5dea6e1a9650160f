"""Jitted public wrappers for the kernel layer.

Routing policy:
  * On TPU (the target) ``interpret=False`` compiles to Mosaic.
  * On CPU the Pallas kernels run in ``interpret=True`` — bit-faithful to
    the kernel body, executed in Python, used by tests.
  * Any other backend has neither Mosaic nor a place in the tests: the
    Pallas wrappers raise there rather than interpret in silence.
  * The models/engine default to the pure-jnp reference implementations
    (ref.py), which XLA fuses well and which lower on any backend; the
    Pallas path is selected via config (``attn_impl="pallas"`` etc.).
"""
from __future__ import annotations

import jax
from jax import Array

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.vm_update import advance_sweep_pallas


def _interpret() -> bool:
    """Compile to Mosaic on a TPU, interpret on the CPU, refuse elsewhere."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "Pallas kernels compile only for a TPU (Mosaic) and interpret only "
        f"on the CPU; the default backend is {backend!r}"
    )


# Longest row tile the fused advance kernel keeps resident: one (8, 2**15)
# f32 tile is 1 MiB, so the four streamed operands, double-buffered, take
# 8 MiB — inside v5e's 16 MiB default scoped VMEM (2**16 overflows it in the
# two-phase variant).  Rows longer than this take the per-row two-phase
# sub-grid.
_MAX_BLOCK = 1 << 15


def advance_block(n_cloudlets: int) -> int:
    """Tile-size heuristic for the advance kernel: the next power of two
    covering the row (floor 128 — the TPU lane width — so tiny Fig-9/10-scale
    scenarios stop paying full-tile overhead), capped at ``_MAX_BLOCK``.
    Whenever the cap is not hit the whole row fits one tile and the kernel
    takes its fused single-pass path."""
    block = 128
    while block < n_cloudlets and block < _MAX_BLOCK:
        block *= 2
    return block


def advance_sweep(rem: Array, rate: Array, active: Array, bound_dt: Array):
    """Engine advance sweep — Pallas twin of ref.advance_sweep_ref.

    Rank-polymorphic like the reference: ``[C]`` per-scenario rows or
    batch-major ``[B, C]`` blocks (the kernel grids over scenario rows
    either way; rank-1 is the B=1 degenerate case).
    """
    return advance_sweep_pallas(
        rem, rate, active, bound_dt,
        block=advance_block(rem.shape[-1]),
        interpret=_interpret(),
    )


def resolve_advance(impl: str):
    """The single advance-sweep routing point (core.step.resolve_advance
    defers here): ``"jnp"`` -> the fusable reference, ``"pallas"`` -> the
    fused batch-grid Mosaic kernel (interpret mode on the CPU).  Both
    implementations pick batch-major vs per-scenario by input rank."""
    if impl == "pallas":
        return advance_sweep
    if impl == "jnp":
        return ref.advance_sweep_ref
    raise ValueError(
        f"unknown sweep_impl {impl!r}: expected 'jnp' or 'pallas'"
    )


def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True, window: int | None = None,
    softcap: float = 0.0, scale: float | None = None,
) -> Array:
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        interpret=_interpret(),
    )


def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128) -> Array:
    return ssd_scan_pallas(
        x, dt, A, Bm, Cm, D, chunk=chunk, interpret=_interpret()
    )


# re-exported oracles (also the default production path on CPU)
attention_ref = ref.attention_ref
ssd_ref = ref.ssd_ref
ssd_chunked_ref = ref.ssd_chunked_ref
advance_sweep_ref = ref.advance_sweep_ref
