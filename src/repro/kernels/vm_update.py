"""Pallas TPU kernel for the simulator's advance sweep (``vm_update``).

The hot loop of the tensorized CloudSim engine is, per event and per
scenario row:

    dt      = min( min_i  rem_i / rate_i  over active i,  bound )
    rem_i  -= rate_i * dt

The batch-major engine (core/step.py) calls this on a ``[B, C]`` block —
one row per live scenario — so the kernel is a **batch grid**: grid step
``g`` (``pl.program_id(0)``) owns scenario rows ``8g .. 8g+7`` (one f32
sublane tile; the batch is padded to a multiple of 8) with their whole
cloudlet tiles resident in VMEM, computes each row's min-reduction AND
applies the depletion in one pass, and emits the rows' ``dt`` into an SMEM
vector.
Fusing the two phases removes the reduce/re-stream round trip that made the
old two-phase kernel lose to jnp: each element is read exactly once.

Rows longer than one tile fall back to a per-row two-phase sub-grid
``(B/8, 2, nb)`` (phase 0 min-reduces across the rows' ``nb`` tiles into
VMEM scratch, phase 1 re-streams and applies) — same math, one extra pass, only
ever taken when a row exceeds the resolver's tile cap (kernels/ops.py picks
the tile: next-pow2 of the row length, floor 128, capped).

Rank-1 inputs (a single scenario) are the degenerate ``B=1`` batch (one
live row of eight) and return scalars, so one kernel serves both engine
paths.

Adaptation note (DESIGN.md §2): CloudSim walks Java object lists here; the
TPU-native form is this dense masked sweep — entity count scales with VMEM
bandwidth, not scheduler overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = 3.0e38

# f32 sublanes per vreg: Mosaic needs a block's second-minor dimension to be
# a multiple of 8, so every grid step owns eight scenario rows (the batch is
# padded up to a multiple of 8; rank-1 inputs are one live row of eight).
ROWS = 8


def kernel_plan(b: int, c: int, block: int) -> dict:
    """Static launch geometry for ``advance_sweep_pallas`` — the single
    source of truth for grid, tile and memory-space declarations.

    ``advance_sweep_pallas`` builds its ``pallas_call`` from this plan, and
    simlint rule R6 audits the same plan (block within the
    ``ops.advance_block`` heuristic bounds, ``(8, block)`` tiles, ``[B]``
    SMEM operands scalar per scenario row) without instantiating the
    kernel — so the audited geometry can never drift from the launched one.
    """
    pad = (-c) % block
    nb = (c + pad) // block
    padded_b = b + (-b) % ROWS
    g = padded_b // ROWS
    plan = {
        "b": b,
        "c": c,
        "block": block,
        "padded_b": padded_b,
        "padded_c": c + pad,
        "nb": nb,
        "variant": "fused" if nb == 1 else "two_phase",
        "grid": (g,) if nb == 1 else (g, 2, nb),
        "tile": (ROWS, block),
        # SMEM-resident [B] vectors: one scalar per scenario row
        "smem_in": (("bound_dt", (padded_b,)),),
        "smem_out": (("dt", (padded_b,)),),
        # two-phase: running per-row minimum across a row's tiles
        "vmem_scratch": () if nb == 1 else (("min_sc", (ROWS, 1)),),
    }
    return plan


def _row_ids():
    return jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)


def _load_rows(ref, base):
    """``[ROWS, 1]`` column of the SMEM scalars ``ref[base:base + ROWS]``."""
    rows = _row_ids()
    col = jnp.zeros((ROWS, 1), jnp.float32)
    for r in range(ROWS):
        col = jnp.where(rows == r, ref[base + r], col)
    return col


def _store_rows(ref, base, col):
    """Scatter a ``[ROWS, 1]`` column into the SMEM scalars of its rows."""
    rows = _row_ids()
    for r in range(ROWS):
        ref[base + r] = jnp.min(jnp.where(rows == r, col, _INF))


def _row_min(rem_ref, rate_ref, active_ref):
    """Per-row time to the first completion in this tile, ``[ROWS, 1]``."""
    rate = rate_ref[...]
    act = (active_ref[...] > 0.5) & (rate > 0)
    per = jnp.where(act, rem_ref[...] / jnp.maximum(rate, 1e-30), _INF)
    return jnp.min(per, axis=1, keepdims=True)


def _deplete(rem_ref, rate_ref, active_ref, dt):
    rem = rem_ref[...]
    return jnp.where(active_ref[...] > 0.5,
                     jnp.maximum(rem - rate_ref[...] * dt, 0.0), rem)


def _fused_kernel(rem_ref, rate_ref, active_ref, bound_ref,
                  dt_ref, out_ref):
    """One grid step == eight scenario rows, whole cloudlet tile resident."""
    base = pl.program_id(0) * ROWS
    dt = jnp.minimum(_row_min(rem_ref, rate_ref, active_ref),
                     _load_rows(bound_ref, base))
    out_ref[...] = _deplete(rem_ref, rate_ref, active_ref, dt)
    _store_rows(dt_ref, base, dt)


def _tiled_kernel(rem_ref, rate_ref, active_ref, bound_ref,
                  dt_ref, out_ref, min_sc):
    """Fallback for rows longer than one tile: per-row two-phase sweep."""
    base = pl.program_id(0) * ROWS
    phase = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        min_sc[...] = _load_rows(bound_ref, base)

    @pl.when(phase == 0)
    def _reduce():
        min_sc[...] = jnp.minimum(
            min_sc[...], _row_min(rem_ref, rate_ref, active_ref)
        )

    @pl.when(phase == 1)
    def _apply():
        dt = min_sc[...]
        out_ref[...] = _deplete(rem_ref, rate_ref, active_ref, dt)

        @pl.when(j == nb - 1)
        def _emit():
            _store_rows(dt_ref, base, dt)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def advance_sweep_pallas(
    rem: Array,
    rate: Array,
    active: Array,
    bound_dt: Array,
    *,
    block: int = 1024,
    interpret: bool = True,
) -> tuple[Array, Array]:
    """Fused min-reduce + depletion.

    Batch-major: rem/rate/active ``[B, C]``, bound_dt ``[B]`` ->
    ``(dt [B], rem' [B, C])``.  Rank-1 ``[C]`` inputs with a scalar bound are
    the ``B=1`` special case and return ``(dt scalar, rem' [C])``.
    """
    squeeze = rem.ndim == 1
    out_dtype = rem.dtype
    if squeeze:
        rem, rate, active = rem[None, :], rate[None, :], active[None, :]
    b, c = rem.shape
    plan = kernel_plan(b, c, block)
    # padded rows and columns are inactive: they never bound a row's dt
    zpad = ((0, plan["padded_b"] - b), (0, plan["padded_c"] - c))
    remp = jnp.pad(rem.astype(jnp.float32), zpad)
    ratep = jnp.pad(rate.astype(jnp.float32), zpad)
    actp = jnp.pad(active.astype(jnp.float32), zpad)
    bound = jnp.pad(jnp.reshape(bound_dt.astype(jnp.float32), (b,)),
                    (0, plan["padded_b"] - b))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [
        jax.ShapeDtypeStruct(plan["smem_out"][0][1], jnp.float32),
        jax.ShapeDtypeStruct(remp.shape, jnp.float32),
    ]
    if plan["variant"] == "fused":
        # one resident tile per row group: single-pass fused kernel
        tile = pl.BlockSpec(plan["tile"], lambda i: (i, 0))
        kernel, scratch = _fused_kernel, []
    else:
        tile = pl.BlockSpec(plan["tile"], lambda i, p, j: (i, j))
        kernel = _tiled_kernel
        scratch = [pltpu.VMEM(shape, jnp.float32)
                   for _, shape in plan["vmem_scratch"]]
    dt, new_rem = pl.pallas_call(
        kernel,
        grid=plan["grid"],
        in_specs=[tile, tile, tile, smem],
        out_specs=[smem, tile],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(remp, ratep, actp, bound)
    dt, new_rem = dt[:b], new_rem[:b, :c].astype(out_dtype)
    if squeeze:
        return dt[0], new_rem[0]
    return dt, new_rem
