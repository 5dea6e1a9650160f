"""Simulation campaigns: batch-major sweeps, sharded chunks, streaming folds.

What cloud researchers actually run with CloudSim is not one simulation but
*sweeps* — policy x seed x workload grids.  Because the engine is a pure
function with traced policy/workload values and static shapes, a campaign is
``simulate`` on the stacked scenario pytree — the batch-major step loop
advances every row natively, with batch-global phase skipping and early-exit
masking (DESIGN.md §10).  This module turns that kernel into a
million-scenario product (DESIGN.md §12):

* ``run_campaign(batched, chunk_size=...)`` — slice the campaign axis into
  fixed-size chunks through ONE compiled program (trailing chunk padded by
  repeating the last row, then trimmed/masked), donating each chunk's
  output-aliasable buffers so working memory is bounded by one chunk.  The
  host work around it is compiled too: a warm sweep issues a fixed number
  of dispatches however many leaves the scenario has.
* ``run_campaign(..., mesh=...)`` — shard each chunk's campaign axis across
  ``mesh[axis]`` via ``shard_map`` (PartitionSpecs from
  ``dist.sharding.campaign_pspec_tree``): shards simulate their rows fully
  locally, so the collective term of this workload is exactly zero and a
  256-device mesh evaluates 256 sub-campaigns concurrently.
* ``run_campaign(..., reduce=...)`` — fold each chunk's ``SimResult`` into
  fixed-shape ``CampaignReducer`` carries *inside the compiled chunk
  program*: the ``[N, ...]`` result pytree is never materialized, so sweep
  size is bounded by wall clock, not memory (core/reducers.py).

``core/search.py`` drives these three together: successive-halving over
policy grids where every rung re-enters the same compiled chunk program.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.engine import simulate
from repro.core.entities import Scenario, SimResult
from repro.core.reducers import CampaignReducer
from repro.core.step import SCOPE_FOLD

# Host spans (``jax.profiler.TraceAnnotation``) of a streaming sweep, on the
# device trace's clock: they say what the host was doing while the chip
# idled between chunk programs.  They record only while the profiler is on.
SPAN_PREPARE = "campaign_prepare"    # once: the shape plan (cached), carries
SPAN_SLICE = "campaign_slice"        # per chunk: the slice program
SPAN_PUT = "campaign_put"            # per chunk, with a mesh: device_put
SPAN_LAUNCH = "campaign_launch"      # per chunk: dispatch of the fold program
SPAN_FINALIZE = "campaign_finalize"  # once: the finalize program
HOST_SPANS = (SPAN_PREPARE, SPAN_SLICE, SPAN_PUT, SPAN_LAUNCH, SPAN_FINALIZE)


def stack_scenarios(scenarios: list[Scenario]) -> Scenario:
    """Stack same-shape scenarios along a new leading campaign axis.

    Static fields (``max_steps``, ``sweep_impl``) are jit-cache metadata, not
    arrays: they cannot vary across one campaign, so disagreement is an error
    (it used to silently keep the first scenario's values).
    """
    if not scenarios:
        raise ValueError("empty campaign")
    ref = scenarios[0]
    for i, scn in enumerate(scenarios[1:], start=1):
        for field in ("max_steps", "sweep_impl"):
            a, b = getattr(ref, field), getattr(scn, field)
            if a != b:
                raise ValueError(
                    f"stack_scenarios: scenario {i} has {field}={b!r} but "
                    f"scenario 0 has {a!r}; static fields must agree across "
                    "a campaign (split into per-value campaigns or set them "
                    "uniformly)"
                )
    ref_treedef = jax.tree.structure(ref)
    for i, scn in enumerate(scenarios[1:], start=1):
        td = jax.tree.structure(scn)
        if td != ref_treedef:
            raise ValueError(
                f"stack_scenarios: scenario {i} has pytree structure {td} "
                f"but scenario 0 has {ref_treedef}; power/topology/instrument "
                "attachments must agree across a campaign"
            )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)


def _campaign_len(batched: Scenario) -> int:
    return jax.tree.leaves(batched)[0].shape[0]


def broadcast_campaign(template: Scenario, n: int, **overrides) -> Scenario:
    """Broadcast one Scenario to an ``n``-point campaign, substituting the
    batched subtrees that actually vary.

    The grid builder for generated workloads: infrastructure/market leaves
    broadcast to a leading campaign axis; vmapped-generated ``cloudlets=``
    and swept ``policy=`` pytrees (leading axis ``n``) replace their
    broadcast counterparts.  Static fields pass through untouched, so the
    result feeds straight into ``run_campaign`` — e.g. a 64-point
    arrival-rate x scale-threshold sweep in one vmap:

        keys = jax.random.split(key, 64)
        cls = jax.vmap(lambda k, r: workload.generate_cloudlets(k, C, rate=r)
                       )(keys, rates)
        pol = jax.vmap(lambda u: template.policy.replace(scale_up_thresh=u)
                       )(threshs)
        res = run_campaign(broadcast_campaign(template, 64,
                                              cloudlets=cls, policy=pol))
    """
    batched = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), template
    )
    for name, sub in overrides.items():
        for leaf in jax.tree.leaves(sub):
            if jnp.ndim(leaf) == 0 or jnp.shape(leaf)[0] != n:
                raise ValueError(
                    f"broadcast_campaign: override {name!r} has a leaf of "
                    f"shape {jnp.shape(leaf)}; every leaf needs leading dim "
                    f"{n} (vmap the builder over the campaign axis)"
                )
    return batched.replace(**overrides)


# `simulate` detects the stacked campaign axis by rank and runs the
# batch-major step loop (engine.is_batched): the campaign dimension lives
# inside the compiled program, not in an outer vmap, so the expensive event
# phases skip on batch-global predicates (DESIGN.md §10).
_run_whole = jax.jit(simulate)


def _sharded_simulate(chunk: Scenario, mesh, axis: str) -> SimResult:
    """``simulate`` with the chunk's campaign axis shard_mapped over
    ``mesh[axis]``.

    In-specs come from the ``dist.sharding`` campaign rule
    (``campaign_pspec_tree``): leading axis on ``mesh[axis]``, everything
    else replicated.  Each shard's sub-campaign keeps its leading rank, so
    ``engine.is_batched`` still routes it through the batch-major step —
    per-shard results are bitwise those of the unsharded run.  Replication
    checking is off (``check_vma=False``): the while-loop carry mixes varying
    per-row state with scalars the static checker cannot prove replicated.
    """
    from repro.dist.sharding import campaign_pspec_tree

    in_tree = campaign_pspec_tree(chunk, mesh, axis)
    pspec = jax.sharding.PartitionSpec
    specs = jax.tree.leaves(in_tree, is_leaf=lambda x: isinstance(x, pspec))
    if any(s and s[0] is None for s in specs):
        n = _campaign_len(chunk)
        raise ValueError(
            f"campaign axis of {n} rows is not divisible by mesh axis "
            f"{axis!r} (size {dict(mesh.shape)[axis]}); pick a chunk_size "
            "that divides"
        )
    run = jax.shard_map(
        simulate, mesh=mesh, in_specs=(in_tree,), out_specs=pspec(axis),
        check_vma=False,
    )
    return run(chunk)


def _sim_fn(mesh, axis: str):
    if mesh is None:
        return simulate
    return lambda chunk: _sharded_simulate(chunk, mesh, axis)


@partial(jax.jit, static_argnums=(1, 2))
def _run_whole_sharded(batched: Scenario, mesh, axis: str) -> SimResult:
    return _sharded_simulate(batched, mesh, axis)


# --------------------------------------------------------------------------
# chunk slicing: one compiled program per chunk, not one eager op per leaf
#
# Eager indexing dispatches an op per leaf per chunk, and on the chip each
# costs far more host time than the copy it asks for.  The slice program
# takes every leaf and the chunk start as a traced i32, so every full chunk
# of a campaign shares one executable; only the trailing short chunk pads,
# and whether it does is decided on the host, so a (grid shape, chunk) pair
# has at most two.  The grid is not donated: callers sweep it again.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(2, 3))
def _slice_program(leaves, lo, chunk: int, pad: bool):
    n = leaves[0].shape[0]
    if pad:
        # clipped rows repeat the last row bit for bit
        rows = lo + jnp.arange(chunk, dtype=jnp.int32)
        part = tuple(jnp.take(x, rows, axis=0, mode="clip") for x in leaves)
    else:
        part = tuple(lax.dynamic_slice_in_dim(x, lo, chunk) for x in leaves)
    return part, jnp.asarray([lo, n], jnp.int32)


def _slice_chunk(leaves, lo: int, chunk: int):
    """Rows ``[lo, lo + chunk)`` of every campaign leaf, the trailing short
    chunk padded by repeating the last row, and the fold's ``(lo, n)``
    bounds as an i32[2] on the device."""
    n = leaves[0].shape[0]
    return _slice_program(tuple(leaves), lo, chunk, lo + chunk > n)


def _avals(leaves) -> tuple:
    return tuple((l.shape, l.dtype) for l in leaves)


# --------------------------------------------------------------------------
# chunked execution with *effective* buffer donation
#
# Donating the whole Scenario pytree is a no-op that warns on every chunk
# ("Some donated buffers were not usable"): XLA can only reuse a donated
# input buffer for an output of identical shape/dtype, and most Scenario
# leaves have no SimResult counterpart.  So the chunk runner donates exactly
# the subset of leaves that CAN alias an output (matched by (shape, dtype)
# multiset against eval_shape of the result) and passes the rest undonated.
# tests/test_campaign.py promotes the donation UserWarning to an error, so a
# regression to wholesale donation fails loudly.
#
# The streaming runner (_run_chunk_fold) donates the reducer *carries*
# instead: its only outputs are the carries, which alias their input buffers
# exactly, while the scenario chunk has no output counterpart at all.
# --------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _donate_mask(treedef, avals: tuple) -> tuple[bool, ...]:
    """Per-leaf: may this input buffer alias some output buffer?"""
    chunk = jax.tree.unflatten(
        treedef, [jax.ShapeDtypeStruct(s, d) for s, d in avals]
    )
    out = jax.eval_shape(simulate, chunk)
    budget: dict = {}
    for leaf in jax.tree.leaves(out):
        key = (leaf.shape, leaf.dtype)
        budget[key] = budget.get(key, 0) + 1
    mask = []
    for s, d in avals:
        n = budget.get((s, d), 0)
        mask.append(n > 0)
        if n:
            budget[(s, d)] = n - 1
    return tuple(mask)


@partial(jax.jit, static_argnums=(2, 3, 4, 5), donate_argnums=(0,))
def _run_chunk_split(donated, kept, mask, treedef, mesh=None, axis="data"):
    it_d, it_k = iter(donated), iter(kept)
    leaves = [next(it_d) if m else next(it_k) for m in mask]
    return _sim_fn(mesh, axis)(jax.tree.unflatten(treedef, leaves))


def _split_chunk(chunk: Scenario):
    """(donated leaves, kept leaves, mask, treedef) for the chunk runner."""
    leaves, treedef = jax.tree.flatten(chunk)
    mask = _donate_mask(treedef, _avals(leaves))
    donated = tuple(l for l, m in zip(leaves, mask) if m)
    kept = tuple(l for l, m in zip(leaves, mask) if not m)
    return donated, kept, mask, treedef


def _run_chunk(chunk: Scenario, mesh=None, axis: str = "data") -> SimResult:
    donated, kept, mask, treedef = _split_chunk(chunk)
    return _run_chunk_split(donated, kept, mask, treedef, mesh, axis)


def lower_chunk(chunk: Scenario, mesh=None, axis: str = "data") -> tuple[str, int]:
    """AOT-compile one campaign chunk through the donating runner and return
    ``(optimized_hlo_text, n_donated)``.

    The HLO module header carries XLA's ``input_output_alias`` table; simlint
    rule R2 checks it covers every ``_donate_mask``-donatable leaf, catching
    the PR-2 "donation that never aliased" regression class statically —
    without running a campaign.  With ``mesh`` the chunk is lowered through
    the shard_map runner instead (the ``campaign_sharded`` lint entry).
    """
    donated, kept, mask, treedef = _split_chunk(chunk)
    compiled = _run_chunk_split.lower(
        donated, kept, mask, treedef, mesh, axis
    ).compile()
    return compiled.as_text(), sum(mask)


# --------------------------------------------------------------------------
# streaming reductions: fold chunk results into fixed-shape carries
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(2,))
def _run_chunk_fold(leaves, bounds, carries, treedef, reducers, mesh, axis):
    scn = jax.tree.unflatten(treedef, leaves)
    res = _sim_fn(mesh, axis)(scn)
    with jax.named_scope(SCOPE_FOLD):
        size = jax.tree.leaves(scn)[0].shape[0]
        index = bounds[0] + jnp.arange(size, dtype=jnp.int32)
        valid = index < bounds[1]
        return tuple(
            r.fold(c, scn, res, index, valid)
            for r, c in zip(reducers, carries)
        )


def _normalize_reduce(reduce):
    """-> (keys | None, tuple_of_reducers, single_flag)."""
    if isinstance(reduce, CampaignReducer):
        return None, (reduce,), True
    if isinstance(reduce, dict):
        for k, r in reduce.items():
            if not isinstance(r, CampaignReducer):
                raise TypeError(f"reduce[{k!r}] is not a CampaignReducer")
        return tuple(reduce), tuple(reduce.values()), False
    raise TypeError(
        f"reduce must be a CampaignReducer or a dict of them, got {reduce!r}"
    )


@dataclasses.dataclass(frozen=True, eq=False)
class _Plan:
    """What a reduced sweep needs that depends only on shapes.  Hashed by
    identity: ``_plan`` hands out one instance per key, so it rides through
    ``_init_carries`` as a static argument at no hashing cost."""

    chunk_avals: Scenario
    res_avals: SimResult
    reducers: tuple
    leaf_shardings: tuple | None   # with a mesh: each chunk leaf's sharding
    rep: object                    # with a mesh: replicated, for the carries


@lru_cache(maxsize=None)
def _plan(treedef, row_avals: tuple, chunk: int, reducers: tuple, mesh,
          axis: str) -> _Plan:
    """The sweep's shape plan: chunk avals, ``eval_shape`` of ``simulate``
    (a trace of the whole engine) and the mesh shardings, computed once per
    key.  ``row_avals`` drop the campaign axis, so every population size of
    one grid (the search driver's rungs) shares a plan."""
    chunk_avals = jax.tree.unflatten(treedef, [
        jax.ShapeDtypeStruct((chunk,) + s, d) for s, d in row_avals
    ])
    res_avals = jax.eval_shape(simulate, chunk_avals)
    # With a mesh, every fold input's sharding is pinned before each fold
    # call: otherwise arrays that flow back from a previous fold (search-
    # driver survivors, the carries themselves) arrive committed to mesh
    # shardings while fresh chunks arrive uncommitted, and the differing
    # shardings fork the jit cache per call — the exact hazard simlint R5
    # probes.
    leaf_shardings = rep = None
    if mesh is not None:
        from repro.dist.sharding import campaign_pspec_tree, named

        leaf_shardings = tuple(jax.tree.leaves(
            named(mesh, campaign_pspec_tree(chunk_avals, mesh, axis)),
            is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding),
        ))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return _Plan(chunk_avals, res_avals, reducers, leaf_shardings, rep)


@partial(jax.jit, static_argnums=0)
def _init_carries(plan: _Plan):
    # a fresh set of buffers on every call: the fold donates them
    return tuple(r.init(plan.chunk_avals, plan.res_avals)
                 for r in plan.reducers)


@partial(jax.jit, static_argnums=1)
def _finalize(carries, reducers):
    return tuple(r.finalize(c) for r, c in zip(reducers, carries))


def _run_reduced(batched: Scenario, chunk_size: int | None, reduce,
                 mesh, axis: str):
    span = jax.profiler.TraceAnnotation
    keys, reducers, single = _normalize_reduce(reduce)
    n = _campaign_len(batched)
    chunk = chunk_size or n

    with span(SPAN_PREPARE):
        leaves, treedef = jax.tree.flatten(batched)
        row_avals = tuple((s[1:], d) for s, d in _avals(leaves))
        plan = _plan(treedef, row_avals, chunk, reducers, mesh, axis)
        carries = _init_carries(plan)

    for lo in range(0, n, chunk):
        # (lo, n) ride as one traced i32[2] so every chunk — first, middle,
        # padded tail — reuses the same compiled fold program
        with span(SPAN_SLICE):
            part, bounds = _slice_chunk(leaves, lo, chunk)
        if mesh is not None:
            with span(SPAN_PUT):
                part = jax.device_put(part, plan.leaf_shardings)
                carries, bounds = jax.device_put((carries, bounds),
                                                 plan.rep)
        with span(SPAN_LAUNCH):
            carries = _run_chunk_fold(
                part, bounds, carries, treedef, reducers, mesh, axis
            )
    with span(SPAN_FINALIZE):
        outs = _finalize(carries, reducers)
    if keys is not None:
        return dict(zip(keys, outs))
    return outs[0] if single else outs


def run_campaign(
    batched: Scenario,
    chunk_size: int | None = None,
    donate: bool = False,
    reduce=None,
    mesh=None,
    axis: str = "data",
) -> SimResult:
    """Run a stacked campaign; the front door for every sweep size.

    ``chunk_size`` bounds working memory: the campaign axis is processed in
    fixed-size chunks through one compiled program (the trailing chunk is
    padded by repeating the last scenario, then trimmed), each chunk's
    output-aliasable input buffers donated to XLA.  ``donate=True`` applies
    the same donation to the unchunked local path — only safe when the
    caller is done with ``batched``.

    ``mesh`` shards every chunk's campaign axis over ``mesh[axis]`` via
    ``shard_map`` (specs from ``dist.sharding.campaign_pspec_tree``); the
    chunk size (or the whole campaign when unchunked) must be divisible by
    that mesh axis.  Shards never communicate — simulations are
    embarrassingly parallel — so this scales linearly until chunks starve.

    ``reduce`` (a ``CampaignReducer`` or dict of them, core/reducers.py)
    switches to streaming mode: each chunk's results fold into fixed-shape
    carries inside the compiled chunk program and only the finalized
    summary (dict mirroring ``reduce``) returns — the ``[N, ...]`` result
    pytree is never materialized, which is what makes 1e5–1e6-point sweeps
    memory-feasible (DESIGN.md §12).
    """
    if chunk_size is not None and chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    n = _campaign_len(batched)
    if mesh is not None:
        if axis not in dict(mesh.shape):
            raise ValueError(
                f"mesh has no axis {axis!r}; axes: {tuple(mesh.axis_names)}"
            )
        per = chunk_size or n
        if per % dict(mesh.shape)[axis]:
            raise ValueError(
                f"chunk of {per} rows is not divisible by mesh axis "
                f"{axis!r} (size {dict(mesh.shape)[axis]})"
            )
    if reduce is not None:
        return _run_reduced(batched, chunk_size, reduce, mesh, axis)
    if chunk_size is None:
        if mesh is None:
            return (_run_chunk if donate else _run_whole)(batched)
        from repro.dist.sharding import campaign_pspec_tree, named

        sharding = named(mesh, campaign_pspec_tree(batched, mesh, axis))
        batched = jax.device_put(batched, sharding)
        return _run_whole_sharded(batched, mesh, axis)
    leaves, treedef = jax.tree.flatten(batched)
    results = []
    for lo in range(0, n, chunk_size):
        part, _ = _slice_chunk(leaves, lo, chunk_size)
        # the chunk is a fresh temporary -> donating it is always safe
        chunk = jax.tree.unflatten(treedef, part)
        results.append(_run_chunk(chunk, mesh, axis))
    return jax.tree.map(lambda *xs: jnp.concatenate(xs)[:n], *results)


def run_campaign_sharded(batched: Scenario, mesh, axis: str = "data") -> SimResult:
    """Shard the campaign's leading axis across ``mesh[axis]``.

    Kept as the one-argument spelling of ``run_campaign(batched,
    mesh=mesh)``; see there.  Each device runs its slice of scenarios
    entirely locally; there is no cross-device communication inside a
    simulation, so the collective term of this workload's roofline is
    exactly zero.
    """
    return run_campaign(batched, mesh=mesh, axis=axis)
