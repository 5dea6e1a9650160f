"""Runs of a cell on the CPU at a small size with the timed path broken
underneath, for ``test_bench_faults.py``.  Each fault is planted in the
program, not the harness:

* ``state_unchanged``: every event step returns its state unchanged but
  for the step counter;
* ``half_batch``: each chunk's fold takes half of its rows;
* ``answer_altered``: the first task's finish time is 1% late in every
  answer.
"""
from __future__ import annotations

import contextlib
import time


def small(bench, cell_name):
    """The cell's configuration and mix at the size its family's CPU tests
    run at."""
    from bench.harness import spec

    cell = spec.cell(bench, cell_name)
    cfg = spec.config(bench, cell["config"])
    return spec.family(cfg).small(cfg, spec.traffic(cell["traffic"]))


@contextlib.contextmanager
def planted(fault: str):
    import jax
    import jax.numpy as jnp

    from repro.core import campaign, engine, step

    saved = []

    def patch(mod, name, fn):
        saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, fn)

    if fault == "state_unchanged":
        one, batch = engine.event_step, step.batch_event_step

        def frozen(scn, carry, ctx):
            _, ev = one(scn, carry, ctx)
            st, aux = carry
            return (st.replace(step=st.step + 1), aux), ev

        def frozen_batch(scn_b, carry, ctx, extras, max_steps):
            _, ev, live = batch(scn_b, carry, ctx, extras, max_steps)
            st, aux = carry
            return (st.replace(step=st.step + live.astype(jnp.int32)), aux), ev, live

        patch(engine, "event_step", frozen)
        patch(step, "batch_event_step", frozen_batch)
    elif fault == "half_batch":
        fold = campaign._run_chunk_fold

        def half_fold(leaves, bounds, carries, treedef, reducers, mesh, axis):
            keep = leaves[0].shape[0] // 2
            bounds = bounds.at[1].set(jnp.minimum(bounds[1], bounds[0] + keep))
            return fold(leaves, bounds, carries, treedef, reducers, mesh, axis)

        patch(campaign, "_run_chunk_fold", half_fold)
    elif fault == "answer_altered":
        fin = engine.finalize_result

        def altered(scn, st):
            return fin(scn, st.replace(finish_t=st.finish_t.at[0].multiply(1.01)))

        patch(engine, "finalize_result", altered)
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        for mod, name, fn in reversed(saved):
            setattr(mod, name, fn)
        jax.clear_caches()


def run(cell_name: str, fault: str, seed: int = 2**31 + 7) -> dict:
    from bench.harness import cell, spec

    bench = spec.load()
    cfg, mix = small(bench, cell_name)
    with planted(fault):
        result, _ = cell.run(bench, cell_name, seed, 2.0, False,
                             time.perf_counter(), config=cfg, mix=mix)
    return result

