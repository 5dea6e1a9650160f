"""Event-engine throughput: batch-major step loop vs vmap-of-simulate.

The tentpole metric of the batch-major refactor (DESIGN.md §10): one
compiled step advances a ``[B, ...]`` campaign natively, so the expensive
event phases (the sequential VM-provisioning scan, the broker dispatch
sort) run under *scalar* ``lax.cond``s on batch-global predicates and are
genuinely skipped when no live row needs them — whereas ``vmap(simulate)``
turns the same conds into ``select``s and pays every phase at every event.

    PYTHONPATH=src python -m benchmarks.event_engine

Writes ``BENCH_event_engine.json``:

* ``event_engine_single.{jnp,pallas}.events_per_s`` — one scenario through
  ``simulate`` under both advance-sweep routings.
* ``event_engine_batch.{batch_major,vmap}.batch_events_per_s`` — the same
  scenario x B=256 (staggered task lengths) through the batch-major path
  vs ``jit(vmap(simulate))``, plus their speedup and a bitwise-equality
  seat (the batch path must be a perf optimization, not a semantic fork).
* ``advance_pow2.{jnp,pallas}`` — the fused advance kernel at an exact
  power-of-two row, where interpret mode pays no padding copies; on CPU
  this is the honest kernel comparison (DESIGN.md §10 caveat), the
  c=100k row lives in BENCH_engine.json.

The benchmark scenario is deliberately provisioning-heavy (few cloudlets,
a large host table): per event the policy/bound/commit work is tens of
small ops while one provisioning pass scans V VMs over [D, H] host tables,
and only the first event has VMs to place — the regime the paper's
Figure 7/8 instantiation experiments model, and the one where batch-major
phase skipping pays.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import simulate, simulate_instrumented, stack_scenarios
from repro.core.entities import SPACE_SHARED, Scenario
from repro.core.scenarios import (
    make_cloudlets,
    make_policy,
    uniform_hosts,
    uniform_market,
    uniform_vms,
)
from repro.kernels import ops

OUT_PATH = "BENCH_event_engine.json"
BATCH = 256


def _time(fn, *args, n_rep: int = 3) -> float:
    out = fn(*args)                                # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n_rep):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_rep


def bench_scenario(mi_scale: float = 1.0, n_vms: int = 50,
                   n_hosts: int = 8_000, n_waves: int = 8,
                   sweep_impl: str = "jnp") -> Scenario:
    """Provisioning-heavy event stream: ``n_vms`` VMs requested at t=0
    (one placement event scanning a 1 x ``n_hosts`` table), then
    ``n_waves`` single-cloudlet submission waves 100 s apart — ~2 events
    per wave, none of which has provisioning or dispatch work."""
    hosts = uniform_hosts(1, n_hosts, cores=1, mips=1000.0)
    vms = uniform_vms(n_vms, ram_mb=128.0)
    cl_vm = np.arange(n_waves) % n_vms
    submit = np.arange(n_waves) * 100.0
    cls = make_cloudlets(cl_vm, np.full(n_waves, 30_000.0 * mi_scale), submit)
    pol = make_policy(host_policy=SPACE_SHARED, vm_policy=SPACE_SHARED,
                      core_reserving=True)
    return Scenario(hosts=hosts, vms=vms, cloudlets=cls,
                    market=uniform_market(1), policy=pol,
                    sweep_impl=sweep_impl)


def bench_single(n_rep: int = 3) -> dict:
    rows = {}
    for impl in ("jnp", "pallas"):
        scn = bench_scenario(sweep_impl=impl)
        fn = jax.jit(simulate)
        wall = _time(fn, scn, n_rep=n_rep)
        res = fn(scn)
        n_events = int(res.n_events)
        rows[impl] = {
            "wall_s": wall,
            "n_events": n_events,
            "events_per_s": n_events / wall,
            "n_finished": int(res.n_finished),
        }
    return rows


def batch_stack(b: int = BATCH) -> Scenario:
    """``b`` rows of the benchmark scenario with staggered task lengths."""
    return stack_scenarios([bench_scenario(1.0 + 0.002 * i) for i in range(b)])


def vmap_simulate(scn_b: Scenario):
    """The baseline the batch-major refactor replaces: the campaign axis in
    an outer vmap, which turns every phase cond into a select."""
    return jax.vmap(lambda s: simulate_instrumented(s)[0])(scn_b)


def bitwise_equal(a, b) -> bool:
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def bench_batch(b: int = BATCH) -> dict:
    scn_b = batch_stack(b)

    # rank detection routes the stacked pytree through the batch-major loop
    run_batch = jax.jit(simulate)
    run_vmap = jax.jit(vmap_simulate)

    res_b = run_batch(scn_b)
    n_events = int(np.asarray(res_b.n_events).sum())
    wall_b = _time(run_batch, scn_b, n_rep=2)
    res_v = run_vmap(scn_b)
    wall_v = _time(run_vmap, scn_b, n_rep=1)

    bitwise = bitwise_equal(res_b, res_v)
    return {
        "batch_size": b,
        "n_events": n_events,
        "batch_major": {
            "wall_s": wall_b,
            "batch_events_per_s": n_events / wall_b,
        },
        "vmap": {
            "wall_s": wall_v,
            "batch_events_per_s": n_events / wall_v,
        },
        "speedup_batch_vs_vmap": wall_v / wall_b,
        "bitwise_equal": bitwise,
    }


def bench_advance_pow2(c: int = 1 << 17, n_rep: int = 5) -> dict:
    """The fused kernel with zero interpret-mode padding overhead."""
    rng = np.random.default_rng(0)
    rem = jnp.asarray(rng.uniform(1e3, 1e6, c).astype(np.float32))
    rate = jnp.asarray(rng.uniform(0.0, 1e3, c).astype(np.float32))
    active = rate > 1.0
    bound = jnp.asarray(1e4, jnp.float32)

    rows = {}
    for impl in ("jnp", "pallas"):
        fn = jax.jit(ops.resolve_advance(impl))
        wall = _time(fn, rem, rate, active, bound, n_rep=n_rep)
        rows[impl] = {"wall_s": wall, "cloudlets": c,
                      "cloudlets_per_s": c / wall}
    return rows


def run() -> dict:
    report = {
        "backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() != "tpu",
        "event_engine_single": bench_single(),
        "event_engine_batch": bench_batch(),
        "advance_pow2": bench_advance_pow2(),
    }
    if not report["event_engine_batch"]["bitwise_equal"]:
        raise AssertionError(
            "batch-major SimResult diverged bitwise from vmap-of-simulate"
        )
    return report


def main() -> None:
    report = run()
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {OUT_PATH}")
    for impl, row in report["event_engine_single"].items():
        print(f"event_engine_single,{impl},events_per_s={row['events_per_s']:.6g}")
    batch = report["event_engine_batch"]
    for impl in ("batch_major", "vmap"):
        print(f"event_engine_batch,{impl},"
              f"batch_events_per_s={batch[impl]['batch_events_per_s']:.6g}")
    print(f"event_engine_batch,speedup,"
          f"{batch['speedup_batch_vs_vmap']:.3g}x,"
          f"bitwise_equal={batch['bitwise_equal']}")
    for impl, row in report["advance_pow2"].items():
        print(f"advance_pow2,{impl},cloudlets_per_s={row['cloudlets_per_s']:.6g}")


if __name__ == "__main__":
    main()
