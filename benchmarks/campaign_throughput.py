"""Campaign throughput at production scale: streaming + sharded sweeps.

The CloudSim companion paper (arXiv:0903.2525) benchmarks large-scale
instantiation; the equivalent claim here is end-to-end *sweep* throughput —
how many complete scenario simulations per second the campaign engine
sustains when the grid is too big to materialize.  Two modes:

* ``streaming`` — a >=1e5-point fig4 campaign through
  ``run_campaign(chunk_size=..., reduce=...)``: chunked batch-major
  simulation with the histogram/argbest/count folds fused into the compiled
  chunk program, so the ``[N, ...]`` result pytree never exists
  (DESIGN.md §12).  Peak memory is one chunk + the reducer carries.
* ``sharded`` — the same streaming sweep with chunks shard_mapped over every
  available device (``data`` mesh).  On CPU CI this is a 1-device mesh, so
  the number is the shard_map-lowering overhead check, not a scaling claim;
  the 4-device bitwise test lives in tests/test_campaign.py.

Both ``scenarios_per_s`` keys are gated against BENCH_baseline.json by
``check_regression.py`` (artifact: BENCH_campaign.json).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.core import broadcast_campaign, run_campaign, scenarios
from repro.core.reducers import (
    ArgBestReducer,
    HistogramReducer,
    SumReducer,
)

ARTIFACT = "BENCH_campaign.json"

N_STREAMING = 131_072      # the >=1e5-point acceptance sweep
N_SHARDED = 65_536
CHUNK = 8_192

REDUCE = {
    "events": SumReducer("n_events"),
    "turnaround": HistogramReducer("mean_turnaround", 0.0, 8000.0, bins=64),
    "best": ArgBestReducer("mean_turnaround"),
}


def fig4_grid(n: int):
    """n-point fig4 campaign with per-row workload scale (distinct rows,
    one compiled program)."""
    base = scenarios.fig4_scenario(0, 0)
    scale = 1.0 + 0.5 * jnp.arange(n, dtype=jnp.float32) / n
    cls = jax.vmap(
        lambda s: base.cloudlets.replace(length_mi=base.cloudlets.length_mi * s)
    )(scale)
    return broadcast_campaign(base, n, cloudlets=cls)


def _timed(fn):
    out = fn()                       # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def run() -> dict:
    report: dict = {}

    batched = fig4_grid(N_STREAMING)
    dt, out = _timed(
        lambda: run_campaign(batched, chunk_size=CHUNK, reduce=REDUCE)
    )
    assert int(out["events"]) > 0 and int(out["best"]["index"]) >= 0
    report["campaign_streaming"] = {"streaming": {
        "n_scenarios": N_STREAMING,
        "chunk_size": CHUNK,
        "wall_s": dt,
        "scenarios_per_s": N_STREAMING / dt,
        "events_per_s": int(out["events"]) / dt,
    }}

    from jax.sharding import Mesh

    devs = jax.devices()
    mesh = Mesh(devs, ("data",))
    batched_s = fig4_grid(N_SHARDED)
    dt, out = _timed(
        lambda: run_campaign(batched_s, chunk_size=CHUNK, mesh=mesh,
                             reduce=REDUCE)
    )
    report["campaign_sharded"] = {"sharded": {
        "n_scenarios": N_SHARDED,
        "chunk_size": CHUNK,
        "n_devices": len(devs),
        "wall_s": dt,
        "scenarios_per_s": N_SHARDED / dt,
    }}
    return report


def main():
    report = run()
    s = report["campaign_streaming"]["streaming"]
    print(f"campaign_streaming,n={s['n_scenarios']},chunk={s['chunk_size']},"
          f"scenarios_per_s,{s['scenarios_per_s']:.0f}")
    d = report["campaign_sharded"]["sharded"]
    print(f"campaign_sharded,n={d['n_scenarios']},devices={d['n_devices']},"
          f"scenarios_per_s,{d['scenarios_per_s']:.0f}")
    with open(ARTIFACT, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {ARTIFACT}")


if __name__ == "__main__":
    main()
