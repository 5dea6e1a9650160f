"""Bring-up run of the event engine and the campaign front door on a TPU.

    python chip_smoke.py              # one chip: phases 1-7
    python chip_smoke.py --chips 4    # four chips: the sharded campaign only

Every phase drives a public entry point (``simulate``, its batch-major form,
``run_campaign``) at the paper's own deployment sizes, checks the simulated
statistics against the anchors the tier-1 tests pin, and prints its name,
its compile seconds and its run seconds (host clock around
``jax.block_until_ready``).  Those times are bring-up observations, not
benchmark numbers.  A failed check raises and the script exits non-zero;
only when every phase passed is the last line of standard output

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

There is no CPU fallback: without a TPU the script exits non-zero before
any phase runs.  Everything runs in this one process (a chip belongs to one
process at a time), and every input is built from the scenario
constructors and fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent

# the campaign grid and chunk of benchmarks/campaign_throughput.py
CAMPAIGN_N = 16_384


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def report(phase: str, compile_s: float | None, run_s: float, **stats) -> None:
    """One line per phase; numbers unrounded."""
    fields = " ".join(f"{k}={v!r}" for k, v in stats.items())
    print(f"[{phase}] compile_s={compile_s!r} run_s={run_s!r} {fields}",
          flush=True)


def compile_aot(fn, *args):
    """``(compiled, seconds)``: lower and compile ``jit(fn)`` for ``args``."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(fn, *args):
    """``(out, seconds)`` of one call, ended by ``block_until_ready``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_fig9_10(n_hosts: int = 10_000, n_vms: int = 50,
                  n_groups: int = 10) -> str:
    """Figure 9/10 under both VM policies and both advance sweeps.  Returns
    the optimized HLO of the Pallas program."""
    from repro.core import SPACE_SHARED, TIME_SHARED, scenarios, simulate

    n_tasks = n_vms * n_groups
    results, hlo = {}, ""
    for impl in ("jnp", "pallas"):
        scns = {
            vp: scenarios.fig9_10_scenario(
                vp, n_hosts=n_hosts, n_vms=n_vms, n_groups=n_groups,
            ).replace(sweep_impl=impl)
            for vp in (SPACE_SHARED, TIME_SHARED)
        }
        # the VM policy is traced: one program serves both
        compiled, compile_s = compile_aot(simulate, scns[SPACE_SHARED])
        if impl == "pallas":
            hlo = compiled.as_text()
        for vp, scn in scns.items():
            res, run_s = run_timed(compiled, scn)
            stats = {"n_finished": int(res.n_finished),
                     "n_events": int(res.n_events),
                     "makespan": float(res.makespan),
                     "mean_turnaround": float(res.mean_turnaround)}
            check(stats["n_finished"] == n_tasks,
                  f"fig9_10 {impl} vm_policy={vp}: {stats}")
            if vp == SPACE_SHARED:
                # dedicated cores: every 1.2e6 MI task runs exactly 1200 s
                ex = np.asarray(res.finish_t) - np.asarray(res.start_t)
                stats["max_abs_exec_minus_1200"] = float(
                    np.abs(ex - 1200.0).max())
                check(stats["max_abs_exec_minus_1200"] <= 1e-2,
                      f"fig9_10 {impl}: space-shared task times {stats}")
            report(f"fig9_10/{impl}/vm_policy={vp}", compile_s, run_s,
                   **stats)
            compile_s = None
            results[impl, vp] = res
    for vp in (SPACE_SHARED, TIME_SHARED):
        a, b = results["jnp", vp], results["pallas", vp]
        # the tolerance of tests/test_engine_pallas.py
        np.testing.assert_allclose(np.asarray(a.finish_t),
                                   np.asarray(b.finish_t), rtol=1e-5)
        check(int(a.n_events) == int(b.n_events),
              f"fig9_10 vm_policy={vp}: pallas n_events {int(b.n_events)} "
              f"!= jnp {int(a.n_events)}")
    return hlo


def phase_table1() -> None:
    from repro.core import scenarios, simulate

    scn = scenarios.table1_scenario(federation=True)
    compiled, compile_s = compile_aot(simulate, scn)
    res, run_s = run_timed(compiled, scn)
    stats = {"n_finished": int(res.n_finished),
             "n_migrations": int(res.n_migrations),
             "makespan": float(res.makespan),
             "mean_turnaround": float(res.mean_turnaround)}
    check(stats["n_finished"] == 25 and stats["n_migrations"] == 10,
          f"table1: {stats}")
    report("table1/federation", compile_s, run_s, **stats)


def phase_fig7_8(n_hosts: int = 100_000) -> None:
    from repro.core import scenarios, simulate

    scn = scenarios.fig7_8_scenario(n_hosts)
    compiled, compile_s = compile_aot(simulate, scn)
    res, run_s = run_timed(compiled, scn)
    stats = {"n_hosts": n_hosts, "n_finished": int(res.n_finished),
             "n_events": int(res.n_events),
             "vms_placed": int(np.asarray(res.vm_placed).sum())}
    check(stats["n_finished"] == 1 and stats["vms_placed"] == 1,
          f"fig7_8: {stats}")
    report("fig7_8/instantiation", compile_s, run_s, **stats)


def _phase_fires(name: str, scn, kind: int, n_finished: int):
    """Run ``scn`` through ``simulate`` and ``simulate_history``: the results
    must agree bitwise, all work finish, and ``kind`` events occur.  Returns
    ``(result, compile_s, run_s, stats)``."""
    from benchmarks.event_engine import bitwise_equal
    from repro.core import simulate, simulate_history

    compiled, compile_s = compile_aot(simulate, scn)
    res, run_s = run_timed(compiled, scn)
    hist_c, hist_compile_s = compile_aot(simulate_history, scn)
    (res_h, hist), hist_run_s = run_timed(hist_c, scn)
    kinds = np.bincount(np.asarray(hist.kind)[np.asarray(hist.valid)],
                        minlength=16)
    stats = {"n_finished": int(res.n_finished), "n_events": int(res.n_events),
             "fired": int(kinds[kind]),
             "history_compile_s": hist_compile_s,
             "history_run_s": hist_run_s}
    check(bitwise_equal(res, res_h),
          f"{name}: simulate and simulate_history diverged")
    check(stats["n_finished"] == n_finished and stats["fired"] > 0,
          f"{name}: {stats}")
    return res, compile_s, run_s, stats


def phase_skippable() -> None:
    import jax.numpy as jnp

    from repro.core import scenarios, simulate_instrumented, step

    scn = scenarios.staging_scenario(n_cloudlets=24)
    _, c, r, stats = _phase_fires("staging", scn, step.K_READY, 24)
    report("phase/transfer(staging)", c, r, **stats)

    scn = scenarios.serving_scenario(
        jax.random.PRNGKey(11), n_requests=24, n_replicas=2, n_pool=1,
        kv_blocks=24.0, rate=1.5, autoscale=True, batch_degradation=0.1,
        median_prompt=64.0, median_new=48.0)
    res, c, r, stats = _phase_fires("serving", scn, step.K_SERVING, 24)
    stats["ttft_p50"] = float(res.ttft_p50)
    stats["tpot_p50"] = float(res.tpot_p50)
    check(stats["ttft_p50"] < 1e30, f"serving: no TTFT recorded {stats}")
    report("phase/serving", c, r, **stats)

    scn = scenarios.reliability_scenario(jax.random.PRNGKey(0))
    res, c, r, stats = _phase_fires("reliability", scn, step.K_FAILURE, 8)
    stats["downtime"] = float(res.downtime)
    check(stats["downtime"] > 0.0, f"reliability: no downtime {stats}")
    report("phase/failures(reliability)", c, r, **stats)

    on = scenarios.autoscale_scenario(jax.random.PRNGKey(0))
    off = on.replace(policy=on.policy.replace(autoscale=jnp.asarray(False)))
    # a fresh callable: jit caches pool per underlying function, so another
    # jax.jit(simulate_instrumented) in this process would count here
    fn = jax.jit(lambda s: simulate_instrumented(s))
    (res_on, out_on), first_s = run_timed(fn, on)
    (res_off, out_off), run_s = run_timed(fn, off)
    stats = {"jit_cache_entries": fn._cache_size(),
             "n_finished_on": int(res_on.n_finished),
             "n_finished_off": int(res_off.n_finished),
             "mean_turnaround_on": float(res_on.mean_turnaround),
             "mean_turnaround_off": float(res_off.mean_turnaround),
             "n_scale_up_on": int(out_on["autoscale"]["n_scale_up"]),
             "n_scale_up_off": int(out_off["autoscale"]["n_scale_up"])}
    n_cl = on.cloudlets.n_cloudlets
    check(stats["jit_cache_entries"] == 1
          and stats["n_finished_on"] == n_cl == stats["n_finished_off"]
          and stats["n_scale_up_on"] == 4 and stats["n_scale_up_off"] == 0
          and stats["mean_turnaround_on"]
          < 0.9 * stats["mean_turnaround_off"],
          f"autoscale: {stats}")
    # one program for both runs: the first call's excess is its compile
    report("phase/autoscale(on,off)", first_s - run_s, run_s, **stats)


def phase_batch_major(b: int | None = None) -> None:
    from benchmarks import event_engine
    from repro.core import simulate

    stack = event_engine.batch_stack(b or event_engine.BATCH)
    batch_c, batch_compile_s = compile_aot(simulate, stack)
    res_b, batch_run_s = run_timed(batch_c, stack)
    vmap_c, vmap_compile_s = compile_aot(event_engine.vmap_simulate, stack)
    res_v, vmap_run_s = run_timed(vmap_c, stack)
    n_fin = np.asarray(res_b.n_finished)
    stats = {"batch": int(n_fin.shape[0]),
             "n_events": int(np.asarray(res_b.n_events).sum()),
             "rows_all_finished": bool((n_fin == 8).all()),
             "bitwise_equal_vmap": event_engine.bitwise_equal(res_b, res_v),
             "vmap_compile_s": vmap_compile_s, "vmap_run_s": vmap_run_s}
    check(stats["rows_all_finished"] and stats["bitwise_equal_vmap"],
          f"batch-major: {stats}")
    report("batch_major", batch_compile_s, batch_run_s, **stats)


def phase_campaign(n: int = CAMPAIGN_N, chunk: int | None = None) -> None:
    from benchmarks import campaign_throughput as ct
    from benchmarks.event_engine import bitwise_equal
    from repro.core import campaign, run_campaign, scenarios, simulate

    chunk = chunk or ct.CHUNK
    batched = ct.fig4_grid(n)

    def fold():
        return run_campaign(batched, chunk_size=chunk, reduce=ct.REDUCE)

    out, first_s = run_timed(fold)
    n_programs = campaign._run_chunk_fold._cache_size()
    again, run_s = run_timed(fold)
    mat, mat_first_s = run_timed(
        lambda: run_campaign(batched, chunk_size=chunk))
    mt = np.asarray(mat.mean_turnaround)
    stats = {"n": n, "chunk": chunk,
             "events": int(out["events"]),
             "events_materialized": int(np.asarray(mat.n_events).sum()),
             "best_index": int(out["best"]["index"]),
             "best_index_materialized": int(np.argmin(mt)),
             "best_value": float(out["best"]["value"]),
             "new_fold_programs_on_repeat":
                 campaign._run_chunk_fold._cache_size() - n_programs,
             "scenarios_per_s": n / run_s,
             "materialized_first_call_s": mat_first_s}
    check(stats["events"] == stats["events_materialized"]
          and stats["best_index"] == stats["best_index_materialized"]
          and stats["new_fold_programs_on_repeat"] == 0
          and bitwise_equal(out, again),
          f"campaign: {stats}")
    # the fold donated its carries: the engine must still run afterwards
    res = jax.block_until_ready(jax.jit(simulate)(scenarios.fig4_scenario(0, 0)))
    fin = np.sort(np.asarray(res.finish_t))
    np.testing.assert_allclose(np.unique(fin), [400.0, 800.0, 1200.0, 1600.0],
                               rtol=1e-5)
    report("campaign/fold+materialized", first_s - run_s, run_s, **stats)


def phase_sharded_campaign(n: int = CAMPAIGN_N, chunk: int | None = None,
                           n_devices: int = 4) -> None:
    """The phase-7 grid folded on a ``data`` mesh against one device."""
    from jax.sharding import Mesh

    from benchmarks import campaign_throughput as ct
    from benchmarks.event_engine import bitwise_equal
    from repro.core import campaign, run_campaign
    from repro.core.reducers import MeanReducer

    chunk = chunk or ct.CHUNK
    devices = jax.devices()[:n_devices]
    mesh = Mesh(np.array(devices), ("data",))
    reduce = {**ct.REDUCE, "mt": MeanReducer("mean_turnaround")}
    batched = ct.fig4_grid(n)

    def fold(m):
        return lambda: run_campaign(batched, chunk_size=chunk, mesh=m,
                                    reduce=reduce)

    local, local_first_s = run_timed(fold(None))
    local, local_s = run_timed(fold(None))
    sharded, sharded_first_s = run_timed(fold(mesh))
    sharded, sharded_s = run_timed(fold(mesh))

    # one chunk through the same sharded program, materialized, to see
    # where its rows ran
    first = jax.tree.map(lambda x: x[:chunk], batched)
    res = jax.block_until_ready(campaign._run_chunk(first, mesh, "data"))
    shards = res.n_events.addressable_shards
    per_device = {s.device.id: int(np.asarray(s.data).sum()) for s in shards}
    stats = {
        "n": n, "chunk": chunk, "devices": [d.id for d in devices],
        "events": int(sharded["events"]),
        "events_one_device": int(local["events"]),
        "best_index": int(sharded["best"]["index"]),
        "best_index_one_device": int(local["best"]["index"]),
        "mean": float(sharded["mt"]["mean"]),
        "mean_one_device": float(local["mt"]["mean"]),
        "rows_per_shard": sorted({int(s.data.shape[0]) for s in shards}),
        "events_per_device_first_chunk": per_device,
        "one_device_compile_s": local_first_s - local_s,
        "one_device_run_s": local_s,
        "one_device_scenarios_per_s": n / local_s,
        "sharded_scenarios_per_s": n / sharded_s,
    }
    check(stats["events"] == stats["events_one_device"]
          and stats["best_index"] == stats["best_index_one_device"]
          and bitwise_equal(sharded["turnaround"]["counts"],
                            local["turnaround"]["counts"])
          and int(sharded["mt"]["n"]) == int(local["mt"]["n"]) == n,
          f"sharded campaign: {stats}")
    # float means regroup per shard: the tolerances of tests/test_reducers.py
    np.testing.assert_allclose(stats["mean"], stats["mean_one_device"],
                               rtol=1e-5)
    np.testing.assert_allclose(float(sharded["mt"]["std"]),
                               float(local["mt"]["std"]), rtol=1e-3)
    check(len(per_device) == n_devices
          and all(v > 0 for v in per_device.values())
          and stats["rows_per_shard"] == [chunk // n_devices],
          f"sharded campaign: not every device held a shard {stats}")
    report(f"campaign/sharded x{n_devices}", sharded_first_s - sharded_s,
           sharded_s, **stats)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded campaign on four chips "
                         "and the one-device campaign it is compared with")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's first device is "
                 f"{devices[0].platform!r}; there is no CPU fallback")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache

    print(f"[device] kind={devices[0].device_kind!r} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={enable_compile_cache()!r}",
          flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded_campaign(n_devices=4)
    else:
        hlo = phase_fig9_10()
        check("tpu_custom_call" in hlo,
              "fig9_10: the Pallas program holds no compiled Mosaic kernel")
        phase_table1()
        phase_fig7_8()
        phase_skippable()
        phase_batch_major()
        phase_campaign()
    print(f"[total] wall_s={time.perf_counter() - t0!r}", flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
