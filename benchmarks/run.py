"""Benchmark driver: one section per paper table/figure + beyond-paper rows.

    PYTHONPATH=src python -m benchmarks.run

Emits ``name,metric,value[,paper_value]`` CSV-ish lines so EXPERIMENTS.md
tables regenerate mechanically, aggregates every per-benchmark JSON into one
``BENCH_report.json``, and exits nonzero if any section raised — a crashed
benchmark used to leave its stale JSON behind for CI to upload as if fresh;
now the stale file is deleted up front, the failure is recorded in the
aggregate report, and the build fails.  The dry-run/roofline sweep is
separate (repro.launch.dryrun) because it needs the 512-device XLA flag.

``--refresh-baseline`` rewrites ``BENCH_baseline.json`` from this run's
fresh artifacts after a fully-green pass, applying the same merge rule the
regression gate uses (``check_regression.py``): every top-level section of
every fresh artifact replaces its baseline counterpart, baseline-only
sections are preserved.  Refreshing section-by-section by hand is what
caused baseline/fresh drift (a gated key measured by a new benchmark but
missing from the baseline fails the gate with a KeyError, not a perf
signal) — always refresh through this flag.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

# (section title, module under benchmarks/, JSON artifact or None)
SECTIONS = (
    ("Figure 7/8: instantiation time & memory (100 -> 100k hosts)",
     "fig7_8_instantiation", None),
    ("Figure 9/10: space- vs time-shared task execution",
     "fig9_10_scheduling", None),
    ("Table 1: federated vs non-federated clouds",
     "table1_federation", None),
    ("Campaign throughput: streaming + sharded sweeps "
     "(-> BENCH_campaign.json)",
     "campaign_throughput", "BENCH_campaign.json"),
    ("Engine advance-sweep: jnp vs Pallas (-> BENCH_engine.json)",
     "engine_sweep", "BENCH_engine.json"),
    ("Batch-major event engine vs vmap (-> BENCH_event_engine.json)",
     "event_engine", "BENCH_event_engine.json"),
    ("Dynamic workloads + auto-scaling (-> BENCH_autoscale.json)",
     "autoscale_workload", "BENCH_autoscale.json"),
    ("Live VM migration across federated DCs (-> BENCH_migration.json)",
     "live_migration", "BENCH_migration.json"),
    ("Host failures + SLA reliability (-> BENCH_reliability.json)",
     "reliability", "BENCH_reliability.json"),
    ("Contention-aware network transfers (-> BENCH_network.json)",
     "network_transfer", "BENCH_network.json"),
    ("KV-cache-bound continuous batching (-> BENCH_serving.json)",
     "serving_sched", "BENCH_serving.json"),
    ("Energy + topology (the paper's future work, implemented)",
     "energy_topology", None),
)

REPORT_PATH = "BENCH_report.json"
BASELINE_PATH = "BENCH_baseline.json"

BASELINE_NOTE = (
    "Regenerated by `python -m benchmarks.run --refresh-baseline` on a "
    "green run. Merge rule (same as check_regression.py): top-level "
    "sections of every fresh BENCH_*.json replace their counterparts "
    "here; sections no fresh artifact produces are preserved. Do not "
    "hand-edit individual sections — partial refreshes are how baseline "
    "and fresh reports drift apart."
)


def refresh_baseline(report: dict) -> None:
    """Merge this run's fresh artifacts over the committed baseline."""
    try:
        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError):
        baseline = {}
    for entry in report["sections"].values():
        artifact = entry.get("artifact")
        if artifact:
            baseline.update(artifact["data"])
    baseline["_note"] = BASELINE_NOTE
    with open(BASELINE_PATH, "w") as f:
        json.dump(baseline, f, indent=2)
    print(f"# refreshed {BASELINE_PATH}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--refresh-baseline", action="store_true",
                    help="after a fully-green run, rewrite "
                         f"{BASELINE_PATH} from the fresh artifacts")
    args = ap.parse_args(argv)
    print(f"# compile cache: {enable_compile_cache()}")
    t_all = time.time()
    report: dict = {"sections": {}, "ok": True}

    # A benchmark that crashes must not leave last run's JSON lying around
    # looking fresh.
    for _, _, artifact in SECTIONS:
        if artifact and os.path.exists(artifact):
            os.remove(artifact)

    for title, mod_name, artifact in SECTIONS:
        print(f"\n# --- {title} ---")
        entry: dict = {"title": title}
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
            entry["status"] = "ok"
        except Exception:
            traceback.print_exc()
            entry["status"] = "error"
            entry["error"] = traceback.format_exc(limit=20)
            report["ok"] = False
        entry["wall_s"] = round(time.time() - t0, 3)
        if artifact:
            try:
                with open(artifact) as f:
                    entry["artifact"] = {"path": artifact, "data": json.load(f)}
            except (OSError, json.JSONDecodeError) as e:
                # missing or truncated artifact: record, don't crash the
                # aggregator — that is the failure mode this driver exists
                # to surface
                if entry["status"] == "ok":
                    entry["status"] = "error"
                    entry["error"] = f"artifact {artifact} unreadable: {e}"
                    report["ok"] = False
        report["sections"][mod_name] = entry

    report["total_wall_s"] = round(time.time() - t_all, 1)
    with open(REPORT_PATH, "w") as f:
        json.dump(report, f, indent=2)
    print(f"\n# wrote {REPORT_PATH}")
    print(f"# total wall time: {report['total_wall_s']:.1f}s")
    failed = [m for m, e in report["sections"].items()
              if e["status"] != "ok"]
    if failed:
        print(f"# FAILED sections: {', '.join(failed)}", file=sys.stderr)
        return 1
    if args.refresh_baseline:
        refresh_baseline(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
