"""Deciding ``correct``: what the timed window produced against the plain
reference (``bench/reference``), number by number, each against its limit.

The reference runs once the window has closed, over the same drawn
scenarios, in float64.  Numbers compared:

* ``time_err``: the largest relative gap of a task's start or finish time,
  ``|program - reference| / max(reference, 1 s)``, over every task of every
  answer (``simulate`` cells);
* ``finished_diff``: tasks finished, program against reference (exact);
* ``events_off``: event batches outside ``[reference, reference + slack]``,
  the slack being the batches a float32 engine may split off an instant
  where kinds of event meet in exact arithmetic (exact);
* sweeps, for every sweep of the window: ``events_off`` of the summed
  ``n_events``; ``hist_total_diff``, rows the histogram counts against rows
  in the grid (exact); ``hist_excess_rows``, rows binned where no value
  within ``time_err``'s limit of the reference lies (exact); ``best_err``,
  the relative gap of the reported best value from the reference's, and of
  the reference's value at the reported index from its best;
  ``best_policy_diff``, policy fields of the reported best row that differ
  from the grid's row at that index (exact).

The limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import fold, sim

# A relative gap of times: set between the program's readings on the chip
# and the bfloat16 control's (PERF.md, "Limits of the check").
TIME_LIMIT = 1e-5
LIMITS = {
    "time_err": TIME_LIMIT,
    "finished_diff": 0,
    "events_off": 0,
    "hist_total_diff": 0,
    "hist_excess_rows": 0,
    "best_err": TIME_LIMIT,
    "best_policy_diff": 0,
}
HUGE = 1e300   # a gap that cannot be measured (an answer missing or infinite)


def reference(config: dict, params: dict, dtype=np.float64) -> dict:
    return sim.simulate_rows(sim.rows_from_config(config, params), dtype)


def _rel(prog, ref) -> np.ndarray:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    both_inf = np.isinf(prog) & np.isinf(ref)
    with np.errstate(invalid="ignore"):
        gap = np.abs(prog - ref) / np.maximum(np.abs(ref), 1.0)
    gap = np.where(both_inf, 0.0, gap)
    return np.where(np.isfinite(gap), gap, HUGE)


def _events_off(n, lo, slack) -> int:
    return int(max(lo - n, 0) + max(n - (lo + slack), 0))


def answers_of_simulate(outputs) -> list[tuple[int, dict]]:
    """``(pool row, answer)`` of each call, on the host."""
    import jax

    got = jax.device_get([{"start_t": r.start_t, "finish_t": r.finish_t,
                           "n_finished": r.n_finished, "n_events": r.n_events}
                          for _, r in outputs])
    return [(i, a) for (i, _), a in zip(outputs, got)]


def compare_simulate(answers, ref: dict) -> tuple[dict, int]:
    """``({number: worst value}, answers failing a limit)``."""
    worst = dict.fromkeys(("time_err", "finished_diff", "events_off"), 0)
    failed = 0
    for i, a in answers:
        nums = {
            "time_err": float(max(_rel(a["start_t"], ref["start_t"][i]).max(),
                                  _rel(a["finish_t"], ref["finish_t"][i]).max())),
            "finished_diff": abs(int(a["n_finished"]) - int(ref["n_finished"][i])),
            "events_off": _events_off(int(a["n_events"]), int(ref["n_events"][i]),
                                      int(ref["event_slack"][i])),
        }
        failed += any(v > LIMITS[k] for k, v in nums.items())
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def answers_of_campaign(outputs) -> list[dict]:
    import jax

    return jax.device_get([o for _, o in outputs])


def compare_campaign(answers, ref: dict, params: dict, mix: dict) -> tuple[dict, int]:
    names = ("events_off", "hist_total_diff", "hist_excess_rows", "best_err",
             "best_policy_diff")
    worst = dict.fromkeys(names, 0)
    failed = 0
    n = len(ref["n_events"])
    for a in answers:
        nums = {}
        for key, r in mix["reduce"].items():
            got = a[key]
            v = np.asarray(ref[r["metric"]], np.float64)
            if r["kind"] == "sum":
                nums["events_off"] = _events_off(
                    int(got), int(ref["n_events"].sum()),
                    int(ref["event_slack"].sum()))
            elif r["kind"] == "histogram":
                counts = np.asarray(got["counts"])
                nums["hist_total_diff"] = abs(int(counts.sum()) - n)
                nums["hist_excess_rows"] = fold.histogram_excess(
                    counts, v, float(r["lo"]), float(r["hi"]), int(r["bins"]),
                    TIME_LIMIT)
            elif r["kind"] == "argbest":
                best = v.min() if r.get("mode", "min") == "min" else v.max()
                idx = int(got["index"])
                at = v[idx] if 0 <= idx < n else np.inf
                nums["best_err"] = float(max(_rel(got["value"], best),
                                             _rel(at, best)))
                pol = got["policy"]
                nums["best_policy_diff"] = 2 if not 0 <= idx < n else (
                    int(int(pol.host_policy) != params["host_policy"][idx])
                    + int(int(pol.vm_policy) != params["vm_policy"][idx]))
        failed += any(v > LIMITS[k] for k, v in nums.items())
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def check_lines(worst: dict) -> dict:
    """``{number: {"value": v, "limit": l}}`` in a fixed order."""
    return {k: {"value": worst[k], "limit": LIMITS[k]} for k in worst}
