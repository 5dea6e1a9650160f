"""One run of one cell: set-up, the measured window, the check, the result.

``run`` is everything ``bench/run.py`` does after it has found the chips;
the tests call it on the CPU with smaller sizes and a broken program.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass

from bench.harness import correct, spec, trace as tr, traffic


def window(door, seconds: float):
    """Questions asked in a closed loop for ``seconds``.  Returns the
    window's start and, for each question that ended inside it,
    ``(start, end, output)``."""
    t0 = time.perf_counter()
    end = t0 + seconds
    done = []
    while True:
        a = time.perf_counter()
        if a >= end:
            break
        out = door.call()
        b = time.perf_counter()
        if b <= end:
            done.append((a, b, out))
    return t0, done


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class Reading:
    """What a per-layer metric reads: the traced window and its counts."""

    trace: tr.Trace
    devices: list[int]        # device ids of the cell's chips
    lo: float                 # traced window on the trace's clock
    hi: float
    n_scenarios: int
    iterations: float         # event-loop iterations per chip

    def __post_init__(self):
        self._busy = {d: self.trace.busy(d) for d in self.devices}

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_on(self, dev: int) -> list[tuple[float, float]]:
        return self._busy[dev]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, mean over the cell's chips."""
        return statistics.fmean(
            tr.covered(self.busy_on(d), self.lo, self.hi) for d in self.devices)

    def scope_s(self, scope: str) -> float:
        """Device seconds of ops under ``scope``, summed over chips."""
        return sum(tr.covered(self.trace.in_scope(d, scope), self.lo, self.hi)
                   for d in self.devices)

    def idle_in(self, span: str) -> tuple[float, int]:
        """Device-idle seconds inside the host spans named ``span`` (mean
        over chips), and how many such spans there are."""
        spans = [(s, e) for n, s, e in self.trace.spans
                 if n == span and s >= self.lo and e <= self.hi]
        idle = statistics.fmean(
            sum((e - s) - tr.covered(self.busy_on(d), s, e) for s, e in spans)
            for d in self.devices)
        return idle, len(spans)


def breakdown(r: Reading, top: int = 10) -> dict:
    """The device ops that took most time (self time, leaf ops, summed over
    chips) and device-idle time by the host span it fell in."""
    ops: dict[str, float] = {}
    for d in r.devices:
        for o in tr.leaves(r.trace.ops.get(d, [])):
            if o.end <= r.lo or o.start >= r.hi:
                continue
            scope = r.trace.scope_of(o)
            key = f"{o.name} {scope}".strip()[:160]
            ops[key] = ops.get(key, 0.0) + (min(o.end, r.hi) - max(o.start, r.lo))
    idle: dict[str, float] = {}
    spans = r.trace.spans
    starts = [a for _, a, _ in spans]
    for d in r.devices:
        for s, e in tr.gaps(r.busy_on(d), r.lo, r.hi):
            cover = {}
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(spans) and spans[i][1] < e:
                n, a, b = spans[i]
                if b > s:
                    cover[n] = cover.get(n, 0.0) + min(b, e) - max(a, s)
                i += 1
            name = max(cover, key=cover.get) if cover else "between spans"
            idle[name] = idle.get(name, 0.0) + (e - s) / len(r.devices)
    rank = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in rank],
            "idle_gaps": [[k, v] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:top]]}


def _traced_window(door, seconds: float):
    """The window under the profiler; returns ``(t0, done, trace)``."""
    import jax

    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(d, profiler_options=opts):
            t0, done = window(door, seconds)
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        space = tr.read_file(paths[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return t0, done, tr.load(space, set(traffic.SPANS))


def _finite(x: float) -> float:
    return x if math.isfinite(x) else correct.HUGE


def run(bench: dict, cell_name: str, seed: int, seconds: float, traced: bool,
        t_start: float, config: dict | None = None, mix: dict | None = None,
        devices=None, on_window=None) -> tuple[dict, dict]:
    """One run of a cell.  Returns ``(result line, checks)``; ``config`` and
    ``mix`` replace the files the cell names (the tests' small sizes);
    ``on_window(start, end)`` hears when the measured window ran."""
    import jax

    cell = spec.cell(bench, cell_name)
    config = config or spec.config(bench, cell["config"])
    mix = mix or spec.traffic(cell["traffic"])
    devices = list(devices if devices is not None else jax.devices())
    used = devices[:cell["chips"]]

    family = spec.family(config)
    door = traffic.door(family, config, mix, seed, used)
    door.warm()
    setup_s = time.perf_counter() - t_start

    if traced:
        t0, done, trace = _traced_window(
            door, min(seconds, float(mix["trace_seconds"])))
    else:
        t0, done = window(door, seconds)
    if on_window is not None:
        on_window(t0, time.perf_counter())
    outputs = [o for _, _, o in done]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(used)}

    metrics = {}
    result_extra = {}
    if not traced:
        wanted = {m["name"]: m for m in spec.metrics_of(bench, cell_name,
                                                        "end_to_end")}
        values = {"setup_s": setup_s}
        if done:
            values["scenarios_per_s"] = (len(done) * door.rows_per_call
                                         / (done[-1][1] - t0))
            ms = [(b - a) * 1e3 for a, b, _ in done]
            values["scenario_ms_p95"] = (statistics.quantiles(
                ms, n=100, method="inclusive")[94] if len(ms) > 1 else ms[0])
        for name, m in wanted.items():
            if name in values:
                metrics[name] = {"value": values[name], "unit": m["unit"]}
    elif done:
        ids = [d.id for d in used]
        # two host spans per question; the window ends with the last
        # question that ended inside it
        spans = trace.spans[:2 * len(done)]
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
        reading = Reading(trace=trace, devices=ids, lo=lo, hi=hi,
                          n_scenarios=len(done) * door.rows_per_call,
                          iterations=door.iterations(outputs))
        for m in spec.metrics_of(bench, cell_name, "per_layer"):
            value = spec.metric_reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        result_extra["breakdown"] = breakdown(reading)

    # the check, once the window has closed and the peak has been read
    ref = family.reference(config, door.params)
    worst, failed = family.compare(family.answers(outputs, mix), ref,
                                   door.params, mix)
    worst = {k: _finite(float(v)) if isinstance(v, float) else v
             for k, v in worst.items()}
    checks = correct.check_lines(worst, family.LIMITS)
    failed = int(failed)
    ok = bool(done and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": ok, "attempted": len(done), "failed": failed,
              "metrics": metrics, "device": device, **result_extra,
              "checks": checks}
    return result, checks


def print_checks(checks: dict, file=sys.stderr) -> None:
    for k, c in checks.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}", file=file)
