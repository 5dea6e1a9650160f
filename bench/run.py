"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload fig9_10.single --seed 7 --seconds 20 --trace 0

Set-up (imports, TPU start, inputs built on the device, compile or cache
load, warm-up) is timed from the start of this process to the first timed
question; then questions are asked in a closed loop for ``--seconds``.
With ``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics, with ``--trace 1`` with its per-layer metrics
from a profiler trace of the window.  Before it, standard error ends with
each number the correctness check compared, beside its limit.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the persistent compilation cache lives at a fixed path inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import cell, spec

    bench = spec.load(ROOT)
    chips = spec.cell(bench, args.workload)["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX's first device is {devices[0].platform!r}; "
              "there is no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    # nothing may compile inside the measured window: count what does
    compiles: list[float] = []

    def heard(event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            compiles.append(time.perf_counter())

    def window_ran(start: float, end: float) -> None:
        n = sum(start <= t <= end for t in compiles)
        print(f"bench: {n} compiles inside the window, {len(compiles)} "
              "in the run before it ended", file=sys.stderr)

    jax.monitoring.register_event_duration_secs_listener(heard)
    result, checks = cell.run(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, on_window=window_ran)
    cell.print_checks(checks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
