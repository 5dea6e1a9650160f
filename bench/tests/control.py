"""The control of the check: the plain reference computed in bfloat16, one
precision below the float32 the configurations state, put in the
program's place and compared as the program is.

    python bench/tests/control.py fig4.sweep 1 2 3

prints the numbers compared, per seed, at the cell's own size; the tests
run it at a small size.  It computes nothing with JAX and touches no chip.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def _folded(ctl: dict, params: dict, mix: dict) -> dict:
    """What a sweep's reducers would return from the control's rows."""
    from bench.reference import fold

    out = {}
    for key, r in mix["reduce"].items():
        v = np.asarray(ctl[r["metric"]], np.float64)
        if r["kind"] == "sum":
            out[key] = int(v.sum())
        elif r["kind"] == "histogram":
            idx = fold.bin_index(v, float(r["lo"]), float(r["hi"]), int(r["bins"]))
            out[key] = {"counts": np.bincount(idx, minlength=int(r["bins"]))}
        elif r["kind"] == "argbest":
            i = int(np.argmin(v) if r.get("mode", "min") == "min" else np.argmax(v))
            out[key] = {"value": v[i], "index": i, "policy": SimpleNamespace(
                host_policy=params["host_policy"][i], vm_policy=params["vm_policy"][i])}
    return out


def readings(config: dict, mix: dict, seed: int) -> dict:
    """The numbers the check compares, with the control as the program."""
    import ml_dtypes

    from bench.harness import spec, traffic

    family = spec.family(config)
    rng = traffic.rng_for(seed)
    n = int(mix["pool"] if mix["front_door"] == "simulate" else mix["rows"])
    params = family.draw(config, mix, n, rng)
    ref = family.reference(config, params)
    ctl = family.reference(config, params, dtype=ml_dtypes.bfloat16)
    if mix["front_door"] == "simulate":
        answers = [(i, {k: ctl[k][i] for k in
                        ("start_t", "finish_t", "n_finished", "n_events")})
                   for i in range(n)]
    else:
        answers = [_folded(ctl, params, mix)]
    worst, _ = family.compare(answers, ref, params, mix)
    return worst


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import spec

    bench = spec.load()
    cell = spec.cell(bench, sys.argv[1])
    cfg, mix = spec.config(bench, cell["config"]), spec.traffic(cell["traffic"])
    for s in sys.argv[2:]:
        print(json.dumps({"cell": sys.argv[1], "seed": int(s),
                          **readings(cfg, mix, int(s))}), flush=True)
