"""Time a small-table lookup as a gather against its exact one-hot form.

    python scripts/lookup_crossover.py [--out FILE]

Each case is ``k`` sibling 1-D tables of length ``n`` read at one ``[C]``
index per row, vmapped over ``B`` rows as the batch event step does (``B``
0: one unbatched row).  Each form runs inside a ``fori_loop`` whose index
moves every iteration, and the loop with no lookup at all is subtracted,
so a number is device time per lookup round.  Forms:

* ``gather``: ``table[idx]``, one gather per table;
* ``onehot_minor``: ``[C, n]`` mask, reduce over the table axis, minor;
* ``onehot_major``: ``[n, C]`` mask, reduce over the table axis, major.

The one-hot forms select the bit pattern and sum it (``segments.lookup``).
Prints one JSON line per case; only a run on the chip gives times that
mean anything.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax


ITERS = 32
REPS = 7

# (name, B, C, n, k): the cells' shapes, then n swept at two row shapes
CASES = [
    ("fig4.vm", 8192, 8, 2, 5),
    ("fig4.perm", 8192, 8, 8, 2),
    ("fig9_10.vm", 256, 500, 50, 5),
    ("fig9_10.perm", 256, 500, 500, 2),
    ("fig9_10.hostperm", 256, 50, 50, 2),
    ("fig9_10.single.vm", 0, 500, 50, 5),
    ("fig9_10.single.perm", 0, 500, 500, 2),
    ("planetlab.vm", 12, 1052, 1052, 5),
] + [(f"sweep.B256C500.n{n}", 256, 500, n, 5) for n in (128, 256, 1024, 2048)] \
  + [(f"sweep.B12C1052.n{n}", 12, 1052, n, 5) for n in (256, 2048, 4096)]


def _bits(t):
    return lax.bitcast_convert_type(t, jnp.uint32)


def _forms(n):
    def gather(tables, idx):
        return [t[idx] for t in tables]

    def minor(tables, idx):
        hit = idx[:, None] == jnp.arange(n, dtype=idx.dtype)
        return [lax.bitcast_convert_type(jnp.sum(
            jnp.where(hit, _bits(t), jnp.uint32(0)), axis=1), jnp.float32)
            for t in tables]

    def major(tables, idx):
        hit = jnp.arange(n, dtype=idx.dtype)[:, None] == idx[None, :]
        return [lax.bitcast_convert_type(jnp.sum(
            jnp.where(hit, _bits(t)[:, None], jnp.uint32(0)), axis=0),
            jnp.float32) for t in tables]

    def none(tables, idx):
        return [idx.astype(jnp.float32)]

    return {"none": none, "gather": gather, "onehot_minor": minor,
            "onehot_major": major}


def _program(form, n, batched):
    def round_(tables, idx, acc):
        outs = form(tables, idx)
        return acc + sum(outs), jnp.where(idx + 1 >= n, 0, idx + 1)

    if batched:
        round_ = jax.vmap(round_)

    @jax.jit
    def run(tables, idx, acc):
        def body(_, c):
            acc, idx = c
            return round_(tables, idx, acc)
        return lax.fori_loop(0, ITERS, body, (acc, idx))[0]

    return run


def _time(run, args) -> float:
    run(*args).block_until_ready()
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def case(name, B, C, n, k, key) -> dict:
    lead = (B,) if B else ()
    kt, ki = jax.random.split(key)
    tables = [jax.random.normal(kk, lead + (n,), jnp.float32)
              for kk in jax.random.split(kt, k)]
    idx = jax.random.randint(ki, lead + (C,), 0, n, jnp.int32)
    acc = jnp.zeros(lead + (C,), jnp.float32)
    forms = _forms(n)
    want = forms["gather"](tables, idx) if not B else jax.vmap(
        forms["gather"])(tables, idx)
    for f in ("onehot_minor", "onehot_major"):
        got = forms[f](tables, idx) if not B else jax.vmap(forms[f])(tables, idx)
        assert all(bool(jnp.all(a == b)) for a, b in zip(got, want)), (name, f)
    secs = {f: _time(_program(fn, n, bool(B)), (tables, idx, acc))
            for f, fn in forms.items()}
    us = {f: (secs[f] - secs["none"]) / ITERS * 1e6
          for f in forms if f != "none"}
    return {"case": name, "B": B, "C": C, "n": n, "k": k,
            "us_per_round": {f: round(v, 2) for f, v in us.items()},
            "loop_us_per_iter": round(secs["none"] / ITERS * 1e6, 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path)
    ap.add_argument("--only", default="", help="run cases whose name holds this")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    lines = []
    for i, c in enumerate(CASES):
        if args.only in c[0]:
            row = case(*c, jax.random.PRNGKey(i))
            row["device"] = dev.device_kind
            lines.append(json.dumps(row))
            print(lines[-1], flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
