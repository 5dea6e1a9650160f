"""The two front doors a traffic mix drives, and what they share.

A traffic mix is a data file (``bench/traffic/<mix>.json``).  The
harness's own keys are ``front_door``, ``rows``, ``pool``, ``chunk_size``,
``mesh_devices``, ``reduce`` and ``trace_seconds``; the rest belong to the
configuration's deployment family (``bench/families/<family>.py``), whose
``draw`` takes each row's traced values from the mix and ``--seed`` and
whose ``build_one``/``build_rows`` make the rows on the device.

Each door is a closed loop, one client waiting for each answer before it
asks the next question:

* ``"simulate"``: one ``simulate`` call per question, on one compiled
  program, cycling through a pool of ``pool`` scenarios built at set-up in
  an order drawn from the seed (each block of ``pool`` calls asks each
  scenario once);
* ``"run_campaign"``: one sweep per question, ``run_campaign(grid,
  chunk_size=..., reduce=..., mesh=...)`` over a grid of ``rows`` scenarios
  built on the device at set-up; ``mesh_devices`` > 0 shards every chunk
  over that many chips.
"""
from __future__ import annotations

import numpy as np

SPANS = ("dispatch", "wait", "run_campaign")


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def reducers(mix: dict) -> dict:
    """The mix's reducers, one instance each (one compiled fold per run)."""
    from repro.core import ArgBestReducer, HistogramReducer, SumReducer

    out = {}
    for key, r in mix["reduce"].items():
        if r["kind"] == "sum":
            out[key] = SumReducer(r["metric"])
        elif r["kind"] == "histogram":
            out[key] = HistogramReducer(r["metric"], float(r["lo"]),
                                        float(r["hi"]), bins=int(r["bins"]))
        elif r["kind"] == "argbest":
            out[key] = ArgBestReducer(r["metric"], r.get("mode", "min"))
        else:
            raise ValueError(f"unknown reducer kind {r['kind']!r}")
    return out


class SimulateDoor:
    """One ``simulate`` call per question."""

    def __init__(self, family, config: dict, mix: dict, seed: int):
        import jax

        from repro.core import simulate

        self.rng = rng_for(seed)
        n = int(mix["pool"])
        self.params = family.draw(config, mix, n, self.rng)
        self.pool = [family.build_one(config, self.params, i, mix)
                     for i in range(n)]
        self.program = jax.jit(simulate).lower(self.pool[0]).compile()
        self.order: list[int] = []
        self.rows_per_call = 1

    def warm(self) -> None:
        import jax

        for scn in self.pool:
            jax.block_until_ready(self.program(scn))

    def next_row(self) -> int:
        if not self.order:
            self.order = list(self.rng.permutation(len(self.pool)))
        return int(self.order.pop(0))

    def call(self):
        """One question: ``(pool row, result)``, the result on the host's
        side of ``block_until_ready``."""
        import jax

        i = self.next_row()
        with jax.profiler.TraceAnnotation("dispatch"):
            out = self.program(self.pool[i])
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(out)
        return i, out

    def iterations(self, outputs) -> float:
        """Event-loop iterations of these calls (one device)."""
        return float(sum(int(res.n_events) for _, res in outputs))


class CampaignDoor:
    """One ``run_campaign`` sweep per question."""

    def __init__(self, family, config: dict, mix: dict, seed: int,
                 devices=None):
        import jax

        self.rng = rng_for(seed)
        self.params = family.draw(config, mix, int(mix["rows"]), self.rng)
        self.grid = family.build_rows(config, self.params, mix)
        jax.block_until_ready(self.grid)
        self.chunk = int(mix["chunk_size"])
        self.reduce = reducers(mix)
        self.mesh = None
        k = int(mix.get("mesh_devices", 0))
        if k:
            from jax.sharding import Mesh

            devs = list(devices if devices is not None else jax.devices())
            self.mesh = Mesh(np.array(devs[:k]), ("data",))
        self.rows_per_call = int(mix["rows"])

    def sweep(self):
        from repro.core import run_campaign

        return run_campaign(self.grid, chunk_size=self.chunk,
                            reduce=self.reduce, mesh=self.mesh)

    def warm(self) -> None:
        import jax

        for _ in range(2):
            jax.block_until_ready(self.sweep())

    def call(self):
        import jax

        with jax.profiler.TraceAnnotation("run_campaign"):
            out = self.sweep()
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(out)
        return 0, out

    def iterations(self, outputs) -> float:
        """Event-loop iterations per chip of these sweeps: the batch-major
        loop of a chunk runs until its slowest row ends, on each chip over
        that chip's rows, so each chunk counts its per-shard maximum of
        ``n_events`` (mean over shards).  Read from one materialized run of
        the same grid, outside any timed or traced window."""
        import jax

        from repro.core import run_campaign

        res = run_campaign(self.grid, chunk_size=self.chunk, mesh=self.mesh)
        ev = np.asarray(jax.device_get(res.n_events))
        n = len(ev)
        n_chunks = -(-n // self.chunk)
        pad = np.concatenate([ev, np.repeat(ev[-1:], n_chunks * self.chunk - n)])
        shards = 1 if self.mesh is None else self.mesh.devices.size
        per = pad.reshape(n_chunks, shards, self.chunk // shards).max(-1)
        return float(per.sum(0).mean()) * len(outputs)


def door(family, config: dict, mix: dict, seed: int, devices=None):
    """The mix's front door over rows of ``config`` that ``family`` draws
    and builds."""
    if mix["front_door"] == "simulate":
        return SimulateDoor(family, config, mix, seed)
    if mix["front_door"] == "run_campaign":
        return CampaignDoor(family, config, mix, seed, devices)
    raise ValueError(f"unknown front door {mix['front_door']!r}")
