"""Streaming campaign reductions: fold chunk results, never hold ``[N, ...]``.

A 1e6-point policy study does not want a million stacked ``SimResult``
pytrees — it wants a handful of summary statistics (mean turnaround, tail
percentiles, violation counts, the single best policy row).  A
``CampaignReducer`` is an associative fold over campaign chunks with a
**fixed-shape carry**: ``run_campaign(batched, chunk_size=..., reduce=...)``
runs each chunk through the one compiled chunk program, folds the chunk's
``SimResult`` into the carry *inside the same jitted call* (so the chunk
result never even returns to Python), and hands back only the finalized
summary.  Working memory is bounded by one chunk plus the carry regardless
of campaign size (DESIGN.md §12).

Protocol
--------
``init(chunk_avals, res_avals)`` builds the carry from the chunk's abstract
shapes (``jax.eval_shape`` trees — no arrays materialized); ``fold(carry,
chunk, res, index, valid)`` consumes one ``[chunk]``-leading batch where
``index`` holds global row indices and ``valid`` masks the repeated-row
padding of the trailing chunk; ``finalize(carry)`` converts the carry to the
user-facing summary.  All three are traced: ``run_campaign`` builds the
carries in one jitted program, folds inside the chunk program and runs
every reducer's ``finalize`` in one jitted program, so each is pure jnp
that reads no value on the host.  Reducers are frozen dataclasses, so they
are hashable and ride through ``jax.jit`` as static arguments — reuse ONE
reducer instance across calls or the jit cache forks per instance.

Determinism and chunk-size invariance
-------------------------------------
Integer folds (``SumReducer`` over counts, ``HistogramReducer`` bin counts,
``ArgBestReducer`` with first-lowest-index tie-breaking, ``ValuesReducer``
scatters) are associative and therefore **bitwise identical** for every
chunking of the same campaign.  Float sums (``MeanReducer``,
``SumReducer`` over f32 fields) regroup additions per chunk, so they agree
only to rounding; percentile estimates from ``HistogramReducer`` are exact
functions of the (bitwise-stable) bin counts, accurate to one bin width.
tests/test_reducers.py pins all of this against the materialized
``[N, ...]`` reference.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.entities import INF, Scenario, SimResult


def _metric_fn(metric):
    """Normalize a metric spec: a ``SimResult`` field name, a dotted path
    into an optional result group (``"power.esv"``), or a callable
    ``SimResult -> [B]`` array (one scalar per scenario row)."""
    if callable(metric):
        return metric
    if isinstance(metric, str):
        head, *rest = metric.split(".")
        if head not in {f.name for f in dataclasses.fields(SimResult)}:
            raise ValueError(
                f"unknown SimResult field {metric!r}; pass a callable for "
                "derived metrics"
            )

        def get(res):
            out = getattr(res, head)
            for name in rest:
                if out is None or not hasattr(out, name):
                    raise ValueError(
                        f"{metric!r}: this campaign's results have no {name!r}")
                out = getattr(out, name)
            return out

        return get
    raise TypeError(f"metric must be a field name or callable, got {metric!r}")


def _metric_aval(metric, res_avals):
    """Abstract [B] value of ``metric`` (shape/dtype only, nothing runs)."""
    aval = jax.eval_shape(_metric_fn(metric), res_avals)
    if len(aval.shape) != 1:
        raise ValueError(
            f"reducer metrics must be one scalar per scenario row ([B]); "
            f"metric {metric!r} has shape {aval.shape} — reduce per-entity "
            "fields (e.g. turnaround [B, C]) to a row scalar in the callable"
        )
    return aval


@dataclasses.dataclass(frozen=True)
class CampaignReducer:
    """Base protocol — see the module docstring for the fold contract."""

    def init(self, chunk_avals: Scenario, res_avals: SimResult):
        raise NotImplementedError

    def fold(self, carry, chunk: Scenario, res: SimResult, index, valid):
        raise NotImplementedError

    def finalize(self, carry):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SumReducer(CampaignReducer):
    """Total of a per-scenario metric — violation counts, downtime seconds.

    Integer metrics fold exactly (associative), so the streamed total is
    bitwise the materialized one for any chunk size.
    """

    metric: object

    def init(self, chunk_avals, res_avals):
        aval = _metric_aval(self.metric, res_avals)
        return jnp.zeros((), aval.dtype)

    def fold(self, carry, chunk, res, index, valid):
        v = _metric_fn(self.metric)(res)
        return carry + jnp.sum(jnp.where(valid, v, jnp.zeros((), v.dtype)))

    def finalize(self, carry):
        return carry


@dataclasses.dataclass(frozen=True)
class MeanReducer(CampaignReducer):
    """Streaming count/sum/sum-of-squares -> ``{n, mean, std}``.

    Float accumulation regroups per chunk, so expect rounding-level (not
    bitwise) agreement with the materialized reference.
    """

    metric: object

    def init(self, chunk_avals, res_avals):
        _metric_aval(self.metric, res_avals)  # validate rank early
        f32 = jnp.float32
        return (jnp.zeros((), f32), jnp.zeros((), f32), jnp.zeros((), f32))

    def fold(self, carry, chunk, res, index, valid):
        n, s, ss = carry
        v = _metric_fn(self.metric)(res).astype(jnp.float32)
        v = jnp.where(valid, v, 0.0)
        return (n + jnp.sum(valid.astype(jnp.float32)), s + jnp.sum(v),
                ss + jnp.sum(v * v))

    def finalize(self, carry):
        n, s, ss = carry
        mean = s / jnp.maximum(n, 1.0)
        var = jnp.maximum(ss / jnp.maximum(n, 1.0) - mean * mean, 0.0)
        return {"n": n, "mean": mean, "std": jnp.sqrt(var)}


@dataclasses.dataclass(frozen=True)
class HistogramReducer(CampaignReducer):
    """Fixed-shape histogram sketch -> bin counts + percentile estimates.

    ``bins`` i32 counters over ``[lo, hi]`` (values clipped into range, so
    the extreme bins double as under/overflow).  Bin counts are integer
    scatters — bitwise chunk-order invariant — and quantiles interpolate
    within the selected bin, so the estimate error is at most one bin width
    ``(hi - lo) / bins`` (the tolerance tests/test_reducers.py asserts).
    Fixed shape is the point: a P²-style sketch with data-dependent marker
    moves would still be fixed-shape, but the histogram keeps the fold a
    pure scatter-add the compiler can fuse into the chunk program.
    """

    metric: object
    lo: float
    hi: float
    bins: int = 64
    qs: tuple = (0.5, 0.9, 0.99)

    def __post_init__(self):
        if not (self.hi > self.lo):
            raise ValueError(f"empty histogram range [{self.lo}, {self.hi}]")
        if self.bins < 1:
            raise ValueError(f"bins must be >= 1, got {self.bins}")

    def init(self, chunk_avals, res_avals):
        _metric_aval(self.metric, res_avals)
        return jnp.zeros((self.bins,), jnp.int32)

    def fold(self, carry, chunk, res, index, valid):
        v = _metric_fn(self.metric)(res).astype(jnp.float32)
        width = (self.hi - self.lo) / self.bins
        idx = jnp.clip(((v - self.lo) / width).astype(jnp.int32),
                       0, self.bins - 1)
        # invalid rows scatter out of bounds and are dropped
        idx = jnp.where(valid, idx, self.bins)
        return carry.at[idx].add(1, mode="drop")

    def finalize(self, carry):
        counts = carry
        total = jnp.maximum(jnp.sum(counts), 1)
        cum = jnp.cumsum(counts)
        width = (self.hi - self.lo) / self.bins
        out = {"counts": counts,
               "edges": jnp.linspace(self.lo, self.hi, self.bins + 1)}
        for q in self.qs:
            target = q * total.astype(jnp.float32)
            bin_ = jnp.argmax(cum.astype(jnp.float32) >= target)
            # interpolate within the bin: how far into its count the
            # target falls
            below = jnp.where(bin_ > 0, cum[jnp.maximum(bin_ - 1, 0)], 0)
            in_bin = jnp.maximum(counts[bin_], 1).astype(jnp.float32)
            frac = jnp.clip((target - below) / in_bin, 0.0, 1.0)
            out[f"q{q:g}"] = self.lo + (bin_.astype(jnp.float32) + frac) * width
        return out


@dataclasses.dataclass(frozen=True)
class LatencyHistogramReducer(HistogramReducer):
    """Serving tail latency pooled across the whole campaign: per-*request*
    TTFT or TPOT values (``[B, C]``, not the usual per-row scalar) folded
    into one fixed-bin histogram (DESIGN.md §14).

    ``metric`` selects the latency: ``"ttft"`` is ``start_t - submit_t``
    (queueing + KV-admission delay until the first decode step),
    ``"tpot"`` is ``(finish_t - start_t) / max_new_tokens`` (observed
    per-token pace, preemption stalls included).  Only *finished serving*
    rows of *valid* scenario rows scatter; everything else drops out of
    bounds.  Counts are integer scatters — bitwise chunk-order invariant —
    and the inherited quantile finalize is exact to one bin width, so a
    million-scenario sweep gets fleet-wide p50/p99 tail latency without
    materializing a single per-row result.
    """

    def __post_init__(self):
        super().__post_init__()
        if self.metric not in ("ttft", "tpot"):
            raise ValueError(
                f"metric must be 'ttft' or 'tpot', got {self.metric!r}"
            )

    def init(self, chunk_avals, res_avals):
        # per-request values: no [B]-rank validation of the base class
        return jnp.zeros((self.bins,), jnp.int32)

    def fold(self, carry, chunk, res, index, valid):
        cls = chunk.cloudlets
        served = (
            cls.exists & (cls.prompt_tokens > 0.0)
            & (res.finish_t < INF / 2)
        )                                                        # [B, C]
        if self.metric == "ttft":
            v = res.start_t - cls.submit_t
        else:
            v = (res.finish_t - res.start_t) / jnp.maximum(
                cls.max_new_tokens, 1.0
            )
        width = (self.hi - self.lo) / self.bins
        idx = jnp.clip(((v - self.lo) / width).astype(jnp.int32),
                       0, self.bins - 1)
        keep = served & valid[:, None]
        idx = jnp.where(keep, idx, self.bins)    # drop out of bounds
        return carry.at[idx].add(1, mode="drop")


@dataclasses.dataclass(frozen=True)
class ArgBestReducer(CampaignReducer):
    """Best scenario row by a scalar metric, carrying its ``Policy`` row.

    Ties resolve to the lowest global row index (``argmin``/``argmax`` take
    the first occurrence inside a chunk; across chunks only a *strict*
    improvement replaces the incumbent), so the fold is bitwise chunk-size
    invariant — the property that lets a sharded million-point sweep name
    one winning policy deterministically.
    """

    metric: object
    mode: str = "min"

    def __post_init__(self):
        if self.mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {self.mode!r}")

    def init(self, chunk_avals, res_avals):
        _metric_aval(self.metric, res_avals)
        row = jax.tree.map(
            lambda a: jnp.zeros(a.shape[1:], a.dtype), chunk_avals.policy
        )
        # carry best in sign space: always minimize sign * metric
        return (jnp.asarray(INF, jnp.float32), jnp.asarray(-1, jnp.int32),
                row)

    def fold(self, carry, chunk, res, index, valid):
        best, best_idx, best_row = carry
        sign = 1.0 if self.mode == "min" else -1.0
        v = _metric_fn(self.metric)(res).astype(jnp.float32)
        masked = jnp.where(valid, sign * v, INF)
        local = jnp.argmin(masked)           # first occurrence: lowest index
        cand = masked[local]
        improved = cand < best               # strict: incumbent wins ties
        best = jnp.where(improved, cand, best)
        best_idx = jnp.where(improved, index[local], best_idx)
        best_row = jax.tree.map(
            lambda leaf, old: jnp.where(improved, leaf[local], old),
            chunk.policy, best_row,
        )
        return (best, best_idx, best_row)

    def finalize(self, carry):
        best, best_idx, best_row = carry
        sign = 1.0 if self.mode == "min" else -1.0
        return {"value": sign * best, "index": best_idx, "policy": best_row}


@dataclasses.dataclass(frozen=True)
class ValuesReducer(CampaignReducer):
    """Scatter one scalar metric per scenario into a fixed ``[n_slots]``
    table — all of a campaign's scores without its ``[N, ...]`` results.

    The search driver's workhorse (core/search.py): ``n_slots`` stays the
    initial population size across successive-halving rungs, so every rung
    folds through the same compiled chunk program (simlint R5).  Scatters
    at distinct indices commute, so the table is bitwise chunk-size
    invariant.
    """

    metric: object
    n_slots: int

    def init(self, chunk_avals, res_avals):
        aval = _metric_aval(self.metric, res_avals)
        return (jnp.zeros((self.n_slots,), aval.dtype),
                jnp.zeros((self.n_slots,), bool))

    def fold(self, carry, chunk, res, index, valid):
        values, filled = carry
        v = _metric_fn(self.metric)(res)
        safe = jnp.where(valid, index, self.n_slots)  # OOB rows drop
        return (values.at[safe].set(v, mode="drop"),
                filled.at[safe].set(True, mode="drop"))

    def finalize(self, carry):
        values, filled = carry
        return {"values": values, "filled": filled}
