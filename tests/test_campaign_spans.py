"""The streaming front door's host spans, read back from a profiler trace
by the benchmark's own reader.

``run_campaign(reduce=...)`` marks its host work with
``jax.profiler.TraceAnnotation``s on the device trace's clock, so the chip's
idle time between chunk programs can be split by what the host was doing:
preparing the sweep once, then per chunk slicing the rows, placing them on
a mesh (only with one), and launching the fold, then finalizing.
"""
import glob
import os

import jax
import pytest

from bench.harness import trace as tr
from repro.core import campaign, reducers, scenarios
from repro.core.entities import SPACE_SHARED, TIME_SHARED

pytestmark = pytest.mark.tier1

CALLER = "run_campaign"


def _grid(n):
    rows = [scenarios.fig4_scenario(
        SPACE_SHARED if i % 2 else TIME_SHARED, TIME_SHARED,
        length_mi=1000.0 + 250.0 * i) for i in range(n)]
    return campaign.stack_scenarios(rows)


def test_spans_nest_inside_the_callers_span(tmp_path):
    grid = _grid(8)
    reduce = {"events": reducers.SumReducer("n_events")}

    def sweep():
        return campaign.run_campaign(grid, chunk_size=4, reduce=reduce)

    jax.block_until_ready(sweep())              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(CALLER):
            out = sweep()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    assert int(out["events"]) > 0
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    t = tr.load(tr.read_file(path), {CALLER, *campaign.HOST_SPANS})

    (_, lo, hi), = [s for s in t.spans if s[0] == CALLER]
    inner = [s for s in t.spans if s[0] != CALLER]
    assert [n for n, _, _ in inner] == [
        campaign.SPAN_PREPARE,
        campaign.SPAN_SLICE, campaign.SPAN_LAUNCH,
        campaign.SPAN_SLICE, campaign.SPAN_LAUNCH,
        campaign.SPAN_FINALIZE,
    ]                                           # no SPAN_PUT without a mesh
    for _, s, e in inner:
        assert lo <= s <= e <= hi
    for (_, _, e), (_, s, _) in zip(inner, inner[1:]):
        assert e <= s                           # one after another
