"""The trace-to-metric reduction, on constructed intervals and on a trace
recorded on one TPU v5e chip: one ``simulate`` call of the Figure 4
scenario, with the harness's ``dispatch`` and ``wait`` host spans."""
import pytest

from bench.harness import trace as tr
from bench.harness.cell import Reading, breakdown
from bench.harness.spec import BENCH, metric_reader

pytestmark = pytest.mark.tier1

RECORDED = BENCH / "tests" / "data" / "fig4_1call.xplane.pb.gz"


def test_union_covered_gaps():
    merged = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.5)])
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.covered(merged, 1.0, 3.5) == pytest.approx(1.5)
    assert tr.covered(merged, -1.0, 10.0) == pytest.approx(3.0)
    assert tr.gaps(merged, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_nested_ops_count_once():
    ops = {0: [tr.Op(0.0, 10.0, "while.1", 1), tr.Op(1.0, 2.0, "fusion.1", 1),
               tr.Op(1.5, 3.0, "fusion.2", 1)]}
    scopes = {1: {"while.1": "jit(f)/while", "fusion.1": "jit(f)/while/body/phase_provision/x",
                  "fusion.2": "jit(f)/while/body/phase_dispatch"}}
    t = tr.Trace(ops=ops, spans=[("dispatch", 0.0, 0.5), ("wait", 0.5, 12.0)],
                 scopes=scopes)
    r = Reading(trace=t, devices=[0], lo=0.0, hi=12.0, n_scenarios=2, iterations=4)
    assert r.busy_s == pytest.approx(10.0)
    assert r.scope_s("phase_provision") == pytest.approx(1.0)
    assert metric_reader("device_idle_share")(r) == pytest.approx(100 * 2 / 12)
    assert metric_reader("device_us_per_step")(r) == pytest.approx(2.5e6)
    assert metric_reader("phase_provision_us")(r) == pytest.approx(0.5e6)
    assert metric_reader("phase_dispatch_us")(r) == pytest.approx(0.75e6)
    assert metric_reader("front_door_gap_ms")(r) is None      # no sweep span
    b = breakdown(r)
    assert [k.split()[0] for k, _ in b["device_ops"]] == ["fusion.2", "fusion.1"]
    assert dict(b["idle_gaps"]) == {"wait": pytest.approx(2.0)}


def test_front_door_gap_is_idle_time_inside_the_sweep_span():
    ops = {0: [tr.Op(1.0, 2.0, "a", 1), tr.Op(3.0, 3.5, "b", 1)],
           1: [tr.Op(0.5, 3.5, "a", 1)]}
    t = tr.Trace(ops=ops, spans=[("run_campaign", 0.0, 3.0), ("wait", 3.0, 3.5)])
    r = Reading(trace=t, devices=[0, 1], lo=0.0, hi=3.5,
                n_scenarios=8, iterations=2)
    # chip 0 idles 2.0 s inside the span, chip 1 0.5 s: mean 1.25 s
    assert metric_reader("front_door_gap_ms")(r) == pytest.approx(1250.0)


def test_recorded_trace():
    t = tr.load(tr.read_file(RECORDED), {"dispatch", "wait"})
    assert [n for n, _, _ in t.spans] == ["dispatch", "wait"]
    assert list(t.ops) == [0] and len(t.ops[0]) == 906
    (program, names), = t.scopes.items()
    assert {o.program for o in t.ops[0]} == {program}
    assert names["while.56"].endswith("/while")
    lo, hi = t.spans[0][1], t.spans[-1][2]
    busy = t.busy(0)
    assert tr.covered(busy, lo, hi) == pytest.approx(2.3566624e-4, rel=1e-6)
    assert tr.covered(t.in_scope(0, "phase_provision"), lo, hi) == pytest.approx(
        5.6626562e-05, rel=1e-6)
    assert tr.covered(t.in_scope(0, "phase_dispatch"), lo, hi) == pytest.approx(
        3.242812e-06, rel=1e-6)
    # the first op runs after the dispatch span began, inside the window
    assert lo < t.ops[0][0].start < hi
    r = Reading(trace=t, devices=[0], lo=lo, hi=hi, n_scenarios=1,
                iterations=4)
    share = metric_reader("device_idle_share")(r)
    assert 0.0 < share < 100.0
    b = breakdown(r)
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert {k for k, _ in b["idle_gaps"]} <= {"dispatch", "wait", "between spans"}
