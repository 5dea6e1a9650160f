"""The per-phase metrics of the event step, on a trace built by hand: each
reads the device time under its scope per scenario, counts ops nested under
one scope once, and reads nothing where its scope is absent (as on a
program that predates the scope)."""
import pytest

from bench.harness import trace as tr
from bench.harness.cell import Reading
from bench.harness.spec import metric_reader

pytestmark = pytest.mark.tier1

BODY = "jit(_run_chunk_fold)/while/body"
SCOPES = {
    "phase_prologue_us": "phase_prologue",
    "phase_bound_us": "phase_bound",
    "phase_advance_us": "phase_advance",
    "phase_commit_us": "phase_commit",
    "loop_cond_us": "loop_cond",
    "phase_freeze_us": "phase_freeze",
}


def _reading(ops, scopes, n_scenarios=4):
    t = tr.Trace(ops={0: sorted(ops, key=lambda o: (o.start, -o.end))},
                 spans=[("run_campaign", 0.0, 1.0), ("wait", 1.0, 10.0)],
                 scopes={1: scopes})
    return Reading(trace=t, devices=[0], lo=0.0, hi=10.0,
                   n_scenarios=n_scenarios, iterations=2)


def _one_op_per_phase():
    """One op under each scope, the i-th lasting (i + 1) * 0.1 s."""
    ops = [tr.Op(0.0, 9.0, "while.1", 1)]
    scopes, t = {"while.1": "jit(f)/while"}, 0.0
    for i, scope in enumerate(SCOPES.values()):
        name = f"fusion.{i}"
        where = "cond" if scope == "loop_cond" else "body"
        ops.append(tr.Op(t, t + 0.1 * (i + 1), name, 1))
        scopes[name] = f"jit(f)/while/{where}/{scope}/vmap()/gather"
        t += 0.1 * (i + 1)
    return ops, scopes


@pytest.mark.parametrize("metric", sorted(SCOPES))
def test_reads_its_scope_per_scenario(metric):
    ops, scopes = _one_op_per_phase()
    i = list(SCOPES).index(metric)
    r = _reading(ops, scopes)
    # (i + 1) * 0.1 s over 4 scenarios
    assert metric_reader(metric)(r) == pytest.approx(1e6 * 0.1 * (i + 1) / 4)


@pytest.mark.parametrize("metric", sorted(SCOPES))
def test_reads_nothing_without_its_scope(metric):
    ops, scopes = _one_op_per_phase()
    absent = SCOPES[metric]
    scopes = {k: v.replace(f"/{absent}/", "/") for k, v in scopes.items()}
    assert metric_reader(metric)(_reading(ops, scopes)) is None
    # a scope opened inside a vmapped function is not the scope
    vmapped = {k: v.replace("/vmap()/", f"/vmap({absent})/")
               for k, v in scopes.items()}
    assert metric_reader(metric)(_reading(ops, vmapped)) is None


def test_nested_ops_count_once():
    # a commit fusion holding a vmapped gather and a scatter-add that
    # overlaps it: 2.0 s of commit, not 2.0 + 0.5 + 1.0
    ops = [tr.Op(0.0, 9.0, "while.1", 1),
           tr.Op(1.0, 3.0, "fusion.1", 1),
           tr.Op(1.5, 2.0, "gather.2", 1),
           tr.Op(2.0, 3.0, "scatter.3", 1),
           tr.Op(4.0, 4.5, "fusion.4", 1)]
    scopes = {"while.1": "jit(f)/while",
              "fusion.1": f"{BODY}/phase_commit",
              "gather.2": f"{BODY}/phase_commit/vmap()/gather",
              "scatter.3": f"{BODY}/phase_commit/vmap()/scatter-add",
              "fusion.4": f"{BODY}/phase_bound/reduce_min"}
    r = _reading(ops, scopes, n_scenarios=2)
    assert metric_reader("phase_commit_us")(r) == pytest.approx(1e6 * 2.0 / 2)
    assert metric_reader("phase_bound_us")(r) == pytest.approx(1e6 * 0.5 / 2)
    assert metric_reader("phase_prologue_us")(r) is None


def test_no_scenarios_reads_nothing():
    ops, scopes = _one_op_per_phase()
    r = _reading(ops, scopes, n_scenarios=0)
    for metric in SCOPES:
        assert metric_reader(metric)(r) is None
