"""Every phase of the event step carries a named scope on the compiled
program, where a profiler trace can find it.

A device op's scope is read from its HLO ``op_name`` metadata, one path
component per ``jax.named_scope``; the benchmark's trace reader
(``bench/harness/trace.py``) matches a scope only as a whole component.  So
each scope of ``step.TRACE_SCOPES`` is opened at the call site, around a
phase's ``jax.vmap`` on the batch path: opened inside a vmapped function it
would render as ``vmap(phase_bound)`` and match nothing.

Three programs are compiled on the CPU: ``simulate`` of one small scenario
(simlint's own, which carries a topology so the transfer phase exists), of
an 8-row stacked campaign (the batch-major step), and the streaming fold
program that ``run_campaign(reduce=...)`` dispatches per chunk.

The last tests read which tables the batch step still gathers from: the
bound's and the loop condition's per-VM and per-cloudlet tables are read
by ``segments.lookup``'s one-hot form up to ``segments.ONE_HOT_MAX``
entries, and by a gather above it.
"""
import math
import re

import jax
import numpy as np
import pytest

from repro.analysis import simlint
from repro.analysis.hlo_walk import HloWalker
from repro.core import campaign, engine, reducers, scenarios, segments, step
from repro.core.entities import SPACE_SHARED, TIME_SHARED

pytestmark = pytest.mark.tier1

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_SHAPE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]")
_CALLEE = re.compile(
    r"\b(condition|body|calls|true_computation|false_computation)="
    r"%?([\w.\-]+)|branch_computations=\{([^}]*)\}")

# The ops whose device time the trace reader attributes.
CHECKED = ("fusion", "reduce", "gather", "scatter", "sort", "custom-call")
# What the step's scopes cover inside the loop: the conditioned phases, the
# other phases of the body, and the condition.
STEP_SCOPES = step.PHASE_SCOPES + (
    step.SCOPE_PROLOGUE, step.SCOPE_BOUND, step.SCOPE_ADVANCE,
    step.SCOPE_COMMIT, step.SCOPE_LOOP_COND, step.SCOPE_FREEZE,
)
# Fusions that XLA builds itself, with an empty op_name, named by the opcode
# at their root: the CPU backend wraps a lone broadcast, reduce-window or
# dynamic-slice into a fusion of its own; ``lax.scan`` lowering (the
# provisioning scan) adds a concatenate of loop-carried indices and the
# reduce that range-checks them.
XLA_INSERTED = ("broadcast", "reduce-window", "dynamic-slice", "concatenate",
                "reduce")

_SINGLE = (step.SCOPE_PROLOGUE, step.SCOPE_BOUND, step.SCOPE_ADVANCE,
           step.SCOPE_COMMIT, step.SCOPE_LOOP_COND, step.SCOPE_INIT,
           step.SCOPE_FINALIZE)
EXPECTED = {
    "single": _SINGLE,
    "batch": _SINGLE + (step.SCOPE_FREEZE,),
    "fold": step.TRACE_SCOPES,               # the chunk program runs them all
}


def _parse(hlo: str) -> dict:
    """Computation name -> [(instruction name, opcode, line)]."""
    comps = {}
    for name, lines in HloWalker(hlo).comps.items():
        ins = comps[name] = []
        for line in lines:
            m = _INSTRUCTION.match(line)
            if m:
                ins.append((m.group(1), _opcode(m.group(2)), line))
    return comps


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side: what follows its
    result type, which may be a tuple holding ``/*index=5*/`` comments."""
    depth = 0
    for i, ch in enumerate(rhs):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"\s*([\w\-]+)\(", rhs[i:])
            return m.group(1) if m else ""
    return ""


def _op_name(line: str) -> str:
    m = _OP_NAME.search(line)
    return m.group(1) if m else ""


def _callees(line: str, opcode: str):
    for m in _CALLEE.finditer(line):
        if m.group(1) == "calls" and opcode == "fusion":
            continue                      # fused bodies belong to the fusion
        if m.group(2):
            yield m.group(2)
        else:
            yield from (c.strip().lstrip("%") for c in m.group(3).split(","))


def _loop_computations(comps: dict) -> set:
    """The event loop's condition and body, and every computation they
    reach through control flow (phase branches, the provisioning scan)."""
    roots = []
    for ins in comps.values():
        for _, opcode, line in ins:
            name = _op_name(line).split("/")
            if opcode == "while" and name.count("while") == 1:
                roots += re.findall(r"\b(?:condition|body)=%?([\w.\-]+)", line)
    assert roots, "no event-loop while in the program"
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for _, opcode, line in comps[c]:
            todo.extend(_callees(line, opcode))
    return seen


def _fused_root(comps: dict, line: str) -> str:
    callee = re.search(r"\bcalls=%?([\w.\-]+)", line).group(1)
    return comps[callee][-1][1]


def _fold_hlo() -> str:
    """The chunk program that a streaming ``run_campaign`` dispatches,
    lowered with the very arguments of its first call."""
    rows = [scenarios.fig4_scenario(
        SPACE_SHARED if i % 2 else TIME_SHARED, TIME_SHARED,
        length_mi=1000.0 + 500.0 * i) for i in range(16)]
    grid = campaign.stack_scenarios(rows)
    texts = []
    fold = campaign._run_chunk_fold

    def recording(*args):
        if not texts:
            texts.append(fold.lower(*args).compile().as_text())
        return fold(*args)

    mp = pytest.MonkeyPatch()
    mp.setattr(campaign, "_run_chunk_fold", recording)
    try:
        out = campaign.run_campaign(
            grid, chunk_size=8,
            reduce={"events": reducers.SumReducer("n_events"),
                    "best": reducers.ArgBestReducer("mean_turnaround")})
    finally:
        mp.undo()
    assert int(out["events"]) > 0
    return texts[0]


@pytest.fixture(scope="module")
def lint():
    return simlint.LintContext()


@pytest.fixture(scope="module")
def programs(lint):
    rows = [scenarios.fig4_scenario(
        SPACE_SHARED if i % 2 else TIME_SHARED, SPACE_SHARED,
        length_mi=1000.0 + 250.0 * i) for i in range(8)]
    batch = campaign.stack_scenarios(rows)
    return {
        "single": lint.hlo("simulate"),
        "batch": jax.jit(engine.simulate).lower(batch).compile().as_text(),
        "fold": _fold_hlo(),
    }


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_each_scope_is_a_whole_path_component(programs, program):
    names = {_op_name(line) for line in programs[program].splitlines()}
    for scope in EXPECTED[program]:
        # the trace reader's own rule (Trace.in_scope)
        assert any(scope in n.split("/") for n in names), (program, scope)


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_no_scope_is_swallowed_by_a_vmap(programs, program):
    for line in programs[program].splitlines():
        name = _op_name(line)
        for bad in ("vmap(phase_", "vmap(loop_cond", "vmap(sim_"):
            assert bad not in name, (program, line.strip()[:200])


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_every_loop_op_carries_a_step_scope(programs, program):
    comps = _parse(programs[program])
    checked = 0
    for c in _loop_computations(comps):
        for name, opcode, line in comps[c]:
            if opcode not in CHECKED:
                continue
            checked += 1
            op_name = _op_name(line)
            if not op_name:
                assert opcode == "fusion", (program, name)
                assert _fused_root(comps, line) in XLA_INSERTED, (program, name)
                continue
            parts = op_name.split("/")
            assert any(s in parts for s in STEP_SCOPES), (program, name, op_name)
    assert checked > 50, (program, checked)


def test_simlint_r1_still_passes(lint):
    findings = simlint.RULES["R1"].fn(lint)
    assert [f for f in findings if f.severity == "error"] == []


# --- small-table lookups ----------------------------------------------------

def _gathered_tables(hlo: str, scopes: tuple) -> list[int]:
    """Entries per row of the table read by each gather under ``scopes``:
    the gather's operand with its leading (batch) axis left out."""
    sizes = []
    for lines in HloWalker(hlo).comps.values():
        dims = {}
        for line in lines:
            m = _SHAPE.match(line)
            if m:
                dims[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
        for line in lines:
            m = _INSTRUCTION.match(line)
            if not m or _opcode(m.group(2)) != "gather":
                continue
            if not any(s in _op_name(line).split("/") for s in scopes):
                continue
            operand = re.search(r"\bgather\(%?([\w.\-]+)", line).group(1)
            sizes.append(math.prod(dims[operand][1:]))
    return sizes


def _fig4_rows(n_cloudlets: int) -> list:
    """Figure 4's host and VMs, ``n_cloudlets`` tasks dealt to both VMs."""
    rows = []
    for vp in (SPACE_SHARED, TIME_SHARED):
        base = scenarios.fig4_scenario(SPACE_SHARED, vp)
        cls = scenarios.make_cloudlets(
            np.arange(n_cloudlets) % 2, np.full(n_cloudlets, 4000.0),
            np.zeros(n_cloudlets), input_mb=0.0, output_mb=0.0)
        rows.append(base.replace(cloudlets=cls))
    return rows


# (rows, host grid entries) of the batch programs the sweep cells run:
# fig4 (8 tasks, 2 VMs, 1 host), fig9_10 (500 tasks, 50 VMs, 10,000 hosts)
SHAPED = {
    "fig4": lambda: (_fig4_rows(8), 1),
    "fig9_10": lambda: ([scenarios.fig9_10_scenario(vp)
                         for vp in (SPACE_SHARED, TIME_SHARED)], 10_000),
}


def _batch_gathers(rows) -> list[int]:
    hlo = jax.jit(engine.simulate).lower(
        campaign.stack_scenarios(rows)).compile().as_text()
    return _gathered_tables(hlo, (step.SCOPE_BOUND, step.SCOPE_LOOP_COND))


@pytest.mark.parametrize("shape", sorted(SHAPED))
def test_bound_and_loop_cond_gather_only_the_host_grid(shape):
    """No per-VM or per-cloudlet table is gathered under ``phase_bound``
    or ``loop_cond``; what is left reads the host grid
    (``policies.host_level_mips``), which ``segments.lookup`` does not
    serve."""
    rows, grid = SHAPED[shape]()
    sizes = _batch_gathers(rows)
    assert sizes and set(sizes) == {grid}, sizes


def test_a_table_above_the_limit_keeps_its_gather():
    """With more cloudlets than ``ONE_HOT_MAX`` the cloudlet permutation of
    ``segment_prefix_sum`` is gathered again; the 2-VM tables stay one-hot."""
    n = segments.ONE_HOT_MAX + 1
    sizes = _batch_gathers(_fig4_rows(n))
    assert sorted(s for s in sizes if s != 1) == [n, n], sizes
