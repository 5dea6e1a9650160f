"""simlint — a static verifier of the engine's structural invariants.

Every performance and correctness claim the engine makes rests on
*structural* properties of the compiled program that ordinary tests cannot
see: the batch-major win requires the phase predicates to lower to real HLO
``conditional``s (not ``select``), campaign donation must actually produce
input/output aliasing, the trace/history bitwise-equality contract requires
instruments to be effect-free observers, and the one-compiled-program
property requires policy knobs to stay traced.  A silent XLA lowering change
or an accidental ``io_callback`` would regress any of them without a test
failing — the numbers would still be right, just slower or un-sweepable.

simlint turns those implicit invariants into machine-checked ones: it traces
the engine's entry points (``simulate`` / ``simulate_trace`` /
``simulate_history``, the batch-major path, ``run_campaign`` chunks, and the
Pallas advance kernel in interpret mode) to jaxpr and optimized HLO, then
runs a registry of rules, each emitting structured ``Finding``s.

Rules (DESIGN.md §11):

=====  ==================  =====================================================
R1     cond-not-select     the provision/dispatch phase predicates survive as
                           ``conditional`` ops with branch computations in the
                           optimized HLO of both engine paths (DESIGN.md §10)
R2     donation-aliases    the campaign chunk runner's compiled module aliases
                           every ``_donate_mask``-donatable input to an output
                           — on the local chunk AND through the shard_map
                           lowering (DESIGN.md §6; the PR-2 never-aliased
                           regression)
R3     pure-observer       driver jaxprs and every Instrument hook carry no
                           effects — no ``io_callback``/``debug_callback``/
                           ``pure_callback``/``debug.print`` (DESIGN.md §3)
R4     shape-stable-scan   no dynamic-shape ops or data-dependent slice widths
                           anywhere in the traced program; ``[B]``-leaf
                           structure is rank-consistent between the single and
                           batch paths (DESIGN.md §10)
R5     recompile-hazard    tracing the same entry across two scenario
                           constructions hits the jit cache — one compilation
                           — and a successive-halving run's rungs all re-enter
                           one compiled streaming-fold program (the
                           one-compiled-program property, DESIGN.md §5/§12)
R6     kernel-budget       the fused advance kernel's launch plan respects the
                           ``ops.advance_block`` heuristic bounds, tiles eight
                           sublane rows per grid step and declares its ``[B]``
                           SMEM operands scalar-per-row
=====  ==================  =====================================================

The rule bodies are thin wrappers over pure ``check_*`` functions operating
on artifacts (HLO text, jaxprs, kernel plans), so tests can feed adversarial
programs — a vmapped (select-lowered) cond, an undonated runner, a noisy
instrument — and prove each rule fires (tests/test_simlint.py).

CLI: ``scripts/simlint.py`` (human-readable report, ``--json`` for CI,
``--rule``/``--entry`` filters, nonzero exit on error-severity findings).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Iterable

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# findings + rule registry
# ---------------------------------------------------------------------------

SEVERITIES = ("error", "warning", "info")


@dataclasses.dataclass
class Finding:
    """One structured lint result."""

    rule: str          # "R1" ... "R6"
    name: str          # rule slug, e.g. "cond-not-select"
    severity: str      # "error" | "warning" | "info"
    entry_point: str   # entry (or "instrument:<name>.<hook>") it was found in
    message: str       # what is wrong (or noteworthy)
    evidence: str = ""  # HLO/jaxpr excerpt backing the finding

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Rule:
    rule: str
    name: str
    entries: tuple     # entry points this rule reads (for --entry filtering)
    fn: Callable       # fn(ctx) -> list[Finding]
    doc: str


RULES: dict[str, Rule] = {}


def rule(rule_id: str, name: str, entries: tuple):
    def deco(fn):
        RULES[rule_id] = Rule(
            rule=rule_id, name=name, entries=entries, fn=fn,
            doc=(fn.__doc__ or "").strip().split("\n")[0],
        )
        return fn
    return deco


def _finding(rule_id: str, severity: str, entry: str, message: str,
             evidence: str = "") -> Finding:
    spec = RULES[rule_id]
    return Finding(rule=rule_id, name=spec.name, severity=severity,
                   entry_point=entry, message=message,
                   evidence=evidence.strip()[:500])


# ---------------------------------------------------------------------------
# the lint context: entry points traced lazily, artifacts cached
# ---------------------------------------------------------------------------

# Entry points traced by the default lint run.  ``batch`` is ``simulate`` on
# a stacked campaign (the batch-major step loop); ``campaign_chunk`` is the
# donating chunk runner's compiled module; ``campaign_sharded`` is the same
# chunk lowered through the ``shard_map`` runner on a 1-device ``data`` mesh
# (the sharded-campaign path of DESIGN.md §12 — R1/R2 re-verify that phase
# conditionals and buffer aliasing survive the shard_map lowering, and R5
# probes that successive-halving rungs re-enter one compiled fold program);
# ``advance_pallas`` is the fused advance kernel in interpret mode.
ENTRY_NAMES = (
    "simulate",
    "simulate_trace",
    "simulate_history",
    "batch",
    "campaign_chunk",
    "campaign_sharded",
    "advance_pallas",
)

_BATCH = 4          # rows in the stacked-campaign entry
_TRACE_SAMPLES = 4  # sample points for the simulate_trace entry


class LintContext:
    """Lazily builds and caches the traced/compiled artifacts rules read.

    Tracing and compiling the engine is the expensive part of a lint run, so
    every artifact is computed at most once; ``entries`` restricts which
    entry points may be traced at all (the ``--entry`` CLI filter).
    """

    def __init__(self, entries: Iterable[str] | None = None):
        self.allowed = tuple(entries) if entries else ENTRY_NAMES
        unknown = set(self.allowed) - set(ENTRY_NAMES)
        if unknown:
            raise ValueError(
                f"unknown entry point(s) {sorted(unknown)}; "
                f"known: {list(ENTRY_NAMES)}"
            )
        self._cache: dict = {}

    def wants(self, entry: str) -> bool:
        return entry in self.allowed

    # -- scenarios ---------------------------------------------------------
    @staticmethod
    def _with_topology(scn):
        """Attach a 1-DC uniform topology so the transfer phase
        (step.SCOPE_TRANSFER) exists in every linted program, and a small
        power-aware consolidation so the consolidation phase
        (step.SCOPE_CONSOLIDATE) does — all lint scenarios carry both,
        keeping R5's structure-identity probe intact."""
        import numpy as np

        from repro.core.consolidate import Consolidation, ConsolidationPolicy
        from repro.core.energy import Topology

        cap = np.asarray(scn.hosts.cores * scn.hosts.mips)
        consolidation = Consolidation.build(
            jnp.full((scn.vms.n_vms, 4), 50, jnp.int32),
            np.zeros(cap.shape, np.int64),
            [[86, 89.4, 92.6, 96, 99.5, 102, 106, 108, 112, 114, 117]], cap,
            ConsolidationPolicy(detector=jnp.int32(0),
                                param=jnp.float32(0.8)), 100.0)
        return dataclasses.replace(scn, topology=Topology.uniform(1),
                                   dynamic_consolidation=consolidation)

    def scenario(self, **kw):
        """The canonical single-scenario lint subject (paper Figure 4)."""
        from repro.core import scenarios
        from repro.core.entities import SPACE_SHARED
        key = ("scn", tuple(sorted(kw.items())))
        if key not in self._cache:
            base = self._with_topology(
                scenarios.fig4_scenario(SPACE_SHARED, SPACE_SHARED))
            self._cache[key] = base.replace(**kw) if kw else base
        return self._cache[key]

    def scenario_variant(self):
        """Same shapes/statics as ``scenario()``, different traced values —
        the R5 cache-hit probe."""
        from repro.core import scenarios
        from repro.core.entities import TIME_SHARED
        if "scn_variant" not in self._cache:
            self._cache["scn_variant"] = self._with_topology(
                scenarios.fig4_scenario(
                    TIME_SHARED, TIME_SHARED, length_mi=1000.0))
        return self._cache["scn_variant"]

    def batch_scenario(self):
        """A small stacked campaign (batch-major path)."""
        from repro.core import campaign, scenarios
        from repro.core.entities import SPACE_SHARED
        if "scn_batch" not in self._cache:
            rows = [
                self._with_topology(scenarios.fig4_scenario(
                    SPACE_SHARED, SPACE_SHARED, length_mi=float(m)
                ))
                for m in (1000.0, 2000.0, 3000.0, 4000.0)[:_BATCH]
            ]
            self._cache["scn_batch"] = campaign.stack_scenarios(rows)
        return self._cache["scn_batch"]

    def mesh(self):
        """A 1-device ``data`` mesh: exercises the full shard_map lowering
        (partitioned module, pspec plumbing, donation-through-shards) while
        staying runnable on any host."""
        if "mesh" not in self._cache:
            from jax.sharding import Mesh
            self._cache["mesh"] = Mesh(jax.devices()[:1], ("data",))
        return self._cache["mesh"]

    # -- entry callables ---------------------------------------------------
    def _entry_fn_args(self, entry: str):
        from repro.core import engine
        from repro.kernels import ops
        if entry == "simulate":
            return engine.simulate, (self.scenario(),)
        if entry == "simulate_trace":
            ts = jnp.linspace(0.0, 400.0, _TRACE_SAMPLES)
            return (lambda scn: engine.simulate_trace(scn, ts),
                    (self.scenario(),))
        if entry == "simulate_history":
            return engine.simulate_history, (self.scenario(),)
        if entry == "batch":
            return engine.simulate, (self.batch_scenario(),)
        if entry == "campaign_sharded":
            from repro.core import campaign
            mesh = self.mesh()
            return (lambda scn: campaign._sharded_simulate(scn, mesh, "data"),
                    (self.batch_scenario(),))
        if entry == "advance_pallas":
            b, c = _BATCH, 96
            args = (
                jnp.ones((b, c), jnp.float32),          # rem
                jnp.ones((b, c), jnp.float32),          # rate
                jnp.ones((b, c), bool),                 # active
                jnp.full((b,), 10.0, jnp.float32),      # bound_dt
            )
            return ops.advance_sweep, args
        raise KeyError(f"no traced callable for entry {entry!r}")

    # -- artifacts ---------------------------------------------------------
    def jaxpr(self, entry: str):
        key = ("jaxpr", entry)
        if key not in self._cache:
            fn, args = self._entry_fn_args(entry)
            self._cache[key] = jax.make_jaxpr(fn)(*args)
        return self._cache[key]

    def hlo(self, entry: str) -> str:
        """Optimized (post-XLA) HLO text of the compiled entry."""
        key = ("hlo", entry)
        if key not in self._cache:
            if entry in ("campaign_chunk", "campaign_sharded"):
                from repro.core import campaign
                mesh = self.mesh() if entry == "campaign_sharded" else None
                txt, n_donated = campaign.lower_chunk(
                    self.batch_scenario(), mesh=mesh
                )
                self._cache[key] = txt
                self._cache[("n_donated", entry)] = n_donated
            else:
                fn, args = self._entry_fn_args(entry)
                self._cache[key] = (
                    jax.jit(fn).lower(*args).compile().as_text()
                )
        return self._cache[key]

    def n_donated(self, entry: str = "campaign_chunk") -> int:
        self.hlo(entry)
        return self._cache[("n_donated", entry)]


# ---------------------------------------------------------------------------
# pure checkers (the testable cores)
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONDITIONAL = re.compile(r"=\s*[^=]*\bconditional\(")
_SELECT = re.compile(r"\bselect(?:-and-scatter)?\(|\bselect\b")
_ALIAS_ENTRY = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")


def _alias_table(header: str) -> str | None:
    """The brace-balanced body of ``input_output_alias={...}`` in an HLO
    module header, or None if the module declares no aliasing."""
    tag = "input_output_alias={"
    start = header.find(tag)
    if start < 0:
        return None
    i, depth = start + len(tag), 1
    for j in range(i, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                return header[i:j]
    return None


def _scoped_lines(hlo_text: str, scope: str) -> list[str]:
    # a named_scope shows up in op_name as a path component —
    # ".../phase_provision/cond" normally, "vmap(phase_provision)/..." when
    # a vmap swallowed it (the very degradation R1 reports)
    pat = re.compile(rf"(?:^|/|\(){re.escape(scope)}(?:$|/|\))")
    out = []
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and pat.search(m.group(1)):
            out.append(line.strip())
    return out


def check_cond_not_select(
    hlo_text: str, scopes: Iterable[str], entry: str, rule_id: str = "R1"
) -> list[Finding]:
    """Each phase scope must appear on a ``conditional`` op (with branch
    computations) in the optimized HLO; a scope present only on ``select``
    ops — or absent entirely — means XLA flattened the predicate and both
    branches execute at every event."""
    findings = []
    for scope in scopes:
        lines = _scoped_lines(hlo_text, scope)
        conds = [
            ln for ln in lines
            if _CONDITIONAL.search(ln)
            and ("branch_computations=" in ln or "true_computation=" in ln)
        ]
        if conds:
            continue
        selects = [ln for ln in lines if "select" in ln]
        if selects:
            findings.append(_finding(
                rule_id, "error", entry,
                f"phase predicate scope {scope!r} was flattened to select "
                "(both branches execute at every event; the batch-major "
                "phase-skip win is gone)",
                selects[0],
            ))
        elif not lines:
            findings.append(_finding(
                rule_id, "error", entry,
                f"phase predicate scope {scope!r} not found in the "
                "optimized HLO — the cond was renamed, restructured, or "
                "optimized away entirely",
            ))
        else:
            findings.append(_finding(
                rule_id, "error", entry,
                f"phase predicate scope {scope!r} present but on no "
                "conditional op — lowering changed shape",
                lines[0],
            ))
    return findings


def check_donation_aliases(
    hlo_text: str, n_donated: int, entry: str, rule_id: str = "R2"
) -> list[Finding]:
    """The compiled module's ``input_output_alias`` table must cover the
    donated parameters.  Zero coverage is the PR-2 regression class (an
    error); partial coverage is a warning — an unaliased donated leaf whose
    matching output was constant-folded (e.g. ``downtime`` in a no-outage
    scenario) is benign but worth surfacing."""
    header = hlo_text.splitlines()[0] if hlo_text else ""
    table = _alias_table(header)
    aliased = (
        sorted({int(a) for a in _ALIAS_ENTRY.findall(table)})
        if table else []
    )
    if n_donated <= 0:
        return [_finding(
            rule_id, "error", entry,
            "no donatable leaves at all — _donate_mask matched nothing "
            "against the result avals",
        )]
    if not aliased:
        return [_finding(
            rule_id, "error", entry,
            f"0 of {n_donated} donatable leaves are aliased: buffer "
            "donation is a no-op and chunked campaigns pay double memory",
            header[:300],
        )]
    missing = [i for i in range(n_donated) if i not in aliased]
    if missing:
        return [_finding(
            rule_id, "warning", entry,
            f"{len(missing)} of {n_donated} donatable leaves not aliased "
            f"(donated arg indices {missing}); usually a constant-folded "
            "output, but check after touching SimResult/_donate_mask",
            header[:300],
        )]
    return []


_CALLBACK_PRIMS = (
    "io_callback", "pure_callback", "debug_callback", "debug_print",
)


def _walk_jaxpr_eqns(jaxpr):
    """Yield every eqn in a (Closed)Jaxpr, recursing into sub-jaxprs."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                if hasattr(x, "jaxpr") or hasattr(x, "eqns"):
                    yield from _walk_jaxpr_eqns(x)


def check_effects(closed_jaxpr, entry: str, rule_id: str = "R3") -> list[Finding]:
    """A driver/hook jaxpr must carry no effects: any effect (io_callback,
    debug print, ...) breaks the pure-observer contract that makes trace =
    history = plain run bitwise and lets XLA reorder freely."""
    findings = []
    effs = getattr(closed_jaxpr, "effects", None) or ()
    if effs:
        findings.append(_finding(
            rule_id, "error", entry,
            f"jaxpr carries effects {sorted(str(e) for e in effs)} — "
            "instruments must be pure observers (DESIGN.md §3)",
        ))
    for eqn in _walk_jaxpr_eqns(closed_jaxpr):
        if any(eqn.primitive.name.startswith(p) for p in _CALLBACK_PRIMS):
            findings.append(_finding(
                rule_id, "error", entry,
                f"callback primitive {eqn.primitive.name!r} in traced "
                "program",
                str(eqn)[:300],
            ))
    return findings


def check_shape_stability(closed_jaxpr, entry: str,
                          rule_id: str = "R4") -> list[Finding]:
    """Every intermediate must have a fully concrete shape, and every
    ``dynamic_slice``-family op must use static slice sizes: a data-dependent
    width would fork the compiled program per trajectory."""
    findings = []
    for eqn in _walk_jaxpr_eqns(closed_jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if not all(isinstance(d, int) for d in shape):
                findings.append(_finding(
                    rule_id, "error", entry,
                    f"non-concrete output shape {shape} from "
                    f"{eqn.primitive.name}",
                    str(eqn)[:300],
                ))
        if eqn.primitive.name in ("dynamic_slice", "dynamic_update_slice"):
            sizes = eqn.params.get("slice_sizes", ())
            if not all(isinstance(s, int) for s in sizes):
                findings.append(_finding(
                    rule_id, "error", entry,
                    f"data-dependent slice widths {sizes} in "
                    f"{eqn.primitive.name}",
                    str(eqn)[:300],
                ))
    return findings


def check_rank_consistency(single_shapes: dict, batch_shapes: dict,
                           batch: int, entry: str,
                           rule_id: str = "R4") -> list[Finding]:
    """Each batch-path SimState leaf must be exactly ``[B] + single`` — the
    contract that lets ``_freeze`` broadcast its row mask per leaf."""
    findings = []
    for path, s_shape in single_shapes.items():
        b_shape = batch_shapes.get(path)
        if b_shape is None:
            findings.append(_finding(
                rule_id, "error", entry,
                f"state leaf {path} exists on the single path only",
            ))
        elif tuple(b_shape) != (batch,) + tuple(s_shape):
            findings.append(_finding(
                rule_id, "error", entry,
                f"state leaf {path}: batch shape {tuple(b_shape)} != "
                f"({batch},) + single shape {tuple(s_shape)}",
            ))
    for path in batch_shapes:
        if path not in single_shapes:
            findings.append(_finding(
                rule_id, "error", entry,
                f"state leaf {path} exists on the batch path only",
            ))
    return findings


def check_one_compilation(jitted, n_calls_expected: int, entry: str,
                          rule_id: str = "R5") -> list[Finding]:
    """After calling a jitted entry on same-shape/same-static inputs, the jit
    cache must hold exactly one executable."""
    size_fn = getattr(jitted, "_cache_size", None)
    if size_fn is None:
        return [_finding(
            rule_id, "info", entry,
            "jit cache size is not inspectable on this jax version; "
            "recompile hazard not checked",
        )]
    n = size_fn()
    if n != 1:
        return [_finding(
            rule_id, "error", entry,
            f"{n} compilations for {n_calls_expected} same-shape calls — "
            "a traced value became static (policy knob? instrument field?) "
            "and forked the jit cache (one-compiled-program property, "
            "DESIGN.md §5)",
        )]
    return []


def check_rung_reuse(n_new_first: int, n_new_repeat: int, entry: str,
                     rule_id: str = "R5") -> list[Finding]:
    """Audit jit-cache *deltas* around a successive-halving run: the first
    run may add at most one executable (every rung — shrinking populations,
    changing fidelities — re-enters the same compiled fold program), and a
    repeat run with different knob values must add none.  Deltas rather than
    absolute sizes because the fold runner is a module-level jit whose cache
    is shared with every other campaign in the process."""
    findings = []
    if n_new_first > 1:
        findings.append(_finding(
            rule_id, "error", entry,
            f"successive-halving compiled {n_new_first} fold programs in "
            "one run — a rung's population/fidelity change forked the jit "
            "cache (fixed-slot ValuesReducer + pinned chunk_size broken?)",
        ))
    if n_new_repeat != 0:
        findings.append(_finding(
            rule_id, "error", entry,
            f"re-running the search with different knob values compiled "
            f"{n_new_repeat} new fold program(s) — a candidate knob became "
            "static (one-compiled-program property, DESIGN.md §5)",
        ))
    return findings


def check_kernel_plan(plan: dict, n_cloudlets: int, max_block: int,
                      entry: str, rule_id: str = "R6") -> list[Finding]:
    """Audit one advance-kernel launch plan against the ``advance_block``
    heuristic bounds, the sublane-aligned tile and the SMEM scalar-per-row
    contract."""
    from repro.kernels.vm_update import ROWS as rows

    findings = []
    block, b = plan["block"], plan["b"]

    def err(msg, ev=""):
        findings.append(_finding(rule_id, "error", entry, msg, ev))

    if block & (block - 1) or block <= 0:
        err(f"block {block} is not a power of two (C={n_cloudlets})")
    if block < 128:
        err(f"block {block} below the 128-lane floor (C={n_cloudlets})")
    if block > max_block:
        err(f"block {block} above the VMEM cap {max_block} "
            f"(C={n_cloudlets})")
    if n_cloudlets <= max_block and block < n_cloudlets:
        err(f"block {block} splits a row (C={n_cloudlets}) that fits the "
            "cap — the fused single-pass path was forfeited")
    if plan["padded_c"] % block:
        err(f"padded row {plan['padded_c']} not a multiple of block {block}")
    nb = plan["padded_c"] // block
    want_variant = "fused" if nb == 1 else "two_phase"
    if plan["variant"] != want_variant:
        err(f"variant {plan['variant']!r} but nb={nb} implies "
            f"{want_variant!r}")
    padded_b = plan["padded_b"]
    if padded_b % rows or not b <= padded_b < b + rows:
        err(f"padded batch {padded_b} is not B={b} rounded up to a "
            f"multiple of {rows} sublanes")
    groups = padded_b // rows
    want_grid = (groups,) if nb == 1 else (groups, 2, nb)
    if tuple(plan["grid"]) != want_grid:
        err(f"grid {tuple(plan['grid'])} != expected {want_grid}")
    if tuple(plan["tile"]) != (rows, block):
        err(f"tile {tuple(plan['tile'])} != ({rows}, {block}) — Mosaic "
            f"needs {rows} f32 sublanes per tile")
    for kind in ("smem_in", "smem_out"):
        for name, shape in plan[kind]:
            if tuple(shape) != (padded_b,):
                err(f"SMEM operand {name!r} has shape {tuple(shape)}; "
                    f"[B]=({padded_b},) scalars-per-row required")
    if plan["variant"] == "fused" and plan["vmem_scratch"]:
        err("fused variant declares VMEM scratch it never reads")
    return findings


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


@rule("R1", "cond-not-select",
      entries=("simulate", "batch", "campaign_sharded"))
def _rule_cond_not_select(ctx: LintContext) -> list[Finding]:
    """Phase predicates lower to real HLO conditionals, not select."""
    from repro.core import step
    findings = []
    # campaign_sharded re-checks the same property through the shard_map
    # lowering — a partitioner that flattened the conds would silently
    # forfeit phase skipping on every sharded campaign
    for entry in ("simulate", "batch", "campaign_sharded"):
        if not ctx.wants(entry):
            continue
        findings += check_cond_not_select(
            ctx.hlo(entry), step.PHASE_SCOPES, entry
        )
    return findings


@rule("R2", "donation-aliases", entries=("campaign_chunk", "campaign_sharded"))
def _rule_donation_aliases(ctx: LintContext) -> list[Finding]:
    """Campaign chunk donation produces real input/output aliasing."""
    findings = []
    for entry in ("campaign_chunk", "campaign_sharded"):
        if not ctx.wants(entry):
            continue
        findings += check_donation_aliases(
            ctx.hlo(entry), ctx.n_donated(entry), entry
        )
    return findings


def _instrument_hook_jaxprs(scn):
    """(label, ClosedJaxpr) for every hook of every engine instrument,
    including the trace/utilization observers the drivers attach."""
    from repro.core import engine, step

    ts = jnp.linspace(0.0, 400.0, _TRACE_SAMPLES)
    extras = (
        step.TraceInstrument(sample_ts=ts),
        step.UtilizationTimelineInstrument(sample_ts=ts),
    )
    instruments = step.instruments_for(scn, extras)
    st = engine.init_state(scn)
    C, V = scn.cloudlets.n_cloudlets, scn.vms.n_vms
    ev = step.StepEvent(
        t0=jnp.float32(0.0), t1=jnp.float32(1.0), dt=jnp.float32(1.0),
        kind=jnp.int32(0),
        rate=jnp.zeros((C,), jnp.float32),
        active=jnp.zeros((C,), bool),
        rem_before=jnp.zeros((C,), jnp.float32),
        newly_started=jnp.zeros((C,), bool),
        newly_finished=jnp.zeros((C,), bool),
        vm_mips=jnp.zeros((V,), jnp.float32),
    )
    out = []
    for ins in instruments:
        aux = ins.init(scn)
        hooks = {
            "pre": lambda st, aux, ins=ins: ins.pre(scn, st, aux),
            "bound": lambda st, aux, ins=ins: ins.bound(scn, st, aux),
            "post": lambda st, aux, ins=ins: ins.post(scn, st, ev, aux),
            "finalize": lambda st, aux, ins=ins: ins.finalize(scn, st, aux),
        }
        for hook, fn in hooks.items():
            out.append((
                f"instrument:{ins.name}.{hook}",
                jax.make_jaxpr(fn)(st, aux),
            ))
    return out


@rule("R3", "pure-observer",
      entries=("simulate", "simulate_trace", "simulate_history", "batch",
               "campaign_sharded"))
def _rule_pure_observer(ctx: LintContext) -> list[Finding]:
    """Drivers and instrument hooks carry no effects."""
    findings = []
    for entry in ("simulate", "simulate_trace", "simulate_history", "batch",
                  "campaign_sharded"):
        if not ctx.wants(entry):
            continue
        findings += check_effects(ctx.jaxpr(entry), entry)
    if ctx.wants("simulate"):
        for label, cj in _instrument_hook_jaxprs(ctx.scenario()):
            findings += check_effects(cj, label)
    return findings


def _shape_tree(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = tuple(leaf.shape)
    return out


@rule("R4", "shape-stable-scan",
      entries=("simulate", "batch", "campaign_sharded", "advance_pallas"))
def _rule_shape_stable(ctx: LintContext) -> list[Finding]:
    """All shapes static; SimState rank-consistent across engine paths."""
    from repro.core import engine
    findings = []
    for entry in ("simulate", "batch", "campaign_sharded", "advance_pallas"):
        if not ctx.wants(entry):
            continue
        findings += check_shape_stability(ctx.jaxpr(entry), entry)
    if ctx.wants("batch"):
        scn, scn_b = ctx.scenario(), ctx.batch_scenario()
        single = jax.eval_shape(engine.init_state, scn)
        batch = jax.eval_shape(jax.vmap(engine.init_state), scn_b)
        findings += check_rank_consistency(
            _shape_tree(single), _shape_tree(batch), _BATCH, "batch"
        )
    return findings


@rule("R5", "recompile-hazard",
      entries=("simulate", "batch", "campaign_sharded"))
def _rule_recompile_hazard(ctx: LintContext) -> list[Finding]:
    """Same entry, two scenario constructions, one compilation."""
    from repro.core import engine
    findings = []
    # each probe jits a *fresh* lambda: the pjit tracing cache is keyed on
    # the underlying callable, so two wrappers of engine.simulate itself
    # would pool their entries and double-count
    if ctx.wants("simulate"):
        f = jax.jit(lambda s: engine.simulate(s))
        f(ctx.scenario())
        f(ctx.scenario_variant())
        findings += check_one_compilation(f, 2, "simulate")
    if ctx.wants("batch"):
        from repro.core import campaign
        g = jax.jit(lambda s: engine.simulate(s))
        g(ctx.batch_scenario())
        g(campaign.broadcast_campaign(ctx.scenario_variant(), _BATCH))
        findings += check_one_compilation(g, 2, "batch")
    if ctx.wants("campaign_sharded"):
        # the search driver's rung-reuse claim: a whole successive-halving
        # run (shrinking populations, rising fidelities) through the sharded
        # streaming fold adds at most ONE executable to the fold runner's
        # cache, and a re-run with fresh knob values adds zero.  The fold
        # runner is a module-level jit, so measure deltas, not sizes.
        from repro.core import campaign, search
        size = campaign._run_chunk_fold._cache_size
        space = {"sensor_interval": (1.0, 2.0, 4.0),
                 "ckpt_interval": (50.0, 100.0)}
        kw = dict(n0=4, fidelities=(100.0, 400.0), chunk_size=2,
                  metric="mean_turnaround", mesh=ctx.mesh())
        before = size()
        search.successive_halving(ctx.scenario(), space,
                                  key=jax.random.PRNGKey(0), **kw)
        mid = size()
        search.successive_halving(ctx.scenario(), space,
                                  key=jax.random.PRNGKey(7), **kw)
        findings += check_rung_reuse(
            mid - before, size() - mid, "campaign_sharded"
        )
    return findings


# n_cloudlets probes for R6: around the floor, a mid-size, both sides of the
# pow-2 boundary, and both sides of the VMEM cap (the fallback frontier).
def _r6_sizes(cap: int) -> tuple[int, ...]:
    return (1, 7, 96, 128, 129, 1000, 4096, cap, cap + 1, 3 * cap)


@rule("R6", "kernel-budget", entries=("advance_pallas",))
def _rule_kernel_budget(ctx: LintContext) -> list[Finding]:
    """Advance-kernel launch plans stay inside the heuristic envelope."""
    from repro.kernels import ops, vm_update
    if not ctx.wants("advance_pallas"):
        return []
    findings = []
    for n in _r6_sizes(ops._MAX_BLOCK):
        block = ops.advance_block(n)
        plan = vm_update.kernel_plan(_BATCH, n, block)
        findings += check_kernel_plan(
            plan, n, ops._MAX_BLOCK, "advance_pallas"
        )
    return findings


# ---------------------------------------------------------------------------
# driver + report
# ---------------------------------------------------------------------------


def run_lint(rules: Iterable[str] | None = None,
             entries: Iterable[str] | None = None) -> list[Finding]:
    """Run the (filtered) rule registry; returns all findings."""
    wanted = tuple(rules) if rules else tuple(RULES)
    unknown = set(wanted) - set(RULES)
    if unknown:
        raise ValueError(
            f"unknown rule(s) {sorted(unknown)}; known: {list(RULES)}"
        )
    ctx = LintContext(entries)
    findings = []
    for rule_id in sorted(wanted):
        findings.extend(RULES[rule_id].fn(ctx))
    order = {s: i for i, s in enumerate(SEVERITIES)}
    findings.sort(key=lambda f: (order.get(f.severity, 99), f.rule))
    return findings


def summarize(findings: list[Finding]) -> dict:
    counts = {s: 0 for s in SEVERITIES}
    for f in findings:
        counts[f.severity] = counts.get(f.severity, 0) + 1
    return counts


def format_report(findings: list[Finding],
                  rules: Iterable[str] | None = None) -> str:
    """Human-readable lint report (the CLI's default output)."""
    lines = []
    checked = sorted(rules) if rules else sorted(RULES)
    for rule_id in checked:
        spec = RULES[rule_id]
        hits = [f for f in findings if f.rule == rule_id]
        status = "ok" if not any(
            f.severity == "error" for f in hits
        ) else "FAIL"
        lines.append(f"[{status:4s}] {rule_id} {spec.name}: {spec.doc}")
        for f in hits:
            lines.append(f"    {f.severity.upper():7s} {f.entry_point}: "
                         f"{f.message}")
            if f.evidence:
                lines.append(f"            | {f.evidence[:160]}")
    counts = summarize(findings)
    lines.append(
        f"simlint: {counts['error']} error(s), {counts['warning']} "
        f"warning(s), {counts['info']} info"
    )
    return "\n".join(lines)
