"""simlint: every rule must (a) pass on the current tree and (b) FIRE on a
hand-built violating program — a linter whose rules never trip is just a
slow no-op, so each rule gets a negative control:

  R1  a vmapped cond (XLA flattens it to select) and a scope-free program
  R2  an undonated chunk runner (empty alias table)
  R3  an instrument hook calling ``jax.debug.callback``
  R4  data-dependent slice widths / mismatched batch leaf ranks
  R5  an entry whose static argument forks the jit cache
  R6  doctored kernel plans (non-pow2 block, split row, wrong SMEM shapes)

The positive (tree-is-clean) checks run the cheap rules directly; the full
six-rule sweep over all entry points is the CI ``scripts/simlint.py`` step,
not a unit test.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import simlint
from repro.core import step
from repro.kernels import ops, vm_update

pytestmark = pytest.mark.tier1


def _errors(findings):
    return [f for f in findings if f.severity == "error"]


# ---------------------------------------------------------------------------
# R1 cond-not-select
# ---------------------------------------------------------------------------


def _hlo_of(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class TestR1CondNotSelect:
    def test_scalar_cond_passes(self):
        def good(x, flag):
            with jax.named_scope(step.SCOPE_PROVISION):
                return jax.lax.cond(
                    flag, lambda v: jnp.dot(v, v), lambda v: v, x
                )

        hlo = _hlo_of(good, jnp.ones((8, 8)), jnp.bool_(True))
        assert simlint.check_cond_not_select(
            hlo, [step.SCOPE_PROVISION], "t"
        ) == []

    def test_vmapped_cond_trips(self):
        # vmap over the predicate forces both branches -> select, the exact
        # degradation R1 exists to catch
        def bad(x, flag):
            with jax.named_scope(step.SCOPE_PROVISION):
                return jax.lax.cond(
                    flag, lambda v: v * 2.0, lambda v: v, x
                )

        hlo = _hlo_of(
            jax.vmap(bad), jnp.ones((4, 8)), jnp.ones((4,), bool)
        )
        errs = _errors(simlint.check_cond_not_select(
            hlo, [step.SCOPE_PROVISION], "t"
        ))
        assert len(errs) == 1
        assert "select" in errs[0].message
        assert errs[0].rule == "R1" and errs[0].entry_point == "t"

    def test_missing_scope_trips(self):
        hlo = _hlo_of(lambda x: x + 1.0, jnp.ones((4,)))
        errs = _errors(simlint.check_cond_not_select(
            hlo, [step.SCOPE_PROVISION, step.SCOPE_DISPATCH], "t"
        ))
        assert len(errs) == 2
        assert all("not found" in e.message for e in errs)


# ---------------------------------------------------------------------------
# R2 donation-aliases
# ---------------------------------------------------------------------------


class TestR2DonationAliases:
    @staticmethod
    def _lower(donate: bool):
        def runner(xs):
            return jax.tree.map(lambda x: x * 2.0 + 1.0, xs)

        f = jax.jit(runner, donate_argnums=(0,) if donate else ())
        args = ({"a": jnp.ones((64,)), "b": jnp.ones((32, 2))},)
        return f.lower(*args).compile().as_text()

    def test_donated_runner_passes(self):
        hlo = self._lower(donate=True)
        assert simlint.check_donation_aliases(hlo, 2, "t") == []

    def test_undonated_runner_trips(self):
        # the PR-2 regression class: runner "donates" nothing, alias table
        # empty, campaigns silently pay double memory
        hlo = self._lower(donate=False)
        errs = _errors(simlint.check_donation_aliases(hlo, 2, "t"))
        assert len(errs) == 1
        assert "0 of 2" in errs[0].message and errs[0].rule == "R2"

    def test_partial_coverage_warns_not_errors(self):
        hlo = self._lower(donate=True)
        out = simlint.check_donation_aliases(hlo, 3, "t")
        assert _errors(out) == []
        assert [f.severity for f in out] == ["warning"]

    def test_zero_donatable_is_error(self):
        errs = _errors(simlint.check_donation_aliases("HloModule m", 0, "t"))
        assert len(errs) == 1 and "no donatable" in errs[0].message


# ---------------------------------------------------------------------------
# R3 pure-observer
# ---------------------------------------------------------------------------


class TestR3PureObserver:
    def test_pure_hook_passes(self):
        cj = jax.make_jaxpr(lambda s: (s * 2.0, jnp.sum(s)))(jnp.ones((4,)))
        assert simlint.check_effects(cj, "t") == []

    def test_debug_callback_instrument_trips(self):
        # a "logging" instrument hook — the classic way to break the
        # bitwise trace-equivalence contract
        def noisy_post(st):
            jax.debug.callback(lambda v: None, st)
            return st

        cj = jax.make_jaxpr(noisy_post)(jnp.ones((4,)))
        errs = _errors(simlint.check_effects(cj, "instrument:noisy.post"))
        assert errs, "debug_callback hook must trip R3"
        assert errs[0].rule == "R3"
        assert errs[0].entry_point == "instrument:noisy.post"

    def test_debug_print_trips(self):
        def chatty(x):
            jax.debug.print("x={x}", x=x)
            return x + 1.0

        cj = jax.make_jaxpr(chatty)(jnp.float32(0.0))
        assert _errors(simlint.check_effects(cj, "t"))


# ---------------------------------------------------------------------------
# R4 shape-stable-scan
# ---------------------------------------------------------------------------


class TestR4ShapeStable:
    def test_static_program_passes(self):
        cj = jax.make_jaxpr(
            lambda x: jax.lax.dynamic_slice(x, (jnp.int32(1),), (3,))
        )(jnp.arange(8.0))
        assert simlint.check_shape_stability(cj, "t") == []

    def test_rank_consistency_passes_on_true_batch(self):
        single = {"a": (8,), "b": ()}
        batch = {"a": (4, 8), "b": (4,)}
        assert simlint.check_rank_consistency(single, batch, 4, "t") == []

    def test_rank_mismatch_trips(self):
        single = {"a": (8,), "b": ()}
        batch = {"a": (4, 8), "b": (2,)}  # wrong batch dim on b
        errs = _errors(
            simlint.check_rank_consistency(single, batch, 4, "t")
        )
        assert len(errs) == 1 and "b" in errs[0].message

    def test_leaf_set_drift_trips(self):
        errs = _errors(simlint.check_rank_consistency(
            {"a": (8,), "gone": ()}, {"a": (4, 8), "new": (4,)}, 4, "t"
        ))
        assert {("gone" in e.message) or ("new" in e.message)
                for e in errs} == {True}
        assert len(errs) == 2


# ---------------------------------------------------------------------------
# R5 recompile-hazard
# ---------------------------------------------------------------------------


class TestR5RecompileHazard:
    def test_traced_knob_passes(self):
        f = jax.jit(lambda x, k: x * k)
        f(jnp.ones((4,)), jnp.float32(2.0))
        f(jnp.ones((4,)), jnp.float32(3.0))
        assert simlint.check_one_compilation(f, 2, "t") == []

    def test_static_knob_forks_cache_and_trips(self):
        # a policy knob accidentally made static: every swept value is a
        # fresh XLA compile — the hazard R5 guards the engine against
        f = jax.jit(lambda x, k: x * k, static_argnums=(1,))
        f(jnp.ones((4,)), 2.0)
        f(jnp.ones((4,)), 3.0)
        errs = _errors(simlint.check_one_compilation(f, 2, "t"))
        assert len(errs) == 1
        assert "2 compilations" in errs[0].message
        assert errs[0].rule == "R5"


# ---------------------------------------------------------------------------
# R6 kernel-budget
# ---------------------------------------------------------------------------


class TestR6KernelBudget:
    @pytest.mark.parametrize("c", [1, 96, 128, 1000, 4096, 3 << 17])
    def test_real_plans_pass(self, c):
        plan = vm_update.kernel_plan(4, c, ops.advance_block(c))
        assert simlint.check_kernel_plan(
            plan, c, ops._MAX_BLOCK, "t"
        ) == []

    def test_non_pow2_block_trips(self):
        plan = vm_update.kernel_plan(4, 192, 192)
        errs = _errors(simlint.check_kernel_plan(plan, 192, 1 << 17, "t"))
        assert any("power of two" in e.message for e in errs)

    def test_sub_floor_block_trips(self):
        plan = vm_update.kernel_plan(4, 64, 64)
        errs = _errors(simlint.check_kernel_plan(plan, 64, 1 << 17, "t"))
        assert any("128-lane floor" in e.message for e in errs)

    def test_over_cap_block_trips(self):
        big = 1 << 18
        plan = vm_update.kernel_plan(4, big, big)
        errs = _errors(simlint.check_kernel_plan(plan, big, 1 << 17, "t"))
        assert any("VMEM cap" in e.message for e in errs)

    def test_split_row_that_fits_trips(self):
        # block 128 on a 256-wide row that would fit a 256 tile: the fused
        # single-pass path was forfeited for no reason
        plan = vm_update.kernel_plan(4, 256, 128)
        errs = _errors(simlint.check_kernel_plan(plan, 256, 1 << 17, "t"))
        assert any("splits a row" in e.message for e in errs)

    def test_doctored_smem_shape_trips(self):
        plan = vm_update.kernel_plan(4, 128, 128)
        plan["smem_out"] = (("dt", (4, 1)),)
        errs = _errors(simlint.check_kernel_plan(plan, 128, 1 << 17, "t"))
        assert any("scalars-per-row" in e.message for e in errs)

    def test_single_row_tile_trips(self):
        # the (1, block) tile Mosaic refuses for B > 1
        plan = vm_update.kernel_plan(4, 128, 128)
        plan["tile"] = (1, 128)
        errs = _errors(simlint.check_kernel_plan(plan, 128, 1 << 17, "t"))
        assert any("sublanes per tile" in e.message for e in errs)

    def test_unpadded_batch_trips(self):
        plan = vm_update.kernel_plan(4, 128, 128)
        plan["padded_b"], plan["grid"] = 4, (4,)
        errs = _errors(simlint.check_kernel_plan(plan, 128, 1 << 17, "t"))
        assert any("multiple of 8 sublanes" in e.message for e in errs)

    def test_doctored_variant_trips(self):
        plan = vm_update.kernel_plan(4, 128, 128)
        plan["variant"], plan["grid"] = "two_phase", (4, 2, 1)
        errs = _errors(simlint.check_kernel_plan(plan, 128, 1 << 17, "t"))
        assert any("implies 'fused'" in e.message for e in errs)

    def test_fused_scratch_trips(self):
        plan = vm_update.kernel_plan(4, 128, 128)
        plan["vmem_scratch"] = (("min_sc", (8, 1)),)
        errs = _errors(simlint.check_kernel_plan(plan, 128, 1 << 17, "t"))
        assert any("scratch" in e.message for e in errs)


# ---------------------------------------------------------------------------
# plumbing: registry, filters, report, JSON round-trip
# ---------------------------------------------------------------------------


class TestPlumbing:
    def test_registry_complete(self):
        assert sorted(simlint.RULES) == ["R1", "R2", "R3", "R4", "R5", "R6"]
        for spec in simlint.RULES.values():
            assert spec.entries and spec.doc
            assert set(spec.entries) <= set(simlint.ENTRY_NAMES)

    def test_unknown_rule_and_entry_raise(self):
        with pytest.raises(ValueError, match="R99"):
            simlint.run_lint(rules=["R99"])
        with pytest.raises(ValueError, match="warp_drive"):
            simlint.LintContext(entries=["warp_drive"])

    def test_r6_runs_clean_on_current_tree(self):
        # cheap true-positive check (no engine tracing); the full-tree
        # zero-error sweep is the blocking CI step
        assert _errors(simlint.run_lint(rules=["R6"])) == []

    def test_findings_sorted_and_serializable(self):
        f_err = simlint.Finding("R5", "recompile-hazard", "error", "e", "m")
        f_wrn = simlint.Finding("R2", "donation-aliases", "warning", "e", "m")
        d = f_wrn.to_dict()
        assert d["rule"] == "R2" and d["severity"] == "warning"
        assert simlint.summarize([f_err, f_wrn]) == {
            "error": 1, "warning": 1, "info": 0
        }
        report = simlint.format_report([f_err, f_wrn])
        assert "[FAIL] R5" in report and "[ok  ] R2" in report
