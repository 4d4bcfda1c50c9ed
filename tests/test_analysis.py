"""repro.analysis (jaxlint): walker mechanics, each rule positive +
negative, and the four canonical regression fixtures — every fixture
runs with the FULL rule catalog active and must trip exactly its own
rule (a checker that fires on healthy programs is as useless as one
that misses sick ones)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    AnalysisError,
    ConstantFootprint,
    Donation,
    DtypeFlow,
    FusionBudget,
    HostSync,
    Report,
    analyze,
    count_primitives,
    outermost_scan_body,
)
from repro.analysis.walker import iter_eqns, sub_jaxprs

R, N, D = 12, 8, 5


# ----------------------------------------------------------------------
# a healthy toy "round scan": one dot per round, nothing baked in
# ----------------------------------------------------------------------
def _toy_scan(state, coeffs):
    """state (n, d), coeffs (R, n, n): R rounds of state ← C_r @ state."""

    def body(carry, coeff):
        new = coeff @ carry
        return new, jnp.sum(new)

    return jax.lax.scan(body, state, coeffs)


def _toy_args():
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    coeffs = jnp.asarray(rng.normal(size=(R, N, N)), jnp.float32)
    return state, coeffs


def _catalog(expect_donated: bool = False):
    """The full rule catalog, sized for the healthy toy scan."""
    return [
        FusionBudget.of({"dot_general": 1, "pallas_call": 0},
                        scope="scan_body"),
        ConstantFootprint(max_total_bytes=1024),
        DtypeFlow(),
        Donation(expect=expect_donated),
        HostSync(),
    ]


class TestWalker:
    def test_iter_eqns_recurses_with_paths(self):
        closed = jax.make_jaxpr(_toy_scan)(*_toy_args())
        prims = {e.primitive.name for e, _ in iter_eqns(closed)}
        assert "scan" in prims and "dot_general" in prims
        # the dot lives INSIDE the scan body: its path says so
        paths = [p for e, p in iter_eqns(closed)
                 if e.primitive.name == "dot_general"]
        assert paths and all("scan" in p for p in paths)

    def test_count_primitives_exclude_within(self):
        closed = jax.make_jaxpr(_toy_scan)(*_toy_args())
        assert count_primitives(closed)["dot_general"] == 1
        assert count_primitives(
            closed, exclude_within=("scan",)).get("dot_general", 0) == 0

    def test_sub_jaxprs_yields_cond_branches(self):
        def f(x, flag):
            return jax.lax.cond(flag, lambda v: v + 1.0,
                                lambda v: v * 2.0, x)

        closed = jax.make_jaxpr(f)(jnp.zeros(3), True)
        cond_eqn = next(e for e, _ in iter_eqns(closed)
                        if e.primitive.name == "cond")
        assert len(list(sub_jaxprs(cond_eqn))) == 2

    def test_outermost_scan_body(self):
        closed = jax.make_jaxpr(_toy_scan)(*_toy_args())
        body = outermost_scan_body(closed)
        assert body is not None
        assert count_primitives(body)["dot_general"] == 1
        no_scan = jax.make_jaxpr(lambda x: x @ x.T)(jnp.ones((3, 3)))
        assert outermost_scan_body(no_scan) is None

    def test_counts_recurse_into_pjit(self):
        inner = jax.jit(lambda x: x @ x.T)
        closed = jax.make_jaxpr(lambda x: inner(x) + 1.0)(jnp.ones((3, 3)))
        assert count_primitives(closed)["dot_general"] == 1


class TestReport:
    def test_clean_report(self):
        report = analyze(_toy_scan, *_toy_args(), rules=_catalog())
        assert isinstance(report, Report) and report.ok
        assert report.failed_rules() == []
        assert report.raise_if_failed() is report
        d = report.to_dict()
        assert d["ok"] and set(d["rules"]) == {
            "fusion-budget", "constant-footprint", "dtype-flow",
            "donation", "host-sync"}
        # clean outcomes still document what was measured
        assert d["rules"]["fusion-budget"]["measured"]["dot_general"] == 1

    def test_raise_carries_findings_text(self):
        bad = FusionBudget.of({"dot_general": 7}, scope="scan_body")
        report = analyze(_toy_scan, *_toy_args(), rules=[bad])
        assert not report.ok
        with pytest.raises(AnalysisError, match="expected exactly 7"):
            report.raise_if_failed()


# ----------------------------------------------------------------------
# the four canonical regressions — full catalog on, exactly one rule trips
# ----------------------------------------------------------------------
def _assert_only_trips(report: Report, rule_name: str):
    assert report.failed_rules() == [rule_name], str(report)


class TestNegativeFixtures:
    def test_materialized_stack_closure_trips_constant_footprint(self):
        """The leak the scanned engine exists to avoid: an (R, n, n)
        coefficient slab captured by closure becomes a 3 KiB trace
        constant instead of an argument."""
        state, coeffs = _toy_args()

        def leaky(s):
            def body(carry, r):
                return coeffs[r] @ carry, jnp.sum(carry)

            return jax.lax.scan(body, s, jnp.arange(R))

        report = analyze(leaky, state, rules=_catalog())
        _assert_only_trips(report, "constant-footprint")
        assert report.outcome("constant-footprint").measured[
            "total_bytes"] >= R * N * N * 4

    def test_f64_literal_trips_dtype_flow(self):
        """One stray float64 under x64 poisons the whole round dtype."""
        state, coeffs = _toy_args()

        with jax.enable_x64(True):
            def f64_scan(s, cs):
                def body(carry, coeff):
                    new = (coeff @ carry
                           + jnp.asarray(1e-3, jnp.float64))
                    return new.astype(jnp.float32), jnp.sum(carry)

                return jax.lax.scan(body, s, cs)

            report = analyze(f64_scan, state, coeffs, rules=_catalog())
        _assert_only_trips(report, "dtype-flow")

    def test_undonated_carry_trips_donation(self):
        """The chunked-mode contract: analyzing with expect=True but
        jitting without donate_argnums must fail — and threading the
        engine's DONATED_CARRY_ARGNUMS through must pass."""
        from repro.core.sweep import DONATED_CARRY_ARGNUMS

        state, coeffs = _toy_args()
        report = analyze(_toy_scan, state, coeffs,
                         rules=_catalog(expect_donated=True),
                         jit_kwargs={})
        _assert_only_trips(report, "donation")

        donated = analyze(
            _toy_scan, state, coeffs, rules=_catalog(expect_donated=True),
            jit_kwargs={"donate_argnums": DONATED_CARRY_ARGNUMS[:1]})
        assert donated.ok, str(donated)
        assert donated.outcome("donation").measured["donated_buffers"] >= 1

    def test_debug_callback_in_round_body_trips_host_sync(self):
        """jax.debug.print inside the scan body = one host round-trip
        per round — the single-dispatch design's cardinal sin."""
        state, coeffs = _toy_args()

        def chatty(s, cs):
            def body(carry, coeff):
                new = coeff @ carry
                jax.debug.print("round sum {}", jnp.sum(new))
                return new, jnp.sum(new)

            return jax.lax.scan(body, s, cs)

        report = analyze(chatty, state, coeffs, rules=_catalog())
        _assert_only_trips(report, "host-sync")
        finding = report.outcome("host-sync").findings[0]
        assert "debug_print" in finding.message


# ----------------------------------------------------------------------
# per-rule specifics not covered by the fixtures
# ----------------------------------------------------------------------
class TestRules:
    def test_fusion_budget_exact_not_at_most(self):
        rule = FusionBudget.of({"dot_general": 0}, scope="scan_body")
        report = analyze(_toy_scan, *_toy_args(), rules=[rule])
        assert not report.ok  # 1 ≠ 0: exact, both directions

    def test_constant_footprint_per_const_cap(self):
        big = jnp.ones((256,), jnp.float32)  # 1 KiB single const

        def f(x):
            return x + big

        rule = ConstantFootprint(max_total_bytes=1 << 20,
                                 max_const_bytes=512)
        report = analyze(f, jnp.zeros((256,)), rules=[rule])
        assert report.failed_rules() == ["constant-footprint"]
        assert "per-constant cap" in report.findings[0].message

    def test_dtype_flow_kernel_upcast_knob(self):
        """mix_in_float32 routes to an in-kernel bf16→f32 upcast the
        analyzer can see — and its absence on the low-precision path."""
        from repro.kernels.gossip_mix import gossip_plane_pallas

        plane = jnp.ones((4, 256), jnp.bfloat16)
        c = jnp.full((4, 4), 0.25, jnp.float32)
        hi = lambda p_, c_: gossip_plane_pallas(p_, c_,
                                                mix_in_float32=True)
        lo = lambda p_, c_: gossip_plane_pallas(p_, c_,
                                                mix_in_float32=False)
        assert analyze(hi, plane, c,
                       rules=[DtypeFlow(expect_kernel_upcasts=True)]).ok
        assert analyze(lo, plane, c,
                       rules=[DtypeFlow(expect_kernel_upcasts=False)]).ok
        assert not analyze(hi, plane, c,
                           rules=[DtypeFlow(
                               expect_kernel_upcasts=False)]).ok
        assert not analyze(lo, plane, c,
                           rules=[DtypeFlow(
                               expect_kernel_upcasts=True)]).ok

    def test_host_sync_scope_all(self):
        def noisy(x):
            jax.debug.print("x {}", x)
            return x * 2.0

        report = analyze(noisy, jnp.ones(3),
                         rules=[HostSync(scope="all")])
        assert report.failed_rules() == ["host-sync"]


# ----------------------------------------------------------------------
# budget metadata (kernels / core)
# ----------------------------------------------------------------------
class TestBudgetMetadata:
    def test_mix_eqn_budget_values(self):
        from repro.kernels.gossip_mix import mix_eqn_budget

        assert mix_eqn_budget("einsum", 6) == {"pallas_call": 0,
                                               "dot_general": 6}
        assert mix_eqn_budget("pallas") == {"pallas_call": 1,
                                            "dot_general": 0}
        assert mix_eqn_budget("edges") == {"pallas_call": 1,
                                           "dot_general": 0}
        assert mix_eqn_budget("sparse") == {"pallas_call": 0,
                                            "dot_general": 0}
        with pytest.raises(KeyError):
            mix_eqn_budget("segment")

    def test_mix_impl_budget_sparse_fallback(self):
        """On a support that doesn't circulant-decompose, the sparse
        impl falls back to dense einsum — and its declared budget must
        say so."""
        from repro.core.decentralized import mix_impl_budget
        from repro.core.topology import barabasi_albert, ring

        n = 16
        ring_support = np.asarray(ring(n).adjacency) + np.eye(n)
        ba_support = (np.asarray(barabasi_albert(n, p=5, seed=0).adjacency)
                      + np.eye(n))
        assert mix_impl_budget("sparse", 3, mix_support=ring_support) == {
            "pallas_call": 0, "dot_general": 0}
        assert mix_impl_budget("sparse", 3, mix_support=ba_support,
                               sparse_slack=0) == {
            "pallas_call": 0, "dot_general": 3}


# ----------------------------------------------------------------------
# engine-matrix preset + CLI (one-cell smokes; full matrix runs in CI)
# ----------------------------------------------------------------------
class TestPreset:
    def test_engine_matrix_lists_49_combos(self):
        from repro.analysis.presets import engine_matrix_combos

        combos = engine_matrix_combos()
        assert len(combos) == 49
        assert len({c.name for c in combos}) == 49
        # the partial-participation cells: every mode on einsum + one
        # kernel backend, sharing the synchronous einsum budgets
        part = [c for c in combos if c.participation]
        assert {(c.mode, c.impl) for c in part} == {
            ("scanned", "einsum"), ("chunked", "einsum"),
            ("mesh", "einsum"), ("unrolled", "einsum"),
            ("scanned", "pallas")}
        # the fault cells: quarantined fault injection through every
        # mode (same einsum budgets), plus a fault × trimmed composition
        fault = [c for c in combos if c.fault]
        assert {c.mode for c in fault} == set(
            ("scanned", "chunked", "mesh", "unrolled"))
        assert any(c.robust == "trimmed" for c in fault)
        # the robust cells cover both order-statistic backends plus the
        # coefficient-transform rule
        assert {(c.impl, c.robust) for c in combos
                if c.robust != "mean"} == {
            ("einsum", "trimmed"), ("einsum", "norm_clip"),
            ("edges", "median")}

    @pytest.mark.parametrize("mode,impl", [
        ("scanned", "pallas"), ("unrolled", "einsum")])
    def test_combo_reports_clean(self, mode, impl):
        from repro.analysis.presets import Combo, run_combo

        report = run_combo(Combo(mode, impl, "stack"))
        assert report.ok, str(report)

    def test_cli_writes_artifact_and_exits_zero(self, tmp_path):
        from repro.analysis.__main__ import main

        out = tmp_path / "ANALYSIS.json"
        code = main(["--only", "^scanned/sparse/stack$",
                     "--out", str(out)])
        assert code == 0
        import json

        payload = json.loads(out.read_text())
        assert payload["ok"] and payload["n_combos"] == 1
        combo = payload["combos"]["scanned/sparse/stack"]
        assert combo["rules"]["fusion-budget"]["ok"]

    def test_cli_only_no_match_is_an_error(self, tmp_path):
        from repro.analysis.__main__ import main

        assert main(["--only", "no-such-combo",
                     "--out", str(tmp_path / "x.json")]) == 2


class TestJaxlintFixture:
    def test_count_walks_equations(self, jaxlint):
        counts = jaxlint.count(_toy_scan, *_toy_args())
        assert counts["dot_general"] == 1 and counts["scan"] == 1

    def test_check_raises_on_violation(self, jaxlint):
        with pytest.raises(AnalysisError):
            jaxlint.check(
                _toy_scan, *_toy_args(),
                rules=[jaxlint.FusionBudget.of({"pallas_call": 3},
                                               scope="all")])
