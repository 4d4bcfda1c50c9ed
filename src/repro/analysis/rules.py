"""The invariant-rule catalog and the :func:`analyze` entry point.

Each rule is a frozen dataclass (hashable, printable, declarative) with a
registered ``name`` and a ``check(ctx) -> List[Finding]`` method over an
:class:`AnalysisContext` — the traced ``ClosedJaxpr`` plus, for rules
that need it, the jit-lowered StableHLO text.  DESIGN.md §13 catalogs
what each rule guards and which PR introduced the contract.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.report import Finding, Report, RuleOutcome
from repro.analysis.walker import (
    all_avals,
    all_consts,
    count_primitives,
    iter_eqns,
    outermost_scan_body,
)

__all__ = [
    "AnalysisContext",
    "Rule",
    "FusionBudget",
    "ConstantFootprint",
    "DtypeFlow",
    "Donation",
    "HostSync",
    "analyze",
    "HOST_CALLBACK_PRIMS",
]

#: Primitives that synchronize with the host.  Any of these inside the
#: round scan body would serialize the whole R-round schedule on host
#: round-trips — the host-sync contract (PR 1's single-dispatch design).
HOST_CALLBACK_PRIMS: Tuple[str, ...] = (
    "io_callback",
    "debug_callback",
    "debug_print",
    "pure_callback",
    "outside_call",
)

_ALIASED_ARG_RE = re.compile(r"%arg(\d+)(?:(?!%arg).)*?tf\.aliasing_output",
                             re.DOTALL)
# Multi-device lowerings defer the input→output pairing to sharding
# propagation and mark donated inputs with ``jax.buffer_donor`` instead.
_BUFFER_DONOR_RE = re.compile(r"%arg(\d+)(?:(?!%arg).)*?jax\.buffer_donor",
                              re.DOTALL)


@dataclasses.dataclass
class AnalysisContext:
    """What a traced program exposes to the rules: its closed jaxpr and —
    when some rule declared ``needs_lowering`` — the StableHLO text of
    ``jax.jit(fn, **jit_kwargs).lower(*args)``."""

    closed_jaxpr: object
    lowered_text: Optional[str] = None
    name: str = "<fn>"

    def scoped(self, scope: str):
        """The sub-jaxpr a ``scope`` selects: ``"all"`` → the whole
        program; ``"scan_body"`` → the outermost scan's body (falling
        back to the whole program when no scan exists, so the same rule
        spec serves scanned and unrolled traces)."""
        if scope == "all":
            return self.closed_jaxpr
        if scope == "scan_body":
            body = outermost_scan_body(self.closed_jaxpr)
            return self.closed_jaxpr if body is None else body
        raise ValueError(f"unknown scope {scope!r}; have 'all', 'scan_body'")


@dataclasses.dataclass(frozen=True)
class Rule:
    """Base class: a named, parameterized invariant check."""

    name = "rule"
    needs_lowering = False

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        raise NotImplementedError

    def _finding(self, message: str, path: str = "") -> Finding:
        return Finding(rule=self.name, message=message, path=path)


@dataclasses.dataclass(frozen=True)
class FusionBudget(Rule):
    """Exact trace-time equation counts — THE kernel-fusion contract.

    ``budget`` maps primitive names to the exact number of equations the
    scoped program must contain (e.g. ``{"pallas_call": 1}``: the whole
    mix is ONE fused kernel launch, PR 5/6).  Counts recurse into
    ``scan`` / ``pjit`` / ``cond`` sub-jaxprs but skip Pallas kernel
    bodies (``dot_general`` inside a kernel is the kernel's MAC, not an
    XLA GEMM).  Expected budgets come from introspectable metadata —
    ``repro.core.decentralized.mix_impl_budget`` /
    ``repro.kernels.gossip_mix.mix_eqn_budget`` — not hand-typed counts.
    """

    budget: Tuple[Tuple[str, int], ...] = ()
    scope: str = "scan_body"
    name = "fusion-budget"

    @staticmethod
    def of(budget: Dict[str, int], scope: str = "scan_body") -> "FusionBudget":
        """Build from a plain dict (the dataclass stores a sorted tuple so
        rule instances stay hashable)."""
        return FusionBudget(budget=tuple(sorted(budget.items())), scope=scope)

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        expected = dict(self.budget)
        counts = count_primitives(ctx.scoped(self.scope),
                                  names=tuple(expected),
                                  exclude_within=("pallas_call",))
        findings = []
        for prim, want in sorted(expected.items()):
            got = counts.get(prim, 0)
            if got != want:
                findings.append(self._finding(
                    f"{prim}: expected exactly {want} equation(s) in "
                    f"scope {self.scope!r}, found {got}"))
        return findings

    def measure(self, ctx: AnalysisContext) -> Dict[str, object]:
        counts = count_primitives(ctx.scoped(self.scope),
                                  names=tuple(dict(self.budget)),
                                  exclude_within=("pallas_call",))
        return {p: counts.get(p, 0) for p in dict(self.budget)}


@dataclasses.dataclass(frozen=True)
class ConstantFootprint(Rule):
    """Bound the bytes of constants baked into the traced program.

    The scanned engine's whole design keeps per-round data (coefficient
    slabs, index schedules, banks) as *arguments*; anything large that
    shows up as a closed-over constant — an ``(R, n, n)`` stack captured
    by a closure, an accidentally materialized coefficient program — is
    a regression that silently multiplies compile memory and bakes data
    into the executable (PR 3's contract).  ``max_total_bytes`` caps the
    sum over all constants; ``max_const_bytes`` caps any single one.
    """

    max_total_bytes: int = 1 << 20
    max_const_bytes: Optional[int] = None
    name = "constant-footprint"

    def _const_bytes(self, const) -> int:
        arr = np.asarray(const)
        return int(arr.size) * int(arr.dtype.itemsize)

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        consts = all_consts(ctx.closed_jaxpr)
        total = sum(self._const_bytes(c) for c in consts)
        findings = []
        if self.max_const_bytes is not None:
            for c in consts:
                nbytes = self._const_bytes(c)
                if nbytes > self.max_const_bytes:
                    arr = np.asarray(c)
                    findings.append(self._finding(
                        f"constant {arr.dtype}{list(arr.shape)} is "
                        f"{nbytes} B > per-constant cap "
                        f"{self.max_const_bytes} B — large data must be "
                        f"an argument, not baked into the trace"))
        if total > self.max_total_bytes:
            findings.append(self._finding(
                f"total constant footprint {total} B > cap "
                f"{self.max_total_bytes} B over {len(consts)} constant(s)"))
        return findings

    def measure(self, ctx: AnalysisContext) -> Dict[str, object]:
        consts = all_consts(ctx.closed_jaxpr)
        return {"n_consts": len(consts),
                "total_bytes": sum(self._const_bytes(c) for c in consts)}


@dataclasses.dataclass(frozen=True)
class DtypeFlow(Rule):
    """No forbidden dtypes anywhere; kernel upcasts only where declared.

    ``forbid`` dtypes (default: any f64 — one stray ``np.float64``
    doubles every downstream buffer) may not appear on any input,
    constant, or equation operand/output.  ``expect_kernel_upcasts``
    checks the low-precision-aggregation contract inside Pallas kernel
    bodies: ``True`` requires at least one small-float→f32
    ``convert_element_type`` (the declared f32 accumulation point,
    ``mix_in_float32=True``); ``False`` requires zero (the
    ``mix_in_float32=False`` path must stay low-precision end to end);
    ``None`` skips the check (no kernel / f32-native plane).  Declared
    expectations come from ``repro.kernels.gossip_mix.mix_accum_upcasts``.
    """

    forbid: Tuple[str, ...] = ("float64", "complex128", "int64")
    expect_kernel_upcasts: Optional[bool] = None
    name = "dtype-flow"

    def _forbidden(self, ctx: AnalysisContext) -> List[Finding]:
        findings, seen = [], set()
        for aval, path in all_avals(ctx.closed_jaxpr):
            dtype = getattr(aval, "dtype", None)
            if dtype is None:
                continue
            if str(dtype) in self.forbid:
                key = (str(dtype), path)
                if key not in seen:
                    seen.add(key)
                    shape = tuple(getattr(aval, "shape", ()))
                    findings.append(self._finding(
                        f"forbidden dtype {dtype} (shape {list(shape)}) "
                        f"in traced program", path="/".join(path)))
        for const in all_consts(ctx.closed_jaxpr):
            dtype = np.asarray(const).dtype
            if str(dtype) in self.forbid:
                findings.append(self._finding(
                    f"forbidden dtype {dtype} constant "
                    f"{list(np.asarray(const).shape)}"))
        return findings

    def _kernel_upcasts(self, ctx: AnalysisContext) -> int:
        small = {"bfloat16", "float16", "float8_e4m3fn", "float8_e5m2"}
        n = 0
        for eqn, path in iter_eqns(ctx.closed_jaxpr):
            if "pallas_call" not in path:
                continue
            if eqn.primitive.name != "convert_element_type":
                continue
            src = getattr(eqn.invars[0].aval, "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src is not None and str(src) in small \
                    and str(dst) == "float32":
                n += 1
        return n

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        findings = self._forbidden(ctx)
        if self.expect_kernel_upcasts is not None:
            ups = self._kernel_upcasts(ctx)
            if self.expect_kernel_upcasts and ups == 0:
                findings.append(self._finding(
                    "declared f32 accumulation (mix_in_float32=True) but "
                    "no small-float→f32 upcast found in any Pallas kernel "
                    "body — accumulation silently runs in low precision"))
            if not self.expect_kernel_upcasts and ups > 0:
                findings.append(self._finding(
                    f"low-precision path (mix_in_float32=False) upcasts "
                    f"to f32 at {ups} site(s) inside Pallas kernel bodies "
                    f"— must stay in the plane dtype"))
        return findings

    def measure(self, ctx: AnalysisContext) -> Dict[str, object]:
        return {"kernel_upcasts": self._kernel_upcasts(ctx)}


@dataclasses.dataclass(frozen=True)
class Donation(Rule):
    """Carry donation actually reaches the lowered program.

    The chunked and sharded engine modes (DESIGN.md §8) donate the
    ``(params, opt)`` carry so long schedules never double-allocate the
    model state — but ``donate_argnums`` silently vanishes if a wrapper
    re-jits without it.  This rule inspects the StableHLO lowering for
    donated-input attributes — ``tf.aliasing_output`` (single-device:
    the input→output pairing already resolved) or ``jax.buffer_donor``
    (multi-device: pairing deferred to sharding propagation):
    ``expect=True`` requires at least ``min_donated`` donated buffers;
    ``expect=False`` requires none (the one-shot scanned program takes
    no donation).  Lowering
    records donation intent on every backend, so the check runs on CPU
    CI too.
    """

    expect: bool = True
    min_donated: int = 1
    name = "donation"
    needs_lowering = True

    def _donated(self, ctx: AnalysisContext) -> List[int]:
        if ctx.lowered_text is None:
            raise ValueError("Donation rule needs the lowered program; "
                             "analyze() provides it when this rule is on")
        return sorted(
            {int(m.group(1))
             for m in _ALIASED_ARG_RE.finditer(ctx.lowered_text)}
            | {int(m.group(1))
               for m in _BUFFER_DONOR_RE.finditer(ctx.lowered_text)})

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        donated = self._donated(ctx)
        if self.expect and len(donated) < self.min_donated:
            return [self._finding(
                f"expected ≥ {self.min_donated} donated input buffer(s) "
                f"(tf.aliasing_output in the lowering), found "
                f"{len(donated)} — the carry is not donated")]
        if not self.expect and donated:
            return [self._finding(
                f"expected no donated inputs, but {len(donated)} "
                f"buffer(s) carry tf.aliasing_output")]
        return []

    def measure(self, ctx: AnalysisContext) -> Dict[str, object]:
        return {"donated_buffers": len(self._donated(ctx))}


@dataclasses.dataclass(frozen=True)
class HostSync(Rule):
    """No host callbacks inside the scan body.

    ``io_callback`` / ``debug_callback`` / ``debug_print`` /
    ``pure_callback`` equations inside the round scan would stall every
    round on a host round-trip,
    silently destroying the one-dispatch-per-run design (PR 1).  Scope
    ``"scan_body"`` checks the outermost scan (the whole program when no
    scan exists, so unrolled traces use the same spec).
    """

    forbid: Tuple[str, ...] = HOST_CALLBACK_PRIMS
    scope: str = "scan_body"
    name = "host-sync"

    def check(self, ctx: AnalysisContext) -> List[Finding]:
        findings = []
        for eqn, path in iter_eqns(ctx.scoped(self.scope)):
            if eqn.primitive.name in self.forbid:
                findings.append(self._finding(
                    f"host callback {eqn.primitive.name!r} inside scope "
                    f"{self.scope!r}", path="/".join(path)))
        return findings


def analyze(
    fn: Callable,
    *args,
    rules: Sequence[Rule],
    jit_kwargs: Optional[dict] = None,
    name: Optional[str] = None,
    **kwargs,
) -> Report:
    """Trace ``fn(*args, **kwargs)`` and run every rule against the jaxpr.

    ``jit_kwargs`` (e.g. ``{"donate_argnums": (0, 1)}``,
    ``{"static_argnames": (...)}``) are applied both to the
    ``jax.make_jaxpr`` trace and to the ``jax.jit(...).lower`` pass that
    runs when any rule ``needs_lowering`` — so the analyzed program is
    the one the engine would actually execute.  Returns a
    :class:`Report`; callers gate with ``report.raise_if_failed()`` or
    inspect per-rule ``outcomes``.
    """
    import jax

    jit_kwargs = dict(jit_kwargs or {})
    if kwargs:
        closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    else:
        closed = jax.make_jaxpr(fn)(*args)
    lowered_text = None
    if any(r.needs_lowering for r in rules):
        lowered = jax.jit(fn, **jit_kwargs).lower(*args, **kwargs)
        lowered_text = lowered.as_text()
    fn_name = name or getattr(fn, "__name__", "<fn>")
    ctx = AnalysisContext(closed_jaxpr=closed, lowered_text=lowered_text,
                          name=fn_name)
    outcomes = []
    for rule in rules:
        findings = rule.check(ctx)
        measured = (rule.measure(ctx)
                    if hasattr(rule, "measure") else {})
        outcomes.append(RuleOutcome(rule=rule.name, findings=findings,
                                    measured=measured))
    return Report(name=fn_name, outcomes=outcomes)
