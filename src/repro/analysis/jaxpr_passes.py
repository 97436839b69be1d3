"""Jaxpr passes: the static recompile-storm detector and the
frontier-sized HBM-intermediate budgets.

``jit-boundary`` traces every program in ``programs.build_specs`` over
the engine's declared shape-bucket universe and flags (a) host
callbacks reaching a jit boundary (a dispatch-blocking sync per call)
and (b) any traced dimension outside its declared bucket class -- the
storm class of bug PR 4 fixed twice dynamically, caught here before a
single batch runs.

``hbm-budget`` generalizes the op-count fusion gate that lived inline
in benchmarks/bench_single_source.py: count the ops producing
frontier-sized (>= B*n/2 element) arrays in each backend's jaxpr and
gate against a baselined per-program budget. One budget table, two
consumers (this pass and ``bench_single_source.op_count_gate``).

jax is imported lazily throughout (the CLI sets XLA_FLAGS first).
"""
from __future__ import annotations

import dataclasses

from repro.analysis import programs
from repro.analysis.core import Context, Finding, Pass

_CALLBACK_MARKERS = ("callback", "outside_call", "infeed", "outfeed")


# ----------------------------------------------------------------------
# generalized from kernels/horner_push/ops.py (which now delegates
# here): recursive eqn iteration through jit/scan/while sub-jaxprs
# ----------------------------------------------------------------------
def sub_jaxprs(v):
    from jax.extend import core
    if isinstance(v, core.Jaxpr):
        return [v]
    if isinstance(v, core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        return [s for x in v for s in sub_jaxprs(x)]
    return []


def iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from iter_eqns(sub)


def count_hbm_intermediates(fn, *args, min_elems: int) -> int:
    """Number of traced ops (recursively, through jit/scan sub-jaxprs)
    producing an array of >= ``min_elems`` elements -- each is a
    frontier-sized HBM materialization candidate. The op-count form of
    the kernel-fusion acceptance gate, measurable on CPU without a TPU
    run (DESIGN.md sections 11 and 14)."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    count = 0
    for eqn in iter_eqns(jaxpr.jaxpr):
        if any(getattr(v.aval, "size", 0) >= min_elems
               for v in eqn.outvars):
            count += 1
    return count


# ----------------------------------------------------------------------
# jit-boundary pass
# ----------------------------------------------------------------------
class JitBoundaryPass(Pass):
    """No host callbacks / non-bucketed shapes at any jit boundary."""

    pass_id = "jit-boundary"

    def run(self, ctx: Context) -> list[Finding]:
        import jax
        uni = programs.universe()
        specs = programs.build_specs(jax.device_count())
        findings: list[Finding] = []
        self.skipped: list[str] = []
        for spec in specs:
            if spec.devices > jax.device_count():
                self.skipped.append(spec.name)
                continue
            findings.extend(self.check_spec(spec, uni))
        return findings

    def check_spec(self, spec: programs.ProgramSpec,
                   uni: dict | None = None) -> list[Finding]:
        import jax
        if uni is None:
            uni = programs.universe()
        findings: list[Finding] = []
        try:
            fn, args = spec.make()
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # a program that no longer traces is
            findings.append(Finding(  # itself a contract break
                pass_id=self.pass_id, file=spec.file, line=1,
                key=f"{spec.name}:trace",
                message=f"program {spec.name} failed to trace over "
                        f"its declared shapes: {type(e).__name__}: "
                        f"{e}"))
            return findings
        prims = sorted({eqn.primitive.name
                        for eqn in iter_eqns(jaxpr.jaxpr)})
        for p in prims:
            if any(mark in p for mark in _CALLBACK_MARKERS):
                findings.append(Finding(
                    pass_id=self.pass_id, file=spec.file, line=1,
                    key=f"{spec.name}:callback:{p}",
                    message=f"program {spec.name} reaches a host "
                            f"callback primitive '{p}' at the jit "
                            "boundary (blocks dispatch per call)"))
        geo_n = programs._geometry(uni)["n"]
        for d in spec.dims:
            if not programs.bucket_ok(d, geo_n, uni):
                findings.append(Finding(
                    pass_id=self.pass_id, file=spec.file, line=1,
                    key=f"{spec.name}:dim:{d.name}",
                    message=f"program {spec.name} dimension "
                            f"{d.name}={d.value} is outside its "
                            f"declared bucket class '{d.bucket}' -- "
                            "this shape recompiles per distinct "
                            "value (recompile storm)"))
        return findings


# ----------------------------------------------------------------------
# HBM-intermediate budgets
# ----------------------------------------------------------------------
# Canonical gate geometry (production-ish n; trace-only, so cheap).
HBM_GEOMETRY = {"n": 10_000, "deg": 3, "B": 16, "W": 64, "l_max": 10}

# Baselined frontier-sized op budgets per (program, backend) at
# HBM_GEOMETRY. lax=113 / pallas=14 are the PR 6 acceptance numbers;
# a regression above budget is a finding, an improvement is a prompt
# to ratchet the budget down.
HBM_BUDGETS = {
    ("source", "lax"): 113,
    ("source", "pallas"): 14,
    ("topk", "lax"): 113,
    ("topk", "pallas"): 14,
}


@dataclasses.dataclass(frozen=True)
class BudgetRow:
    program: str
    backend: str
    measured: int
    budget: int | None
    min_elems: int
    model_bytes: int

    @property
    def over(self) -> bool:
        return self.budget is not None and self.measured > self.budget


def hbm_budget_report(n: int | None = None) -> list[BudgetRow]:
    """Measure frontier-sized HBM ops for each gated program.

    Budgets apply at the canonical ``HBM_GEOMETRY`` n; at any other n
    the rows carry ``budget=None`` (measured only -- callers can still
    assert pallas <= lax).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.single_source import (batched_single_source,
                                          batched_single_source_pallas)
    from repro.core.topk import batched_topk, batched_topk_pallas
    from repro.kernels.horner_push import ops as hp_ops

    geo = dict(HBM_GEOMETRY)
    if n is not None:
        geo["n"] = n
    n, deg, B, W, l_max = (geo["n"], geo["deg"], geo["B"], geo["W"],
                           geo["l_max"])
    canonical = n == HBM_GEOMETRY["n"]
    m = deg * n
    bn, eb = hp_ops.DEFAULT_BN, hp_ops.DEFAULT_EB
    nb = -(-n // bn)
    ep = max(eb, -(-((m + nb - 1) // nb) // eb) * eb)
    f32 = jnp.float32
    s = jax.ShapeDtypeStruct
    lax_args = (s((n, W), jnp.int32), s((n, W), f32), s((n,), f32),
                s((m,), jnp.int32), s((m,), jnp.int32), s((m,), f32),
                s((B,), jnp.int32), s((), f32))
    pl_args = (s((n, W), jnp.int32), s((n, W), f32), s((n,), f32),
               s((nb, ep), jnp.int32), s((nb, ep), jnp.int32),
               s((nb, ep), f32), s((B,), jnp.int32), s((), f32))
    min_elems = B * n // 2       # anything frontier-sized
    cost = hp_ops.push_cost_model(n, m, B, ep, l_max, bn=bn, eb=eb)

    gated = {
        ("source", "lax"): (lambda *a: batched_single_source(
            *a, n=n, l_max=l_max), lax_args, cost["lax_bytes"]),
        ("source", "pallas"): (lambda *a: batched_single_source_pallas(
            *a, n=n, l_max=l_max, bn=bn, eb=eb, interpret=True),
            pl_args, cost["pallas_bytes"]),
        ("topk", "lax"): (lambda *a: batched_topk(
            *a, n=n, l_max=l_max, k=16), lax_args, cost["lax_bytes"]),
        ("topk", "pallas"): (lambda *a: batched_topk_pallas(
            *a, n=n, l_max=l_max, k=16, bn=bn, eb=eb, interpret=True),
            pl_args, cost["pallas_bytes"]),
    }
    rows = []
    for (prog, backend), (fn, args, bytes_) in gated.items():
        measured = count_hbm_intermediates(fn, *args,
                                           min_elems=min_elems)
        budget = (HBM_BUDGETS[(prog, backend)] if canonical else None)
        rows.append(BudgetRow(program=prog, backend=backend,
                              measured=measured, budget=budget,
                              min_elems=min_elems, model_bytes=bytes_))
    return rows


class HbmBudgetPass(Pass):
    """Per-program frontier-sized HBM-intermediate budgets."""

    pass_id = "hbm-budget"

    def run(self, ctx: Context) -> list[Finding]:
        findings: list[Finding] = []
        for row in hbm_budget_report():
            if row.over:
                findings.append(Finding(
                    pass_id=self.pass_id,
                    file="src/repro/core/single_source.py"
                    if row.program == "source"
                    else "src/repro/core/topk.py",
                    line=1,
                    key=f"{row.program}/{row.backend}:hbm",
                    message=f"{row.program}/{row.backend} "
                            f"materializes {row.measured} "
                            f"frontier-sized HBM intermediates at "
                            f"n={HBM_GEOMETRY['n']} (budget "
                            f"{row.budget}); fusion regressed"))
        return findings
