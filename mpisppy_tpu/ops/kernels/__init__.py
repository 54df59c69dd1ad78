"""Kernel layer for the ADMM subproblem solver: one fused device
program per solve instead of a host-driven segment loop.

Three modes, selected by ``subproblem_kernel_mode`` (utils/config /
engine options; anatomy in doc/kernels.md):

  ``segmented``  today's ops/qp_solver host-segmented drivers,
                 BIT-FOR-BIT — the dispatch below is never entered, so
                 the existing pipeline-equivalence suite is the
                 guarantee;
  ``fused``      the whole solve (f32 bulk + factor handoff + accurate
                 tail + polish) as one device program: the XLA
                 fused-scan of reference.py for mixed/df32, the plain
                 ``qp_solve`` jit for native precision;
  ``auto``       fused wherever the solve is eligible (see
                 resolve_mode), segmented otherwise — the default.

Inside the fused program rides one doc/roofline.md §5 trade: explicit
L⁻¹ matmuls for the df32 tail's triangular solves (behind
``l_inv_profitable``: on where the build amortizes AND an apply beats
the prepared substitution's — sslp's n = 520; off at UC width, where
the chip measured the substitution faster at 64 and at 128 rows).
Recovery solves (chunk retries, the scenario hospital) ALWAYS take
the segmented path in native precision — the existing quality-gate
machinery doubles as the fused path's full-precision fallback.

Counters: ``kernel.fused_iters`` (ADMM iterations executed by fused
programs), ``kernel.l_inv_factorizations`` (eager L⁻¹ builds) —
catalogued in doc/observability.md.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from ...utils.config import FUSED_IR_SWEEPS
from ..qp_solver import (PackedMatrix, SplitMatrix, _needs_host_factor,
                         _trace_seg, f64_polish_form, f64_product_form,
                         f64_loop_form, f64_refactor_form,
                         f64_stack_block_rows, qp_solve)
from .reference import fused_mixed_solve, l_inv_profitable


def resolve_mode(mode: str, factors) -> str:
    """``auto`` resolution. A solve is fused-eligible unless its rho
    adaptation must run on the HOST (non-shared f64 factors whose
    explicit inverse this backend cannot build on the device —
    qp_solver._needs_host_factor): the fused program cannot call back
    out for the host-exact refactorization mid-loop. On the TPU a
    per-scenario float64 stack is inverted inside the program by the
    TPU's own forms (qp_solver.f64_refactor_form: "unrolled" at
    n <= 16, PR 42; "blocked" above, PR 45), so it fuses: loop,
    in-program rho adaptation and polish are ONE launch; only a stack
    too large to rebuild on the device (the hospital's UC-width
    batches) or a backend nobody measured keeps the host. Program
    length is no criterion: one program of 36,794 f64 matmul
    iterations ran 59.7 s to completion on the attached v5e
    (CHANGES.md PR 24)."""
    if mode == "fused":
        return "fused"
    if mode == "segmented" or _needs_host_factor(factors):
        return "segmented"
    return "fused"


@dataclass
class KernelPlan:
    """One mode's resolved kernel decisions, prepared once per
    factorization and reused every solve call (core/ph caches plans
    beside the factor cache and invalidates them together)."""
    mode: str                    # "fused" | "segmented" (resolved)
    l_inv: bool = False
    A_lo: object = None          # bulk-phase A_s operand (mixed/df32)
    f64_products: str | None = None   # qp_solver.f64_product_form(A_s)
    f64_polish: str | None = None     # qp_solver.f64_polish_form(A_s)
    f64_refactor: str | None = None   # qp_solver.f64_refactor_form(A_s)
    f64_loop: str | None = None       # qp_solver.f64_loop_form(A_s)
    f64_stack_block: int | None = None  # qp_solver.f64_stack_block_rows(A_s)
    # the eager explicit-inverse builds of this plan's solves (span
    # ``qp.l_inv_build``): {builds, seconds, n, panels}, totals; empty
    # until one ran. The plan outlives ``reset_phase_timing``, so a
    # build of set-up is still told after a window
    linv_build: dict = field(default_factory=dict)
    # the eager builds of this plan's per-scenario float64 inverse
    # (span ``qp.f64_refactor_build``: a mode's cold state): {builds,
    # seconds, rows, n}, totals kept the same way; empty where the
    # factor is none (the in-program rebuilds of a solve are
    # ``admm_iters_per_call["refactors"]``)
    f64_build: dict = field(default_factory=dict)

    def descriptor(self) -> dict:
        """The bench/telemetry kernel block. ``backend`` and
        ``block_dtype`` are constants: there is one fused program and
        its packed blocks are f32 (the benchmark's readers and
        ``est_hbm_bytes_per_iter`` keep the keys). ``f64_products`` is
        how this backend runs the batched float64 products of a
        per-scenario matrix (``"reduce"`` / ``"dot"``,
        doc/kernels.md §3d), None where the factors have none;
        ``f64_polish`` how it runs the factor-and-substitute side of a
        float64 polish over these factors (``"unrolled"`` /
        ``"blocked"`` / ``"library"``, §3e, §3h), None where no float64
        polish can run (a split matrix never polishes);
        ``f64_refactor`` where and how the explicit float64 KKT inverse
        of these factors is rebuilt when rho moves (``"unrolled"`` /
        ``"blocked"`` / ``"library"``: inside the solve program;
        ``"host"``: numpy, between device calls, §3f, §3h),
        None where the factor is no float64 inverse; ``f64_loop`` the
        shape of the loop that adapts rho inside the program
        (``"resident"``: the rebuild once a four-check period between
        two inner loops, no ``conditional``; ``"conditional"``: under a
        ``lax.cond`` in the loop's body, §3g), None where no program
        rebuilds a float64 inverse; ``f64_stack_block`` the rows of a
        block where the ADMM scan walks a wide per-scenario float64
        stack in blocks of scenarios (§3i), None where it is one scan
        over all rows."""
        return {"mode": self.mode, "backend": "reference",
                "l_inv": bool(self.l_inv), "block_dtype": "f32",
                "f64_products": self.f64_products,
                "f64_polish": self.f64_polish,
                "f64_refactor": self.f64_refactor,
                "f64_loop": self.f64_loop,
                "f64_stack_block": self.f64_stack_block}


def prepare(factors, *, mode="auto", l_inv="auto", precision="native",
            tail_iter=0, ir_sweeps=1, s_chunk=1):
    """Resolve the kernel decisions for one mode's factors (host,
    eager, once per factorization): mode, the L⁻¹ profitability
    verdict, and — for mixed/df32 — the bulk phase's f32 A operand.

    Out-of-band ``ir_sweeps`` (the fused program unrolls them
    statically — utils/config.FUSED_IR_SWEEPS): explicit ``fused`` is a
    config error the engine raises before any trace; ``auto`` falls
    back to segmented here, so exotic sweep counts keep working through
    the host-segmented drivers."""
    forms = dict(f64_products=f64_product_form(factors.A_s),
                 f64_polish=f64_polish_form(factors.A_s),
                 f64_refactor=f64_refactor_form(factors.A_s),
                 f64_loop=f64_loop_form(factors.A_s),
                 f64_stack_block=f64_stack_block_rows(factors.A_s))
    if int(ir_sweeps) not in FUSED_IR_SWEEPS:
        if mode == "fused":
            raise ValueError(
                f"kernel mode 'fused' supports ir_sweeps in "
                f"[{FUSED_IR_SWEEPS.start}, {FUSED_IR_SWEEPS.stop - 1}]"
                f"; got {ir_sweeps} (use 'segmented')")
        return KernelPlan(mode="segmented", **forms)
    if mode == "fused" and _needs_host_factor(factors):
        # explicit fused cannot serve these factors: the tail handoff
        # and in-loop rho adaptation would call _factorize in-trace on
        # non-shared f64 KKTs whose device inverse is not trusted on
        # this backend (qp_solver._device_f64_linalg_trusted). A config
        # error here beats NaN solves deep inside the jit.
        raise ValueError(
            "kernel mode 'fused' cannot serve non-shared f64 factors "
            "whose rho adaptation must refactorize on the host "
            "(untrusted f64 device linalg on this backend); use "
            "'segmented', or 'auto' which falls back automatically")
    if resolve_mode(mode, factors) == "segmented":
        return KernelPlan(mode="segmented", **forms)
    split = isinstance(factors.A_s, SplitMatrix)
    use_linv = False
    if split:
        n = factors.A_s.shape[-1]
        if l_inv == "on":
            use_linv = True
        elif l_inv == "auto":
            # budget = TAIL only: the f32 bulk phase never applies the
            # explicit inverse (un-refined solves hand L.tri to the
            # componentwise-stable back-substitution — see LInv); and
            # at this (n, rows) an apply must beat the prepared one
            use_linv = l_inv_profitable(n, s_chunk, tail_iter, ir_sweeps)
    A_lo = None
    if precision in ("mixed", "df32"):
        if split:
            A_hi = factors.A_s.hi
            pk_hi = factors.A_s.pk_hi
            A_lo = A_hi if pk_hi is None else PackedMatrix(A_hi, pk_hi)
        else:
            # non-split mixed: the bulk casts the dense operand
            # in-trace, exactly as qp_solve_mixed does eagerly
            A_lo = factors.A_s
    return KernelPlan(mode="fused", l_inv=use_linv, A_lo=A_lo, **forms)


def kernel_solve(plan: KernelPlan, factors, data, q, state, *,
                 precision, max_iter, tail_iter, e_pri, e_dua,
                 stall_rel, polish, polish_chunk, ir_sweeps,
                 check_every=25, polish_iters=12, adaptive_rho=True,
                 donate=False):
    """The fused-mode twin of core/ph._solver_call's segmented
    dispatch: same (state, x, yA, yB) contract, same tolerance policy
    (the caller computed e_pri/e_dua), one device program per call.
    ``adaptive_rho=False`` freezes the stepsize trajectory — the
    incumbent-pool evaluator needs it because shared-mode adaptation
    pools statistics over rows that include INFEASIBLE candidates
    (doc/incumbents.md)."""
    t0 = time.perf_counter()
    if precision in ("mixed", "df32"):
        # the split (df32) representation never polishes (_solve_impl
        # forces it off), so the flag must not reach the jit as a
        # STATIC: iter-0 (polish on) and hot (polish off) solves would
        # compile the same UC-width program twice — measured 14.5-19 s
        # and ~11 GiB of host memory for the second copy on the chip
        # machine (CHANGES.md PR 24)
        polish = polish and not isinstance(factors.A_s, SplitMatrix)
        st, x, yA, yB = fused_mixed_solve(
            factors, plan.A_lo, data, q, state, bulk_iter=max_iter,
            tail_iter=tail_iter, check_every=check_every, eps_abs=e_pri,
            eps_rel=e_pri, eps_abs_dua=e_dua, eps_rel_dua=e_dua,
            polish=polish, polish_iters=polish_iters,
            polish_chunk=polish_chunk, stall_rel=stall_rel,
            ir_sweeps=ir_sweeps, l_inv=plan.l_inv,
            adaptive_rho=adaptive_rho, donate=donate,
            build_log=plan.linv_build)
        tag = "fused-mixed"
    else:
        st, x, yA, yB = qp_solve(
            factors, data, q, state, donate=donate, max_iter=max_iter,
            check_every=check_every, eps_abs=e_pri, eps_rel=e_pri,
            polish=polish, polish_iters=polish_iters,
            polish_chunk=polish_chunk, eps_abs_dua=e_dua,
            eps_rel_dua=e_dua, stall_rel=stall_rel, ir_sweeps=ir_sweeps,
            adaptive_rho=adaptive_rho)
        tag = "fused-native"
    # same observability contract as the segmented drivers' per-segment
    # stamps (counter + optional MPISPPY_TPU_SOLVE_TRACE event), one
    # stamp per fused program. ``kernel.fused_iters`` is deliberately
    # NOT booked here: reading ``int(st.iters)`` now would block on the
    # whole fused program and serialize chunk k's solve with chunk
    # k+1's dispatch — the exact overlap fusion exists to create. The
    # core/ph callers book it after their existing post-solve sync
    # (the chunked loop's phase-honesty block / _ph_step's), where the
    # scalar read is a copy, not a stall.
    _trace_seg(tag, t0, st)
    return st, x, yA, yB


def est_hbm_bytes_per_iter(*, n, m, s_chunk, pk_pass_bytes=None,
                           ir_sweeps=1, l_inv=True, block_dtype="f32",
                           factor_bytes=4, vec_bytes=8):
    """Traffic model of ONE fused df32 tail iteration (per chunk);
    benchmarks/bytes_model.py is held equal to it (doc/roofline.md §6):

      factor applies : 2 triangle passes x (1 seed + ir_sweeps IR
                       solves) x n² x 4 B — a FULL square a pass:
                       exact for an L⁻¹ matmul, twice what a prepared
                       substitution reads (PERF.md §7 row 10; the
                       signature keeps ``l_inv``, the price ignores
                       it);
      A passes       : (2 + 2·ir_sweeps) packed split passes (1 rhs Aᵀy
                       + ir_sweeps x (Ax + Aᵀy) + 1 zAx) over the
                       hi+lo packed operand bytes (dense m·n·8 when
                       unpacked);
      vectors        : ~6 (S, m)/(S, n) f64 sweeps (rhs assembly,
                       projections, dual updates).

    Returns {"tail": bytes, "bulk": bytes}; the bulk model books f32
    vectors/factor. ``block_dtype="bf16"`` (halved bulk A bytes) prices
    a storage the program no longer has: the signature is pinned by
    benchmarks/tests/test_yardstick.py, which holds this function equal
    to benchmarks/bytes_model.py for that value too (ROADMAP C2)."""
    a_pass = pk_pass_bytes if pk_pass_bytes is not None else m * n * 8
    tail_factor = 2 * (1 + int(ir_sweeps)) * n * n * factor_bytes
    tail_a = (2 + 2 * int(ir_sweeps)) * a_pass
    tail_vec = 6 * (m + n) * s_chunk * vec_bytes
    bulk_a_pass = a_pass / 2  # hi only, no lo operand in the bulk
    if block_dtype == "bf16":
        bulk_a_pass /= 2
    bulk = int(2 * n * n * factor_bytes + 2 * bulk_a_pass
               + 6 * (m + n) * s_chunk * 4)
    return {"tail": int(tail_factor + tail_a + tail_vec), "bulk": bulk}


def est_l_inv_build_bytes(*, n, factor_bytes=4):
    """HBM bytes the explicit inverse's build cannot do without
    (``qp_solver._make_l_inv``): the factor's computed half read once
    and the inverse's half written once, n² · ``factor_bytes`` in all.
    A floor, not a traffic count: the panel build re-reads the panel's
    rows so far at every block row. benchmarks/linv_bytes_model.py is
    the benchmark's own copy (held equal by benchmarks/tests)."""
    return int(n) * int(n) * int(factor_bytes)
