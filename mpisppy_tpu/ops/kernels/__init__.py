"""Kernel-backend layer for the ADMM subproblem solver: one fused
device program per solve instead of a host-driven segment loop.

Three modes, selected by ``subproblem_kernel_mode`` (utils/config /
engine options; anatomy in doc/kernels.md):

  ``segmented``  today's ops/qp_solver host-segmented drivers,
                 BIT-FOR-BIT — the dispatch below is never entered, so
                 the existing pipeline-equivalence suite is the
                 guarantee;
  ``fused``      the whole solve (f32 bulk + factor handoff + accurate
                 tail + polish) as one device program. Backends:
                 ``reference`` (XLA fused-scan — default everywhere,
                 the correctness oracle; reference.py) and ``pallas``
                 (the opt-in TPU VMEM-resident iteration block for
                 small dense f32 shapes; a solve outside its scope is
                 a config error, never a silent demotion;
                 pallas_kernel.py);
  ``auto``       fused wherever the solve is eligible (see
                 resolve_mode), segmented otherwise — the default.

Inside the fused program ride the two doc/roofline.md §5 trades:
explicit L⁻¹ matmuls for the df32 tail's triangular solves (behind
``l_inv_profitable``) and bf16 storage of the packed A-blocks for the
f32 bulk phase (explicit opt-in, behind ``bf16_gate`` with f32
fallback on trip — see prepare() on why "auto" never engages it).
Recovery solves (chunk retries, the scenario hospital) ALWAYS take
the segmented path in native precision — the existing quality-gate
machinery doubles as the fused path's full-precision fallback.

Counters: ``kernel.fused_iters`` (ADMM iterations executed by fused
programs), ``kernel.l_inv_factorizations`` (eager L⁻¹ builds),
``kernel.bf16_fallbacks`` (gate trips) — catalogued in
doc/observability.md.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ... import obs
from ...utils.config import (FUSED_IR_SWEEPS, KERNEL_BACKENDS,
                             KERNEL_BLOCK_DTYPES as BLOCK_DTYPES,
                             KERNEL_L_INV_MODES as L_INV_MODES,
                             KERNEL_MODES)
from ..qp_solver import (LInv, PackedMatrix, SplitMatrix,
                         _needs_host_factor, _trace_seg, qp_solve)
from . import pallas_kernel
from .reference import (BF16_GATE_REL, bf16_gate, bf16_packed,
                        fused_mixed_solve, l_inv_profitable)


def resolve_mode(mode: str, factors) -> str:
    """``auto`` resolution. A solve is fused-eligible unless its rho
    adaptation must run on the HOST (non-shared f64 factors on a
    backend with untrusted f64 device linalg — qp_solver
    ._needs_host_factor): the fused program cannot call back out for
    the host-exact refactorization mid-loop. Program length is no
    criterion: one program of 36,794 f64 matmul iterations ran 59.7 s
    to completion on the attached v5e (CHANGES.md PR 24)."""
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
    backend: str                 # "reference" | "pallas" (effective)
    precision: str               # the precision the plan serves
    l_inv: bool = False
    block_dtype: str = "f32"     # "f32" | "bf16" (effective)
    A_lo: object = None          # bulk-phase A_s operand (mixed/df32)
    bf16_err: float | None = None
    # host copy of factors.sigma, read ONCE at prepare() time: the
    # pallas block needs it as a compile-time constant, and reading it
    # per solve call would put a scalar D2H on every chunk dispatch
    # (graft-lint SYNC001 caught exactly that)
    sigma_host: float | None = None

    def descriptor(self) -> dict:
        """The bench/telemetry kernel block."""
        return {"mode": self.mode, "backend": self.backend,
                "l_inv": bool(self.l_inv),
                "block_dtype": self.block_dtype}


SEGMENTED_PLAN = KernelPlan(mode="segmented", backend="reference",
                            precision="native")


def prepare(factors, *, mode="auto", backend="reference",
            l_inv="auto", block_dtype="auto", precision="native",
            bulk_iter=0, tail_iter=0, ir_sweeps=1, s_chunk=1):
    """Resolve the kernel decisions for one mode's factors (host,
    eager, once per factorization): mode, effective backend, the L⁻¹
    profitability verdict, and — for mixed/df32 — the bulk phase's
    A operand with bf16 blocks substituted when the gate admits them.

    Out-of-band ``ir_sweeps`` (the fused program unrolls them
    statically — utils/config.FUSED_IR_SWEEPS): explicit ``fused`` is a
    config error the engine raises before any trace; ``auto`` falls
    back to segmented here, so exotic sweep counts keep working through
    the host-segmented drivers."""
    if int(ir_sweeps) not in FUSED_IR_SWEEPS:
        if mode == "fused":
            raise ValueError(
                f"kernel mode 'fused' supports ir_sweeps in "
                f"[{FUSED_IR_SWEEPS.start}, {FUSED_IR_SWEEPS.stop - 1}]"
                f"; got {ir_sweeps} (use 'segmented')")
        return SEGMENTED_PLAN
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
        return SEGMENTED_PLAN
    split = isinstance(factors.A_s, SplitMatrix)
    use_linv = False
    if split:
        n = factors.A_s.shape[-1]
        if l_inv == "on":
            use_linv = True
        elif l_inv == "auto":
            # budget = TAIL only: the f32 bulk phase never applies the
            # explicit inverse (un-refined solves hand L.tri to the
            # componentwise-stable back-substitution — see LInv)
            use_linv = l_inv_profitable(n, s_chunk, tail_iter, ir_sweeps)
    A_lo, bdt, err = None, "f32", None
    if precision in ("mixed", "df32"):
        if split:
            A_hi = factors.A_s.hi
            pk_hi = factors.A_s.pk_hi
            if pk_hi is not None:
                pk_bulk = pk_hi
                # bf16 blocks are EXPLICIT OPT-IN ("bf16"), never
                # "auto": measured on the UC LP relaxation, the ~2⁻⁸
                # coefficient rounding relocates the degenerate
                # optimum by tens of percent while every residual
                # converges — an error the residual-based gates
                # (quantization pre-gate here, quality-gate recovery
                # in the chunked loop) are structurally blind to.
                # See doc/kernels.md §bf16 for the measurement; the
                # driver-run objective cross-checks are the evidence
                # that could justify widening this per model family.
                if block_dtype == "bf16":
                    trips, err = bf16_gate(pk_hi)
                    if trips:
                        obs.counter_add("kernel.bf16_fallbacks")
                        obs.event("kernel.bf16_fallback",
                                  {"quant_err": err,
                                   "gate": BF16_GATE_REL})
                    else:
                        pk_bulk = bf16_packed(pk_hi)
                        bdt = "bf16"
                A_lo = PackedMatrix(A_hi, pk_bulk)
            else:
                A_lo = A_hi
        else:
            # non-split mixed: the bulk casts the dense operand
            # in-trace, exactly as qp_solve_mixed does eagerly
            A_lo = factors.A_s
    if backend == "pallas" and not (
            precision == "native"
            and getattr(factors.A_s, "ndim", 0) == 2
            and not isinstance(factors.A_s, (SplitMatrix, PackedMatrix))):
        # a backend that was ASKED for and cannot serve the solve is a
        # config error, like explicit ``fused`` above — never a demotion
        # to ``reference`` that leaves the run reporting a kernel it
        # did not run
        raise ValueError(
            "kernel backend 'pallas' serves native-precision solves "
            "over one shared dense A only (see ops/kernels/"
            f"pallas_kernel.py); got precision={precision!r}, A_s="
            f"{type(factors.A_s).__name__} ndim="
            f"{getattr(factors.A_s, 'ndim', None)}. Use the default "
            "'reference' backend")
    # host copy of sigma, read once here (prepare is host+eager by
    # contract) so the per-solve pallas launch never pays a scalar
    # D2H; partial factor stubs (scope tests) simply carry None and
    # fused_admm_block's direct-caller fallback covers them
    sig = getattr(factors, "sigma", None)
    return KernelPlan(mode="fused", backend=backend,
                      precision=precision, l_inv=use_linv,
                      block_dtype=bdt, A_lo=A_lo, bf16_err=err,
                      sigma_host=None if sig is None else float(sig))


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
    if plan.backend == "pallas":
        # the state-dependent half of the scope check (operator form,
        # dtypes, VMEM estimate — prepare() only sees the factors)
        why = pallas_kernel.pallas_scope_reason(factors, state)
        if why is not None:
            raise ValueError(
                f"kernel backend 'pallas' cannot serve this solve: {why}"
                ". Use the default 'reference' backend")
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
            adaptive_rho=adaptive_rho, donate=donate)
        tag = "fused-mixed"
    elif plan.backend == "pallas":
        # the pallas block runs the WHOLE budget at fixed rho (the
        # kernel cannot refactorize — pallas_kernel.py), then the
        # oracle finisher polishes and unscales the block's iterates
        # through the very code the reference runs. The finisher
        # recomputes the residuals post-polish, so the block's fused
        # pri/dua outputs serve the parity tests and the on-chip
        # production tiling (where they gate WITHOUT leaving VMEM),
        # not this driver. ``donate`` flows to the finisher: ``st``
        # aliases the block's outputs plus the caller's factor/rho
        # buffers, exactly the ownership donate=True relinquishes.
        if obs.enabled():
            # roofline capture for the pallas block (obs/profile.py);
            # degrades to profile.unavailable if the backend's cost
            # model cannot see through the pallas lowering
            from ...obs import profile as _profile
            x_s, yA_s, yB_s, zA_s, zB_s, _, _ = _profile.call(
                "kernel.pallas", pallas_kernel.fused_admm_block,
                factors, data, q, state, n_steps=max_iter,
                sigma=plan.sigma_host)
        else:
            x_s, yA_s, yB_s, zA_s, zB_s, _, _ = \
                pallas_kernel.fused_admm_block(
                    factors, data, q, state, n_steps=max_iter,
                    sigma=plan.sigma_host)
        st = state._replace(x=x_s, yA=yA_s, yB=yB_s, zA=zA_s, zB=zB_s)
        st, x, yA, yB = qp_solve(
            factors, data, q, st, donate=donate, max_iter=0,
            check_every=check_every, eps_abs=e_pri, eps_rel=e_pri,
            polish=polish, polish_iters=polish_iters,
            polish_chunk=polish_chunk, eps_abs_dua=e_dua,
            eps_rel_dua=e_dua, stall_rel=stall_rel, ir_sweeps=ir_sweeps,
            adaptive_rho=adaptive_rho)
        st = st._replace(iters=jnp.asarray(int(max_iter), jnp.int32))
        tag = "fused-pallas"
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
    """doc/roofline.md traffic model of ONE fused df32 tail iteration
    (per chunk), the number the bench's uc1024 row records so a driver
    re-run can confirm the predicted drop:

      factor applies : 2 triangle passes x (1 seed + ir_sweeps IR
                       solves) x n² x 4 B — identical bytes for
                       triangular solves and L⁻¹ matmuls (the trade
                       converts latency, not traffic; l_inv=False only
                       flags that the latency win is off);
      A passes       : (2 + 2·ir_sweeps) packed split passes (1 rhs Aᵀy
                       + ir_sweeps x (Ax + Aᵀy) + 1 zAx) over the
                       hi+lo packed operand bytes (dense m·n·8 when
                       unpacked);
      vectors        : ~6 (S, m)/(S, n) f64 sweeps (rhs assembly,
                       projections, dual updates).

    Returns {"tail": bytes, "bulk": bytes}; the bulk model halves the
    A-operand bytes under bf16 blocks and books f32 vectors/factor."""
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
