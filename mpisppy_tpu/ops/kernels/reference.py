"""The kernel layer's fused mixed/df32 program (an XLA fused scan):
one full precision-escalated ADMM solve — f32 bulk
phase, factor handoff, accurate tail, polish — traced as a SINGLE
device program, so no iterate, factor, or residual ever round-trips
through the host between phases.

What this removes, relative to the segmented driver it replaces
(ops/qp_solver.qp_solve_segmented / qp_solve_mixed):

 - the per-segment host dispatch + blocking ``int(state.iters)`` D2H
   readback (one per ~100-500 iterations per chunk — at uc1024 scale,
   8 chunks x 5+ segments of sync per PH iteration);
 - the per-phase state casts materialized between separate jits (the
   lo->hi handoff now fuses into the tail's first iteration);
 - the host's opportunity to interleave — the whole solve is one
   enqueue, so chunk k+1's assembly genuinely overlaps chunk k's solve
   in the pipelined PH loop instead of waiting on segment syncs.

The MATH is deliberately not new: both phases call the same
``_solve_impl`` body every segmented solve runs, so this program is
bit-compatible with ``segmented`` whenever the iteration budget fits
one segment (the micro-parity CI test pins that at 1e-10), and
tolerance-equivalent beyond (segment boundaries reset the stall window
and rho-adaptation cadence, which a continuous loop does not — see
doc/kernels.md).

One roofline trade lives here (doc/roofline.md §5 headroom item 1),
``l_inv``: the df32 tail's two triangular solves become two MXU
matmuls by carrying the EXPLICIT L⁻¹ (qp_solver.LInv) in the solver
state, behind ``l_inv_profitable`` (the inverse's build must amortize
over the iteration budget, and its apply must beat the prepared
substitution's at this width and row count).

A solve that goes wrong is caught by the SAME df32 gate machinery that
already guards the segmented path: the chunked PH loop's quality gate
retries flagged chunks in native precision through the segmented
driver (core/ph._solve_loop_chunked pass 2), which does not use the
fused program — the recovery path IS the full-precision fallback.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...utils.runtime import compile_serialized
from ..qp_solver import (_TRI_BLOCK, LInv, PackedMatrix, QPData, QPState,
                         SplitMatrix, _cast_floats, _factorize, _make_l_inv,
                         _prepare_factor, _raw_factor, _solve_impl,
                         l_inv_panels, make_l_inv)

__all__ = ["fused_mixed_solve", "l_inv_profitable"]


# ---------------- roofline trade guard ----------------

# Seconds of ONE M⁻¹ apply (all rows of a device call) in either form,
# fitted to four chip readings on the UC factor (TPU v5e, n = 13,056,
# REPS applies chained in one loop: PERF.md §6, PR 41's Step 0):
#
#   rows   prepared substitution   two products with L⁻¹
#    64        1.733 ms                 1.923 ms
#   128        2.214 ms                 2.849 ms
#
# The substitution is 2·⌈n/_TRI_BLOCK⌉ sequential block steps plus its
# triangular products (2·rows·n² flops); the inverse is two FULL
# (rows, n) × (n, n) products at HIGHEST (4·rows·n² flops: 93% of the
# chip's 197e12 / 6 at 128 rows) and never less than reading both
# squares (87% of 819 GB/s at 64 rows).
_PREP_STEP_S = 6.1e-6      # (1.733 ms − its products) / 204 steps
_PREP_FLOPS = 4.5e13       # 0.481 ms more for 64 more rows
_LINV_FLOPS = 3.06e13      # 2.849 ms at 128 rows
_LINV_BYTES_S = 7.09e11    # 1.923 ms at 64 rows


def prepared_apply_s(n, rows):
    """Modelled seconds of one prepared M⁻¹ apply (see the table)."""
    return 2 * -(-n // _TRI_BLOCK) * _PREP_STEP_S \
        + 2 * rows * n * n / _PREP_FLOPS


def l_inv_apply_s(n, rows):
    """Modelled seconds of one explicit-inverse M⁻¹ apply."""
    return max(2 * n * n * 4 / _LINV_BYTES_S, 4 * rows * n * n / _LINV_FLOPS)


def l_inv_profitable(n, s_chunk, tail_iter, ir_sweeps=1):
    """Whether the tail carries the explicit L⁻¹. Two tests, both from
    what the program can observe (n, rows per device call, the tail's
    budget):

    The build must amortize. The inverse back-substitutes n RHS
    columns ONCE; only the TAIL applies it (``s_chunk`` columns
    ``(1 + ir_sweeps)`` times per iteration — the f32 bulk hands
    ``LInv.tri`` to the plain back-substitution, see qp_solver.LInv),
    so the break-even test is one tail's column solves >= the build's
    n. That is deliberately the margin, not a multiple: the df32 chunk
    chain flows ONE factor across every chunk and every warm-started
    PH iteration until rho refactorizes, so each solve past the first
    applies the same inverse for free. A short exploratory solve
    (small s_chunk·tail) must not pay an (n, n) inversion it never
    recoups.

    And an apply must beat the PREPARED substitution's, which is what
    the inverse stands in for since qp_solver.PreparedFactor:
    ``l_inv_apply_s < prepared_apply_s``. The inverse removes the
    substitution's sequential block steps and pays twice its flops and
    bytes, so it wins where the steps dominate (narrow factors, few
    rows: 71 µs against 85 at sslp's (520, 2000)) and loses at UC
    width: measured there, a df32 tail iteration is 5.36 ms prepared
    against 5.82 with L⁻¹ at 64 rows and 7.08 against 8.91 at 128, the
    steady hot PH iteration at 128 rows 0.518 s against 0.62–0.85, with
    2.3 GB less HBM (PERF.md §6, PR 41). The constants are the v5e's;
    no other backend has a reading, and the trade is the same in kind
    there."""
    rows = max(int(s_chunk), 1)
    applies = int(tail_iter) * (1 + int(ir_sweeps)) * rows
    return applies >= int(n) \
        and l_inv_apply_s(int(n), rows) < prepared_apply_s(int(n), rows)


# ---------------- the fused mixed/df32 program ----------------

def _fused_mixed_impl(factors, A_lo, data, q, iterates, aux,
                      eps_abs, eps_rel, eps_abs_dua, eps_rel_dua, *,
                      bulk_iter, tail_iter, check_every, adaptive_rho,
                      polish, polish_iters, polish_chunk, stall_rel,
                      ir_sweeps, l_inv, alpha=1.6):
    """Traceable body of the fused precision-escalated solve. Faithful
    to qp_solve_mixed's phase semantics (same eps floors, same factor
    handoff, same budget split) with the host segment loops replaced by
    the in-jit while_loops ``_solve_impl`` already owns.

    ``iterates`` = (x, yA, yB, zA, zB) — DONATED (see
    _fused_mixed_jit_donated); ``aux`` = (L, rho_scale, iters) — NEVER
    donated: the df32 chunked
    loop deliberately shares one flowed factor across every chunk state
    (core/ph pass-3 unify), so L is not uniquely owned and must be
    copied, exactly as qp_solve_mixed's ``owned_lo = donate and not
    split`` protects it today."""
    x, yA, yB, zA, zB = iterates
    L, rho_scale, iters0 = aux
    S = x.shape[0]
    dt_hi = x.dtype
    inf0 = jnp.full((S,), jnp.inf, dt_hi)
    state = QPState(x=x, yA=yA, yB=yB, zA=zA, zB=zB, L=L,
                    rho_scale=rho_scale, iters=iters0,
                    iters_lo=jnp.zeros((), jnp.int32),
                    refactors=jnp.zeros((), jnp.int32), pri_res=inf0,
                    dua_res=inf0, pri_rel=inf0, dua_rel=inf0)
    lo = jnp.float32
    split = isinstance(factors.A_s, SplitMatrix)
    if not isinstance(A_lo, (SplitMatrix, PackedMatrix)) \
            and getattr(A_lo, "dtype", lo) != lo:
        # non-split mixed: the plan stages the RAW dense operand and
        # the bulk casts it in-trace, exactly as qp_solve_mixed's eager
        # _cast_floats does (a packed A_lo is already f32 storage)
        A_lo = A_lo.astype(lo)

    # lo-phase operands: factors cast around the pre-staged A_lo (A_s
    # detached first: a cast of it would be discarded by the next line)
    f_lo = _cast_floats(factors._replace(A_s=jnp.zeros((), lo)), lo)
    f_lo = f_lo._replace(A_s=A_lo)
    d_lo = QPData(P_diag=data.P_diag.astype(lo), A=A_lo,
                  l=data.l.astype(lo), u=data.u.astype(lo),
                  lb=data.lb.astype(lo), ub=data.ub.astype(lo))
    st_lo = _cast_floats(state, lo)
    L_lo0, rho_lo0 = st_lo.L, st_lo.rho_scale
    if split and isinstance(L_lo0, LInv):
        # the bulk never applies the explicit inverse (its un-refined
        # x-update hands L.tri to the back-substitution — see LInv), so
        # carry the RAW factor through the bulk loop: an LInv carry
        # would make every in-bulk rho refactorization rebuild an n-RHS
        # inverse it immediately discards. The handoff below restores
        # the flowed inverse when rho never moved, and builds a fresh
        # one exactly once when it did. The raw factor is PREPARED for
        # the bulk's substitution (qp_solver.PreparedFactor: its
        # diagonal blocks inverted once per solve, not per iteration).
        st_lo = st_lo._replace(L=_prepare_factor(L_lo0.tri))
    if not split:
        st_lo = st_lo._replace(L=_factorize(f_lo, st_lo.rho_scale))
    # the f32 phase is a WARM START for the tail: same noise-floor
    # clamps as qp_solve_mixed
    eps_lo = jnp.maximum(jnp.asarray(eps_abs, lo), 1e-4)
    eps_rel_lo = jnp.maximum(jnp.asarray(eps_rel, lo), 1e-3)
    eps_rel_lo_dua = jnp.maximum(jnp.asarray(eps_rel_dua, lo), 1e-2)
    # qp.bulk / qp.handoff / qp.tail: op metadata only (see the note at
    # qp_solver._Ax) — the fused program's phases, named for xprof
    with jax.named_scope("qp.bulk"):
        st_lo, _, _, _ = _solve_impl(
            f_lo, d_lo, q.astype(lo), st_lo, bulk_iter, check_every,
            eps_lo, eps_rel_lo, alpha, adaptive_rho, False, polish_iters,
            0, eps_lo, eps_rel_lo_dua, stall_rel)

    # handoff: rho and (in split mode) the f32 factor carry over — the
    # factorization's (n, n) transients are the biggest allocations in
    # the whole solve path, so the tail must not rebuild one the bulk
    # already holds
    with jax.named_scope("qp.handoff"):
        rho_hi = st_lo.rho_scale.astype(dt_hi)
        L_lo = st_lo.L
        st_hi = _cast_floats(st_lo._replace(L=jnp.zeros((), lo)), dt_hi)
        if split:
            L_hi = L_lo
            if l_inv and not isinstance(L_hi, LInv):
                # the bulk carries the raw factor (stripped above), so THIS
                # is where the tail's explicit inverse comes from. The
                # factor is a pure function of rho_scale, so when the
                # bulk's rho adaptation never moved it the flowed inverse
                # from the chunk chain is still exact — reuse it; build a
                # fresh one (once per solve, not once per in-bulk
                # refactorization) only when rho actually changed.
                if isinstance(L_lo0, LInv):
                    L_hi = jax.lax.cond(
                        jnp.all(st_lo.rho_scale == rho_lo0),
                        lambda: L_lo0, lambda: _make_l_inv(L_lo))
                else:
                    L_hi = _make_l_inv(L_hi)
        else:
            L_hi = _factorize(factors, rho_hi)
        st_hi = st_hi._replace(L=L_hi, rho_scale=rho_hi)
    with jax.named_scope("qp.tail"):
        st, x_un, yA_un, yB_un = _solve_impl(
            factors, data, q, st_hi, tail_iter, check_every, eps_abs,
            eps_rel, alpha, adaptive_rho, polish, polish_iters,
            polish_chunk, eps_abs_dua, eps_rel_dua, stall_rel, ir_sweeps)
    st = st._replace(iters=st_lo.iters + st.iters, iters_lo=st_lo.iters,
                     refactors=st_lo.refactors + st.refactors)
    return st, x_un, yA_un, yB_un


# ``adaptive_rho`` is deliberately NOT a static, and there is no
# non-donating twin: at UC width every distinct program is minutes of
# compile and ~11 GiB of host memory (CHANGES.md PR 24), so ONE
# executable serves the hub's first pass and its donating hot passes,
# the Lagrangian spoke and the frozen-rho incumbent pool. It consumes
# the ITERATE buffers only (see _fused_mixed_impl on why aux must be
# copied); a caller that keeps its state passes private copies of those
# (S, n + m)-sized arrays (fused_mixed_solve, ``donate=False``).
_FUSED_STATICS = ("bulk_iter", "tail_iter", "check_every", "polish",
                  "polish_iters", "polish_chunk", "stall_rel",
                  "ir_sweeps", "l_inv", "alpha")
_fused_mixed_jit_donated = compile_serialized(
    jax.jit(_fused_mixed_impl, static_argnames=_FUSED_STATICS,
            donate_argnames=("iterates",)), _FUSED_STATICS)


def fused_mixed_solve(factors, A_lo, data, q, state, *, bulk_iter,
                      tail_iter, check_every, eps_abs, eps_rel,
                      eps_abs_dua, eps_rel_dua, polish, polish_iters,
                      polish_chunk, stall_rel, ir_sweeps, l_inv,
                      adaptive_rho=True, donate=False, build_log=None):
    """One fused mixed/df32 solve call (see _fused_mixed_impl).
    ``l_inv`` states arriving with a 2-D f32 Cholesky factor (prepared
    or bare) are wrapped to LInv EAGERLY so the jit sees one pytree
    structure for the whole chunk chain (a mid-chain structure flip
    would recompile the UC-sized program). That build is the span
    ``qp.l_inv_build``; it waits for the inverse (a cold state's first
    solve, which nothing overlaps), so its seconds are the build's, and
    ``build_log`` (the plan's ``KernelPlan.linv_build``) adds them
    up."""
    if l_inv and not isinstance(state.L, LInv):
        L = _raw_factor(state.L)
        if getattr(L, "ndim", 0) == 2 and L.dtype == jnp.float32:
            obs.counter_add("kernel.l_inv_factorizations")
            n = int(L.shape[-1])
            shape = {"n": n, "panels": l_inv_panels(n)}
            with obs.span("qp.l_inv_build", cat="qp", args=shape) as sp:
                F = make_l_inv(L)
                # lint: ok[SYNC001] a cold state's one eager build, ahead of its first solve: the span's seconds are the build's only if it waits
                jax.block_until_ready(F.inv)
            state = state._replace(L=F)
            if build_log is not None:
                build_log.update(shape,
                                 builds=build_log.get("builds", 0) + 1,
                                 seconds=build_log.get("seconds", 0.0)
                                 + sp.seconds)
    iterates = (state.x, state.yA, state.yB, state.zA, state.zB)
    if not donate:
        iterates = tuple(jnp.copy(a) for a in iterates)
    aux = (state.L, state.rho_scale, state.iters)
    fn = _fused_mixed_jit_donated
    kw = dict(bulk_iter=int(bulk_iter), tail_iter=int(tail_iter),
              check_every=int(check_every),
              adaptive_rho=np.bool_(adaptive_rho), polish=bool(polish),
              polish_iters=int(polish_iters),
              polish_chunk=int(polish_chunk), stall_rel=float(stall_rel),
              ir_sweeps=int(ir_sweeps), l_inv=bool(l_inv))
    return fn(factors, A_lo, data, q, iterates, aux,
              eps_abs, eps_rel, eps_abs_dua, eps_rel_dua, **kw)
