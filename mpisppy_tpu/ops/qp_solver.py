"""Batched dense ADMM QP/LP solver (OSQP-style), the framework's native
subproblem kernel.

The reference rents a commercial MIP solver per scenario through Pyomo
(ref. mpisppy/phbase.py:1304-1362, solve_loop :999) — one process-boundary
solver call per subproblem per PH iteration, which is where ~all of its
wall-clock goes. Here the whole scenario batch is solved simultaneously on
the TPU: every operation below is a batched matmul / triangular solve /
elementwise op over the leading scenario axis, so S scenarios cost one MXU
pass, not S solver calls.

Form:   min ½ xᵀ diag(P) x + qᵀx   s.t.  l ≤ A x ≤ u,  lb ≤ x ≤ ub.

Variable boxes are handled NATIVELY in the ADMM splitting (they are a
second, diagonal constraint block), not folded into A as identity rows:
the identity block's KKT contribution is a pure diagonal, so the fold
would only double the row count and materialize (S, n, n) of zeros.

Structure sharing: ``A`` (and ``P_diag``) may be given UNBATCHED —
``A (m, n)``, ``P_diag (n,)`` — when every scenario shares the same
matrix and only (c, l, u, lb, ub) differ (true for UC/sizes/sslp/hydro,
where scenarios differ in the rhs only). The KKT factorization is then a
single shared (n, n) Cholesky instead of (S, n, n), the per-iteration
matmuls become one (m, n) × (n, S) MXU pass, and HBM stops scaling as
S·n² — this is what makes the 1000-scenario north star
(ref. paperruns/larger_uc/1000scenarios_wind) fit one chip.

Method: ADMM as in OSQP (Stellato et al. 2020) with
 - Ruiz equilibration of the KKT matrix (bound rows enter analytically),
 - per-row stepsize rho (boosted on equality rows/fixed columns) with
   OSQP's adaptive rho rule, refactorizing inside the solve loop when the
   change exceeds 5x (tied to a single scalar in shared-structure mode so
   the factor stays shared),
 - the Cholesky factor of M = diag(P) + sigma*I + Aᵀdiag(ρ_A)A + diag(g²ρ_b)
   carried in the *solver state*: PH iterations change only q, so the
   factor and adapted rho persist across warm-started solves,
 - periodic residual checks inside a lax.while_loop (compiler-friendly
   control flow; no Python in the loop).

Why ADMM and not simplex/IPM: the iteration is pure BLAS-3 over the batch
(MXU-friendly, no pivoting/branching), tolerances ~1e-6..1e-8 in f64 and
~1e-4 in f32 are ample for PH/bounding, and the factor-caching matches PH's
access pattern exactly.

Known limitation: on scenarios whose optimum is DEGENERATE (more active
constraints than variables), the polished duals retain O(dual tolerance)
residual components along the rank-deficient directions, and the
certified dual bound is then loose by ~1e-4 RELATIVE (residual times the
widest variable box). Non-degenerate scenarios polish to machine-level
exactness. 1e-4 relative matches the reference's own target MIP gaps
(0.01-0.07%, see BASELINE.md), and the bound stays VALID either way.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.runtime import compile_serialized


# MPISPPY_TPU_SOLVE_TRACE=1: wall-time stamps per solver segment (each
# stamp forces a device sync, serializing host work behind device
# compute — a measurement tool, never a default). The r4 verdict's MFU
# question is unanswerable without knowing where a 15-second chunk solve
# actually spends its time: f32 bulk vs df32 tail vs handoffs.


def _trace_enabled() -> bool:
    """Re-read the env flag LAZILY on every segment: the historical
    import-time freeze meant tests (and long-lived processes) could
    never toggle the trace after the first ``import qp_solver``."""
    return bool(int(os.environ.get("MPISPPY_TPU_SOLVE_TRACE", "0") or 0))


def _trace_seg(tag, t0, state):
    obs.counter_add("qp.solve_segments")
    if not _trace_enabled():
        return
    jax.block_until_ready(state.x)
    dt = time.perf_counter() - t0
    iters = int(state.iters)
    pri = float(jnp.max(state.pri_rel))
    msg = (f"[solve-trace] {tag}: {dt:7.3f}s ran={iters:4d} "
           f"pri_rel_max={pri:.2e}")
    # telemetry first (structured, mergeable), raw stderr second (the
    # historical greppable form tools already parse)
    obs.event("qp.solve_segment",
              {"tag": tag, "seconds": dt, "iters": iters,
               "pri_rel_max": pri})
    # latency histogram: segment durations are multi-modal (f32 bulk
    # vs df32 tail vs polish) — the bucketed tails tell them apart
    # where a mean cannot
    obs.histogram_observe("qp.solve_segment_seconds", dt)
    print(msg, file=sys.stderr, flush=True)


class SplitMatrix(NamedTuple):
    """Double-float ("df32") matrix: hi + lo ≈ the f64 matrix, both f32.

    TPU MXUs have no f64 datapath — XLA emulates f64 matmuls by
    splitting BOTH operands into multiple f32 terms and materializing
    every cross product, which at reference-UC scale (25836 × 13056)
    exceeds HBM (measured: 17.6 G needed vs 15.75 G for ONE A @ x).
    The classic double-float compensation (Dekker 1971 two-term split)
    gets ~2× the f32 mantissa from THREE ordinary f32 MXU passes:

        A @ x ≈ hi @ x_hi + lo @ x_hi + hi @ x_lo      (drop lo·lo)

    with the three f32 products accumulated in f64 (cheap: products are
    (S, m)-shaped vectors, not matrices). Input quantization error
    drops from ~6e-8 to ~4e-15 relative; what remains is the f32
    accumulation noise of each pass (~1e-7 relative, sqrt(n)·eps32),
    which sets the ADMM residual floor — measured ample for the 1e-4
    solver-grade target where plain f32 plateaus at ~1e-2. This is the
    kernel's big-instance representation: no f64 copy of A ever sits
    in HBM and no emulated-f64 matmul is ever compiled.

    ``struct``/``pk_hi``/``pk_lo`` (optional): the structure-packed
    matvec form (see ops/packed.py). ``struct`` is the host-derived
    index skeleton attached at ship time; where packing pays for the
    rows a device call solves (``packed.pack_profitable``) setup gathers
    the SCALED hi/lo into ``pk_hi``/``pk_lo``, after which every
    _Ax/_ATy pass reads ~1.5% of the dense bytes (the r5 MFU fix — the
    round-4 dense kernel measured 3.8% MFU, its passes dominating HBM
    traffic); where it does not, the scaled pair keeps ``struct``
    alone and the matvecs are the dense split products, Aᵀy's leading
    pass summed by the skeleton's row classes (_ATy).
    The dense pair stays resident for the factorization matmul and
    support_touch."""
    hi: jax.Array
    lo: jax.Array
    struct: object = None      # packed.PackStructure | None
    pk_hi: object = None       # packed.Packed | None
    pk_lo: object = None       # packed.Packed | None

    @property
    def ndim(self):
        return self.hi.ndim

    @property
    def shape(self):
        return self.hi.shape

    @property
    def dtype(self):
        # the VALUE dtype the pair represents (consumers dispatch on it)
        return jnp.float64


class PackedMatrix(NamedTuple):
    """Single-precision matrix with a packed matvec form riding along:
    the f32 bulk phase's view of a packed SplitMatrix (dense ``hi`` for
    in-loop refactorization, packed for every matvec)."""
    dense: jax.Array
    pk: object                 # packed.Packed

    @property
    def ndim(self):
        return self.dense.ndim

    @property
    def shape(self):
        return self.dense.shape

    @property
    def dtype(self):
        return self.dense.dtype


def split_f32(a) -> SplitMatrix:
    """Two-term split of an f64 array (hi = f32 round, lo = residual)."""
    hi = a.astype(jnp.float32)
    lo = (a - hi.astype(jnp.float64)).astype(jnp.float32)
    return SplitMatrix(hi, lo)


def split_f32_np(a):
    """Host-numpy twin of split_f32 (the ONE split convention — data
    shipping and tests must not re-derive it). Returns (hi, lo)."""
    a = np.asarray(a, np.float64)
    hi = a.astype(np.float32)
    lo = (a - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


def merged64(A):
    """The f64 value of a SplitMatrix (or a plain array cast to f64).
    Materializes (m, n) in f64 — use only inside fused elementwise/
    reduce computations or on host."""
    if isinstance(A, SplitMatrix):
        return A.hi.astype(jnp.float64) + A.lo.astype(jnp.float64)
    return A.astype(jnp.float64) if hasattr(A, "astype") else A


class ScaledView(NamedTuple):
    """QPData.A as a VIEW over the factors' scaled matrix:
    A = diag(1/E) · A_s · diag(1/D). At df32 scale the raw split A and
    the scaled split A_s cannot both live in HBM (2.7 GB each on the
    reference-UC instance); once the base factors exist, engines swap
    their QPData.A for this view and the raw pair frees. Matvec
    consumers (_Ax/_ATy — residual checks, dual objectives, dives)
    dispatch on it transparently."""
    A_s: jax.Array          # SplitMatrix or dense (m, n)
    D: jax.Array            # (n,)
    E: jax.Array            # (m,)

    @property
    def ndim(self):
        return 2

    @property
    def shape(self):
        return self.A_s.shape

    @property
    def dtype(self):
        return jnp.float64


def host_dense_A(A):
    """Host numpy f64 of a QPData.A under any representation. A
    ScaledView at df32 scale would need a multi-GB device->host pull
    and a dense host copy — consumers must use the device dispatch
    paths instead."""
    if isinstance(A, ScaledView):
        raise TypeError("host_dense_A on a ScaledView: reconstructing "
                        "the dense matrix host-side defeats the view's "
                        "purpose; use _Ax/_ATy/support_touch on device")
    if isinstance(A, SplitMatrix):
        return np.asarray(A.hi, np.float64) + np.asarray(A.lo, np.float64)
    return np.asarray(A, np.float64)


@jax.jit
def _support_touch_jit(hi, viol):
    # jitted so the abs/mask/cast fuse into the matmul operand instead
    # of materializing eager (m, n) transients (GBs at df32 scale)
    supp = (jnp.abs(hi) > 1e-10).astype(jnp.float32)
    v = viol.astype(jnp.float32)
    if hi.ndim == 2:
        return v @ supp
    return jnp.einsum("sm,smn->sn", v, supp)


def support_touch(A, viol):
    """(S, n) column-touch counts of the (S, m) bool row mask ``viol``
    through A's sparsity support — on DEVICE for the big
    representations (the dive's targeted-repair column selection)."""
    hi = A
    if isinstance(A, ScaledView):
        hi = A.A_s
    if isinstance(hi, SplitMatrix):
        hi = hi.hi
    return _support_touch_jit(hi, jnp.asarray(viol))


class QPData(NamedTuple):
    """Stacked problem data; leading axis S = scenarios. ``A`` and
    ``P_diag`` may be unbatched ((m, n) / (n,)) when shared across the
    batch — see the module docstring. A shared ``A`` may further be a
    SplitMatrix (df32 big-instance representation)."""
    P_diag: jax.Array   # (S, n) or (n,) shared
    A: jax.Array        # (S, m, n) or (m, n) shared; maybe SplitMatrix
    l: jax.Array        # (S, m)
    u: jax.Array        # (S, m)
    lb: jax.Array       # (S, n)
    ub: jax.Array       # (S, n)


class QPFactors(NamedTuple):
    """Static setup artifacts (scaling + scaled matrices). Shapes follow
    QPData's sharing: batched (S, ...) or shared (no S axis)."""
    sigma: jax.Array       # scalar
    D: jax.Array           # (S, n) | (n,) column equilibration
    E: jax.Array           # (S, m) | (m,) row equilibration (A rows)
    Eb: jax.Array          # (S, n) | (n,) row equilibration (bound rows)
    cost_scale: jax.Array  # (S,) | () objective scaling
    A_s: jax.Array         # (S, m, n) | (m, n) scaled A
    P_s: jax.Array         # (S, n) | (n,) scaled P diagonal
    rho_A: jax.Array       # (S, m) | (m,) relative per-row rho (eq boosted)
    rho_b: jax.Array       # (S, n) | (n,) bound-row rho (fixed cols boosted)


class QPState(NamedTuple):
    """Warm-startable solver state; L and rho persist across solves."""
    x: jax.Array          # (S, n) scaled iterate
    yA: jax.Array         # (S, m) scaled row duals
    yB: jax.Array         # (S, n) scaled bound duals
    zA: jax.Array         # (S, m) scaled row slacks
    zB: jax.Array         # (S, n) scaled bound slacks
    L: jax.Array          # (S,n,n)|(n,n) KKT inverse (f64) / Cholesky (f32);
    #                       the shared f32 factor rides in a container:
    #                       PreparedFactor, or LInv under the L⁻¹ trade
    rho_scale: jax.Array  # (S,) | () multiplier on the rho patterns
    iters: jax.Array      # scalar total ADMM iterations in last solve
    iters_lo: jax.Array   # scalar: of those, the low-precision (f32 bulk)
    #                       phase's; iters - iters_lo is the accurate
    #                       tail's. 0 for a solve with no bulk phase.
    refactors: jax.Array  # scalar: in-loop rho refactorizations of the
    #                       last solve (= factor preparations; the host
    #                       refactorizations of untrusted-f64 backends
    #                       count under qp.host_rho_refactors instead)
    pri_res: jax.Array    # (S,) unscaled
    dua_res: jax.Array    # (S,) unscaled
    pri_rel: jax.Array    # (S,) pri_res / problem scale (feasibility metric)
    dua_rel: jax.Array    # (S,) dua_res / dual scale (drives host rho adapt)


# ``qp.*`` named scopes (here and in _solve_impl / the fused program):
# op METADATA only — no op, shape or output changes — so an xprof or
# Perfetto view of a capture taken with the HLO proto groups the
# program's anonymous ``fusion.N``s by solver phase (doc/observability.md).
@jax.named_scope("qp.Ax")
def _Ax(A, x):
    """A x with A (m,n) shared, (S,m,n) batched, SplitMatrix (df32),
    PackedMatrix, or ScaledView; x (S,n) -> (S,m). The split path runs
    three f32 MXU passes and accumulates in f64 (see SplitMatrix);
    packed representations route through ops/packed.py."""
    if isinstance(A, ScaledView):
        return _Ax(A.A_s, x / A.D) / A.E
    if isinstance(A, PackedMatrix):
        from .packed import pk_Ax
        return pk_Ax(A.pk, x)
    if isinstance(A, SplitMatrix):
        xh = x.astype(jnp.float32)
        xl = (x - xh.astype(jnp.float64)).astype(jnp.float32)
        if A.pk_hi is not None:
            from .packed import pk_Ax_split
            return pk_Ax_split(A.pk_hi, A.pk_lo, xh, xl)
        f64 = jnp.float64
        return ((xh @ A.hi.T).astype(f64) + (xh @ A.lo.T).astype(f64)
                + (xl @ A.hi.T).astype(f64))
    if A.ndim == 2:
        return x @ A.T
    return _batched_matvec(A, x)


@jax.named_scope("qp.ATy")
def _ATy(A, y):
    """Aᵀ y with A (m,n) shared, (S,m,n) batched, SplitMatrix,
    PackedMatrix, or ScaledView; y (S,m) -> (S,n)."""
    if isinstance(A, ScaledView):
        return _ATy(A.A_s, y / A.E) / A.D
    if isinstance(A, PackedMatrix):
        from .packed import pk_ATy
        return pk_ATy(A.pk, y)
    if isinstance(A, SplitMatrix):
        yh = y.astype(jnp.float32)
        yl = (y - yh.astype(jnp.float64)).astype(jnp.float32)
        if A.pk_hi is not None:
            from .packed import pk_ATy_split
            return pk_ATy_split(A.pk_hi, A.pk_lo, yh, yl)
        f64 = jnp.float64
        if A.struct is not None and A.struct.g_rows.shape[0]:
            # a structured matrix left dense (packed.pack_profitable):
            # the leading pass keeps the packed form's two partial
            # sums, local rows and global rows, apart until f64. One
            # f32 accumulation over ALL rows mixes the rho-weighted
            # equality rows with the coupling rows, and that rounding
            # is the tail's residual floor: twice the packed form's on
            # sslp_10_50 (PERF.md §6, PR 33). A zeroed y entry adds an
            # exact zero, so each dot sums its own class's rows only;
            # the two correction passes are 1e-7 of the first and need
            # no such care
            from .packed import global_row_mask
            g = global_row_mask(A.struct)
            lead = (jnp.where(g, 0, yh) @ A.hi).astype(f64) \
                + (jnp.where(g, yh, 0) @ A.hi).astype(f64)
        else:
            lead = (yh @ A.hi).astype(f64)
        return lead + (yh @ A.lo).astype(f64) + (yl @ A.hi).astype(f64)
    if A.ndim == 2:
        return y @ A
    return _batched_rmatvec(A, y)


# ---- batched float64 products: per-scenario matrices in native f64 ----
# The TPU has no float64 dot. Its compiler emulates a BATCHED f64
# ``dot_general`` as nested ``while`` loops over eight f32 limbs (~44
# trips of slice / update / select ops a product), which at a served
# farmer stack ((24, 7, 12)) was four fifths of the chip's time (PERF.md
# §6, PR 38). The same product written as a broadcast multiply and a sum
# over the contracted axis is ONE fusion of the soft-float element-wise
# f64 the rest of the ADMM body already runs on, IEEE f64 throughout.
# The rule is "always": on the chip the reduction was 40-50x faster at
# (24, 7, 12) and still 50x at (24, 700, 1200), where it streams the
# matrix at 650 GB/s of the 819 the HBM gives; the emulated dot reads at
# least those bytes, so no larger shape turns the order (sweep table and
# HLO census: doc/kernels.md §3d).

def _matvec_dot(M, v):
    return jnp.einsum("sij,sj->si", M, v)


def _matvec_reduce(M, v):
    return jnp.sum(M * v[:, None, :], axis=-1)


def _rmatvec_dot(M, v):
    return jnp.einsum("sij,si->sj", M, v)


def _rmatvec_reduce(M, v):
    # sums over the matrix's own row axis: no transposed copy of M is
    # carried beside it (on the chip faster than, or within 1% of, the
    # transposed copy summed over its minor axis at every swept shape)
    return jnp.sum(M * v[:, :, None], axis=1)


def f64_product_form(M) -> str | None:
    """``"reduce"`` / ``"dot"``: how THIS process's backend runs the
    batched products of a per-scenario float64 matrix ``M`` (anything
    with ``ndim`` 3 and that dtype); None for everything else (shared
    matrices, plain or in a split / packed / scaled container, are 2-D;
    the mixed bulk's batch is f32), whose products are not these."""
    if M.ndim != 3 or M.dtype != jnp.float64:
        return None
    return "reduce" if jax.default_backend() == "tpu" else "dot"


def _batched_product(M, v, reduce_form, dot_form):
    form = f64_product_form(M)
    if form is None:                 # an f32 batch (the mixed bulk): MXU
        return dot_form(M, v)
    # trace-time count of the products lowered each way on this backend
    obs.counter_add(f"kernel.f64_products_{form}")
    # chosen at lowering time, per platform (as _chol_solve does for a
    # PreparedFactor): CPU and GPU keep the library dot, and a program
    # compiled HERE for a described TPU takes the TPU form
    return jax.lax.platform_dependent(M, v, tpu=reduce_form,
                                      default=dot_form)


def _batched_matvec(M, v):
    """Rows of M v: M (S, r, c), v (S, c) -> (S, r)."""
    return _batched_product(M, v, _matvec_reduce, _matvec_dot)


def _batched_rmatvec(M, v):
    """Rows of Mᵀ v: M (S, r, c), v (S, r) -> (S, c)."""
    return _batched_product(M, v, _rmatvec_reduce, _rmatvec_dot)


def _ruiz_equilibrate(P_diag, A, iters=15):
    """Modified Ruiz equilibration of the KKT matrix [[P, Āᵀ],[Ā, 0]] with
    Ā = [A; I] — the identity (bound-row) block is handled analytically:
    its scaled row j is the single value g_j = Eb_j·D_j. Returns (D, E, Eb)
    with scaled P = D P D (diag), A = E A D, bound rows = diag(Eb·D).
    df32 callers pass the f32 hi part (see _qp_setup_split)."""
    n = A.shape[-1]
    m = A.shape[-2]
    bshape = A.shape[:-2]
    D = jnp.ones(bshape + (n,), A.dtype)
    E = jnp.ones(bshape + (m,), A.dtype)
    Eb = jnp.ones(bshape + (n,), A.dtype)

    def body(_, DEE):
        D, E, Eb = DEE
        As = E[..., :, None] * A * D[..., None, :]
        Ps = D * P_diag * D
        g = Eb * D
        cnorm = jnp.maximum(jnp.maximum(jnp.abs(Ps),
                                        jnp.max(jnp.abs(As), axis=-2)),
                            jnp.abs(g))
        rnorm = jnp.max(jnp.abs(As), axis=-1)
        d = jnp.where(cnorm < 1e-12, 1.0,
                      1.0 / jnp.sqrt(jnp.maximum(cnorm, 1e-12)))
        e = jnp.where(rnorm < 1e-12, 1.0,
                      1.0 / jnp.sqrt(jnp.maximum(rnorm, 1e-12)))
        eb = 1.0 / jnp.sqrt(jnp.maximum(jnp.abs(g), 1e-12))
        return D * d, E * e, Eb * eb

    D, E, Eb = jax.lax.fori_loop(0, iters, body, (D, E, Eb))
    return D, E, Eb


def _factorize(factors: QPFactors, rho_scale, keep=None):
    """EXPLICIT INVERSE of M = diag(P_s) + sigma I + A_sᵀ diag(ρ_A) A_s
    + diag(g²ρ_b). Shared mode (A_s (m,n), rho_scale scalar) returns one
    (n, n) inverse.

    Why an inverse and not the Cholesky factor (f64): the ADMM x-update
    runs thousands of times per solve, and a TPU triangular solve is a
    SEQUENTIAL back-substitution — milliseconds of latency at small
    batch — while applying a precomputed inverse is one MXU matmul
    (microseconds). The inverse is computed ONCE per (re)factorization
    via two n-RHS triangular solves (themselves MXU-blocked), and in f64
    the equilibrated, sigma-regularized M keeps the inverse-apply error
    far below the ADMM's own tolerance. In F32 the inverse's κ(M)·eps
    error (~1e-1 on UC-class conditioning) destabilizes the iteration —
    measured NaN blowups at S=256 — so the f32 path keeps the Cholesky
    factor and pays the triangular solves. _chol_solve dispatches on the
    stored matrix's dtype. The shared (2-D) f32 factor comes back
    PREPARED (PreparedFactor: the factor plus its inverted diagonal
    blocks, doc/kernels.md §prepared factor); batched f32 factors stay
    raw. The ill-conditioned penalty systems in the POLISH always use
    honest Cholesky solves. The per-scenario (3-D) float64 inverse is
    the library pair (``_kkt_inverse_library``) or, in the TPU
    lowering, the unrolled recurrences at n <= ``_POLISH_UNROLL_MAX_N``
    and the blocked ones above (``f64_refactor_form``). ``keep``: at a
    REbuild of such a stack, ``(moved, old)``: the rows whose rho moved
    and the inverse built before it did (``_in_row_chunks``)."""
    A_s, P_s = factors.A_s, factors.P_s
    g = factors.Eb * factors.D
    n = A_s.shape[-1]
    if isinstance(A_s, SplitMatrix):
        return _factorize_split(factors, rho_scale)
    if isinstance(A_s, PackedMatrix):
        # in-loop rho refactorization during the f32 bulk phase: the
        # packed form serves matvecs only — the (n, n) product wants
        # the one dense MXU pass
        A_s = A_s.dense
    invert = A_s.dtype == jnp.float64
    if A_s.ndim == 2:
        rA = factors.rho_A * rho_scale
        rB = factors.rho_b * rho_scale
        M = A_s.T @ (rA[:, None] * A_s)
        M = M + jnp.diag(P_s + factors.sigma + g * g * rB)
        L = jnp.linalg.cholesky(M)
        if not invert:
            return _prepare_factor(L)
        eye = jnp.eye(n, dtype=A_s.dtype)
        w = jax.lax.linalg.triangular_solve(L, eye, left_side=True,
                                            lower=True)
        return jax.lax.linalg.triangular_solve(L, w, left_side=True,
                                               lower=True, transpose_a=True)
    rA = factors.rho_A * rho_scale[:, None]
    rB = factors.rho_b * rho_scale[:, None]
    diag = P_s + g * g * rB
    if not invert:
        return _kkt_factor_library(A_s, rA, factors.sigma, diag)
    # trace-time count of the per-scenario float64 refactorizations
    # lowered each way on this backend (f64_refactor_form)
    obs.counter_add(f"kernel.f64_refactor_{f64_refactor_form(A_s)}")
    with jax.named_scope("qp.refactor"):
        return _kkt_inverse(A_s)(A_s, rA, factors.sigma, diag, keep)


def _factorize_split(factors: QPFactors, rho_scale):
    """df32 factorization: a plain f32 Cholesky factor of M, built from
    ONE f32 MXU pass — no f64 matmul (which would OOM at big-instance
    scale, see SplitMatrix), no host roundtrip, fully traceable (so
    in-jit rho refactorization stays available).

    The factor is a PRECONDITIONER-quality object, not the solver: the
    df32 x-update (see _m_solve_ir in _solve_impl) wraps each
    triangular solve in mixed-precision iterative refinement whose
    residuals come from split-f32 matvecs with f64 accumulation. The
    f32 quantization of M and the κ(M)·eps32 solve error are both
    corrected by the refinement — the classic IR contraction argument
    (error × κ·eps32 per sweep) that Newton–Schulz on an explicit
    inverse does NOT enjoy here (measured: split-product cancellation
    noise ~κ·1e-7 makes Newton DEGRADE a 2e-5 seed to 7e-3).

    Returns the factor PREPARED for the x-update's substitution
    (PreparedFactor, doc/kernels.md §prepared factor): what the solve
    needs from L alone is built here, once per (re)factorization, not
    once per ADMM iteration."""
    A_s, P_s = factors.A_s, factors.P_s
    f32 = jnp.float32
    g32 = (factors.Eb * factors.D).astype(f32)
    rA32 = (factors.rho_A * rho_scale).astype(f32)
    rB32 = (factors.rho_b * rho_scale).astype(f32)
    M32 = A_s.hi.T @ (rA32[:, None] * A_s.hi)
    M32 = M32 + jnp.diag(P_s.astype(f32) + jnp.asarray(factors.sigma, f32)
                         + g32 * g32 * rB32)
    return _prepare_factor(jnp.linalg.cholesky(M32))


def _device_f64_linalg_trusted():
    """Whether this backend's BATCHED f64 cholesky/triangular_solve can
    be trusted with the explicit KKT inverse. On the attached v5e
    (jax 0.9 / libtpu 0.0.34) it cannot: through _factorize itself, on
    real UC KKT matrices broadcast to a batch of 4 (the scenario
    hospital's spelling), |M·M⁻¹ − I|max is 4.8e-12 at n = 132,
    8.1e-12 at n = 1488 — and 0.90 at n = 768 (10 generators x 24 h,
    cond 6.6e3), where the SAME matrix comes back at 7.0e-12 through
    the unbatched shared factor and 1.3e-13 from numpy (chip probe,
    CHANGES.md PR 24; chip_smoke.py's ``f64_linalg`` lines repeat the
    n = 132 and n = 768 cases on every run). The defect is shape-
    dependent, so no width is safe. An inverse that wrong turns the
    ADMM x-update into an expanding map (iterates to 1e33 within 100
    iterations, then NaN), so on TPU — and on any backend nobody has
    measured — non-shared f64 factors take the host-exact path below,
    unless they are narrow enough for the TPU lowering not to call the
    library at all (f64_refactor_form, the one reader of this rule).
    CPU and GPU have native f64 linalg."""
    return jax.default_backend() in ("cpu", "gpu", "cuda", "rocm")


def _needs_host_factor(factors) -> bool:
    """Non-shared f64 factors on an untrusted-f64-linalg backend must be
    inverted on the HOST, and in-jit rho refactorization disabled (the
    host inverse cannot be recomputed inside the device loop). The
    SHARED f64 branch always keeps the device path: one unbatched
    factor on the hub's hot path, measured at 4.9e-12 / 7.0e-12 /
    7.9e-12 for n = 132 / 768 / 1488 on the attached v5e (ibid.).
    Neither do the small per-scenario stacks the TPU lowering inverts
    by unrolled element-wise recurrences (``f64_refactor_form``
    "unrolled": the library linalg the distrust is about never runs)."""
    return f64_refactor_form(factors.A_s) == "host"


def _kkt_host(factors: QPFactors, rho_scale, rows=None):
    """The per-scenario KKT matrices M = diag(P_s) + sigma I +
    A_sᵀ diag(ρ_A) A_s + diag(g²ρ_b) themselves, in numpy float64
    (``rows``: only those scenarios'): what _factorize_host inverts, and
    what a device inverse is held against (chip_smoke, tests)."""
    sel = (lambda a: a if rows is None else a[rows])
    A_s = sel(np.asarray(factors.A_s))
    P_s = sel(np.asarray(factors.P_s))
    g = sel(np.asarray(factors.Eb * factors.D))
    rho_scale = sel(np.asarray(rho_scale))
    rA = sel(np.asarray(factors.rho_A)) * rho_scale[:, None]
    rB = sel(np.asarray(factors.rho_b)) * rho_scale[:, None]
    M = np.einsum("smi,sm,smj->sij", A_s, rA, A_s)
    M += np.eye(A_s.shape[-1]) * float(factors.sigma)
    diag = P_s + g * g * rB
    idx = np.arange(A_s.shape[-1])
    M[:, idx, idx] += diag
    return M


def _factorize_host(factors: QPFactors, rho_scale, rows=None):
    """numpy twin of _factorize's non-shared f64 explicit-inverse branch
    (see _device_f64_linalg_trusted for why it exists). Eager-only.
    ``rows``: optional index array — invert only those scenarios' KKTs
    and return a (len(rows), n, n) block for the caller to scatter.
    Returns a HOST array; the caller ships it."""
    return np.linalg.inv(_kkt_host(factors, rho_scale, rows))


_factorize_jit = compile_serialized(jax.jit(_factorize))


def factorize_dispatch(factors: QPFactors, rho_scale):
    """The ONE eager factorization entry: host-exact inverse on
    untrusted-f64 backends, device path otherwise. Every eager
    (re)factorization site must come through here — a site calling
    _factorize directly silently reintroduces the garbage device
    inverse (see _device_f64_linalg_trusted)."""
    if _needs_host_factor(factors):
        return jnp.asarray(_factorize_host(factors, rho_scale))
    return _factorize_jit(factors, rho_scale)


def _tri_solve(L, b):
    """Solve M x = b given a true BATCHED Cholesky factor L (S, n, n);
    b (S, n): the ``lax.linalg.triangular_solve`` pair. Two users: the
    POLISH's library form (its rho_big penalty systems, cond ~
    rho_big/sigma, are too ill-conditioned for an explicit M⁻¹; on the
    TPU at small n the polish takes ``_linv_pair_solve`` instead, see
    ``f64_polish_form``) and ``_chol_solve`` on a per-scenario f32
    factor. The native-f64 main loop applies _chol_solve's inverse."""
    y = jax.lax.linalg.triangular_solve(L, b[..., None], left_side=True,
                                        lower=True, transpose_a=False)
    x = jax.lax.linalg.triangular_solve(L, y, left_side=True,
                                        lower=True, transpose_a=True)
    return x[..., 0]


# ---- the polish's small batched float64 linalg (ISSUE 40) ----
# The TPU has no float64 linalg either: its compiler expands a batched
# f64 ``cholesky`` / ``triangular_solve`` into ``while`` loops over rows
# with ``dynamic-update-slice``s, and the ``smi,sm,smj->sij`` product
# into the limb loop nest of the products above. On a served farmer
# stack ((24, 12, 12): 3 factorizations and 288 triangular solves a
# polish call) that was 68 ms a call, twelve calls a wheel (PERF.md §6,
# PR 40). The SAME arithmetic written as recurrences UNROLLED over the
# static n, on whole (S, n) / (S, n, n) slabs, is element-wise float64,
# which the compiler runs as soft-float inside ordinary fusions: no
# loop, no update-slice. The substitutions run ONCE per factor, on the
# identity (``_unrolled_linv``), and every solve of the polish's scans
# is then two reduce-form products with L⁻¹ (``_linv_pair_solve``):
# L⁻ᵀ(L⁻¹ b) carries sqrt(cond) a factor where an explicit Mp⁻¹ would
# carry cond, and al_step's refinement sweeps stay (doc/kernels.md §3e:
# the chip sweep's seconds and residuals by form, n and S).

# Largest n the polish unrolls at. The unrolled program grows with n
# (~14 fusions a Cholesky column, ~4 a row of L⁻¹, three factors a
# polish program) and the TPU compiler's seconds grow faster: a polish
# program at S = 24 compiles for a v5e in 28 s at n = 12 and at n = 16
# (the library form: 23 and 30), 73 s at n = 24 (19) and 163 s at
# n = 48 (21): COMPILE seconds turn first, between 16 and 24, long
# before device seconds could (doc/kernels.md §3e). Above it the
# library calls stay.
_POLISH_UNROLL_MAX_N = 16


def _gram_reduce(A, r):
    # Aᵀ diag(r) A as a multiply and a sum over the row axis (the form
    # of _rmatvec_reduce, one rank up)
    return jnp.sum(A[:, :, :, None] * (r[:, :, None] * A)[:, :, None, :],
                   axis=1)


def _unrolled_cholesky(M):
    """Lower Cholesky factor of M (S, n, n), n static: the left-looking
    column recurrence, one reduce, one sqrt and one divide a column on
    (S, n) slabs, the factor filled in by a select on the column index
    (no stack, so nothing the compiler turns into an update-slice).
    Reads M's lower triangle. A non-positive-definite matrix gives NaN
    from its first bad pivot's column on (sqrt of a negative), as the
    library does: the polish's NaN candidates must lose."""
    n = M.shape[-1]
    idx = jnp.arange(n)
    L = jnp.zeros_like(M)
    for j in range(n):
        c = M[:, :, j]
        if j:
            c = c - jnp.sum(L * L[:, j, None, :], axis=-1)
        col = jnp.where(idx >= j, c / jnp.sqrt(c[:, j])[:, None], 0.0)
        L = jnp.where(idx == j, col[:, :, None], L)
    return L


def _unrolled_linv(L):
    """L⁻¹ of a batched lower factor (S, n, n) by the forward
    substitution on the identity, unrolled over the static n: row j is
    (e_j − Σ_{k<j} L[j, k] X[k, :]) / L[j, j], one reduce and one divide
    a row on (S, n) slabs."""
    n = L.shape[-1]
    idx = jnp.arange(n)
    eye = jnp.eye(n, dtype=L.dtype)
    X = jnp.zeros_like(L)
    for j in range(n):
        s = eye[j][None, :]
        if j:
            s = s - jnp.sum(L[:, j, :, None] * X, axis=1)
        X = jnp.where(idx[:, None] == j,
                      (s / L[:, j, j, None])[:, None, :], X)
    return X


def _linv_pair_solve(Linv, b):
    """x = L⁻ᵀ (L⁻¹ b) from the explicit L⁻¹ (S, n, n): two reduce-form
    products (the second over L⁻¹'s own row axis, no transposed copy)."""
    return _rmatvec_reduce(Linv, _matvec_reduce(Linv, b))


def _penalty_factor_library(A_b, rpA, diag):
    Mp = jnp.einsum("smi,sm,smj->sij", A_b, rpA, A_b)
    Mp = Mp + jax.vmap(jnp.diag)(diag)
    return jnp.linalg.cholesky(Mp)


def _penalty_matrix(A_b, rpA, diag):
    eye = jnp.eye(A_b.shape[-1], dtype=diag.dtype)
    return _gram_reduce(A_b, rpA) + diag[:, :, None] * eye


def _penalty_factor_unrolled(A_b, rpA, diag):
    return _unrolled_linv(_unrolled_cholesky(_penalty_matrix(A_b, rpA, diag)))


def _tpu_stack_form(A_s) -> str | None:
    """Static, the ONE shape test of the TPU's own per-scenario float64
    linalg (the polish's factor-and-substitute side and the explicit KKT
    inverse alike): ``"unrolled"`` for a 3-D float64 stack no wider than
    ``_POLISH_UNROLL_MAX_N``, ``"blocked"`` for a wider one whose (S, n,
    n) float64 array stays under ``_F64_BLOCKED_MAX_BYTES``, None for
    everything else (a shared 2-D matrix, f32, a split matrix, a stack
    too large to rebuild on the device)."""
    if isinstance(A_s, SplitMatrix) or A_s.ndim != 3 \
            or A_s.dtype != jnp.float64:
        return None
    S, _, n = A_s.shape
    if n <= _POLISH_UNROLL_MAX_N:
        return "unrolled"
    return "blocked" if 8 * S * n * n <= _F64_BLOCKED_MAX_BYTES else None


def _polish_unrollable(A_s) -> bool:
    """Static: whether the TPU lowering of the linalg over these factors
    takes the fully unrolled forms (and the rho loop its "resident"
    shape, ``f64_loop_form``)."""
    return _tpu_stack_form(A_s) == "unrolled"


# ---- the same linalg, BLOCKED, for stacks wider than that (ISSUE 45) ----
# Above ``_POLISH_UNROLL_MAX_N`` the unrolled program no longer compiles
# in any useful time, the library's batched float64 calls are the v5e's
# row loops (and wrong at some shapes, _device_f64_linalg_trusted), and
# numpy between device calls costs a round trip a rho move. The blocked
# forms run the SAME recurrences on the diagonal blocks only (one
# ``_unrolled_cholesky`` and one ``_unrolled_linv`` of a (S, b, b) block,
# b = ``_F64_BLOCK``, in the body of a ``fori_loop`` over block rows, so
# the program's size does not grow with n), and everything off the
# diagonal as reduce-form products of (S, b, n) row panels with what
# is already built: element-wise float64 throughout, no ``cholesky`` /
# ``triangular_solve`` / ``dot`` for the compiler to expand. Every n^3
# product is spelled as a multiply and a sum over the operands' common
# ROW axis (the fastest spelling the chip's sweeps saw), and runs over
# the part of its operands that is not stored zeros: the block rows are
# walked in ``_F64_GROUPS`` static groups whose slices are static (a
# mask saves nothing on a SIMD unit; a static extent does), and the
# explicit inverse is computed for one triangle of blocks and mirrored.
# doc/kernels.md §3h: the chip's seconds and residuals by spelling,
# group count, n and S.

# Width of a diagonal block: the widest stack the unrolled recurrences
# compile at (the rule that stops them is the rule that sizes them)
_F64_BLOCK = _POLISH_UNROLL_MAX_N

# Static groups the block rows of a blocked build are walked in. A
# group's products work on the extent that is nonzero for ITS block
# rows, so the n^3 stages do (G + 1)(G + 2) / 6G^2 (the Cholesky, the
# explicit inverse) and (G + 1)(2G + 1) / 6G^2 (the substitution) of the
# full square's work, against 1/6 and 1/3 at a group a block row. A
# group is one more branch of the Cholesky's ``lax.switch``, one more
# substitution loop and one more strip of the product: small bodies (the
# unrolled diagonal factor stays in the program once). 3: the count the
# cell's runs were held to ``correct`` at; iter-0 stops at its cap, so
# another count is another summation order and another end state
# (doc/kernels.md §3h: device seconds, compile seconds and iter-0's
# compared numbers by G).
_F64_GROUPS = 3

# Largest (S, n, n) float64 array the blocked forms take. A solve keeps
# the inverse it came with and the one it hands back, the other mode's
# state its own, and a polish one U⁻¹ a candidate: five or six such
# arrays on a chip of 16 GB (the builds' own temporaries are row chunks,
# ``_F64_BUILD_BYTES``). Above it (the scenario hospital's UC-width
# batches: (4, 13056, 13056) is 5.5 GB an array) the inverse stays
# numpy's, between device calls (``f64_refactor_form`` "host").
_F64_BLOCKED_MAX_BYTES = 2 << 30


def _pad_spd(M, b):
    """M (S, n, n) padded by an identity block to the next multiple of
    ``b`` (its factor and inverse are then the originals padded the
    same way)."""
    n = M.shape[-1]
    pad = (-n) % b
    if not pad:
        return M
    M = jnp.pad(M, ((0, 0), (0, pad), (0, pad)))
    tail = (jnp.arange(n + pad) >= n).astype(M.dtype)
    return M + tail[None, :, None] * jnp.eye(n + pad, dtype=M.dtype)


def _block_row_groups(n):
    """Static ``(s, e)`` element extents of the groups the n / b block
    rows are walked in (``_F64_GROUPS`` of them, fewer where there are
    fewer block rows; none empty)."""
    nb = -(-n // _F64_BLOCK)
    G = min(_F64_GROUPS, nb)
    cuts = [min(nb * g // G * _F64_BLOCK, n) for g in range(G + 1)]
    return list(zip(cuts, cuts[1:]))


def _blocked_cholesky(M):
    """``(U, Dinv)`` of M (S, n, n), n a multiple of b = ``_F64_BLOCK``:
    the upper Cholesky factor U = Lᵀ (M = UᵀU), left-looking, one block
    row a trip, and the inverses of L's diagonal blocks stacked by rows
    ((S, n, b)). A trip takes block row k of M less the product of U's
    block column k with the rows of U above it, factors the (b, b)
    diagonal block by the unrolled recurrences and turns the row panel
    into U's by one product with the block's L⁻¹. The product runs on
    rows [0, e) and columns [s, n) of the trip's group (U's rows from k
    on are still zero, and so are its columns left of the diagonal): a
    ``lax.switch`` on the block row's group around the product ALONE,
    so that the loop, and the unrolled factor in its body, is in the
    program once. Reads M's upper triangle of blocks. Like the library,
    a non-positive-definite matrix gives NaN from its first bad pivot
    on."""
    S, n, _ = M.shape
    b = _F64_BLOCK
    within = jnp.arange(b)
    col = jnp.arange(n)
    groups = _block_row_groups(n)
    ends = jnp.asarray([e for _s, e in groups])

    def above(s, e):
        def product(U, kb):
            Ucol = jax.lax.dynamic_slice_in_dim(U[:, :e], kb, b, axis=2)
            return jnp.pad(
                jnp.sum(Ucol[:, :, :, None] * U[:, :e, None, s:], axis=1),
                ((0, 0), (0, 0), (s, 0)))
        return product

    branches = [above(s, e) for s, e in groups]

    def trip(k, carry):
        U, Dinv = carry
        kb = k * b
        R = jax.lax.dynamic_slice_in_dim(M, kb, b, axis=1) \
            - jax.lax.switch(jnp.sum(kb >= ends), branches, U, kb)
        Linv = _unrolled_linv(_unrolled_cholesky(
            jax.lax.dynamic_slice_in_dim(R, kb, b, axis=2)))
        # U's rows kb .. kb+b: L_kk⁻¹ R, zero left of the diagonal
        P = jnp.sum(Linv[:, :, :, None] * R[:, None, :, :], axis=2)
        P = jnp.where(col[None, None, :] >= kb + within[None, :, None],
                      P, 0.0)
        return (jax.lax.dynamic_update_slice_in_dim(U, P, kb, axis=1),
                jax.lax.dynamic_update_slice_in_dim(Dinv, Linv, kb, axis=1))

    return jax.lax.fori_loop(
        0, n // b, trip,
        (jnp.zeros_like(M), jnp.zeros((S, n, b), M.dtype)))


def _blocked_uinv(U, Dinv):
    """W = U⁻¹ (upper) of ``_blocked_cholesky``'s pair, by the backward
    substitution on the identity, one block row a trip from the last:
    row block k is L_kk⁻ᵀ (E_k − U[k, :] W), the product summed over
    the rows of W and of L = Uᵀ (one transposed copy a build: block row
    k of U is then a block column, as in the factorization), on rows
    and columns [s, n) of the trip's group (W is upper, and its rows up
    to k are still zero)."""
    S, n, _ = U.shape
    b = _F64_BLOCK
    within = jnp.arange(b)
    L = jnp.swapaxes(U, 1, 2)
    W = jnp.zeros_like(U)
    for s, e in reversed(_block_row_groups(n)):
        col = jnp.arange(s, n)
        Ls = L[:, s:]

        def trip(t, W, s=s, e=e, col=col, Ls=Ls):
            kb = e - (t + 1) * b
            Lcol = jax.lax.dynamic_slice_in_dim(Ls, kb, b, axis=2)
            Linv = jax.lax.dynamic_slice_in_dim(Dinv, kb, b, axis=1)
            E = (col[None, :] == kb + within[:, None]).astype(U.dtype)
            T = E[None] - jnp.sum(
                Lcol[:, :, :, None] * W[:, s:, None, s:], axis=1)
            Wrow = jnp.sum(Linv[:, :, :, None] * T[:, :, None, :], axis=1)
            return jax.lax.dynamic_update_slice(W, Wrow, (0, kb, s))

        W = jax.lax.fori_loop(0, (e - s) // b, trip, W)
    return W


def _blocked_factor_inverse(M):
    """W = U⁻¹ of an SPD stack M (S, n, n) of any n (its upper triangle
    of blocks is read): M⁻¹ = W Wᵀ."""
    n = M.shape[-1]
    W = _blocked_uinv(*_blocked_cholesky(_pad_spd(M, _F64_BLOCK)))
    return W[:, :n, :n]


def _uinv_pair_solve(W, b):
    """x = W (Wᵀ b) = M⁻¹ b from W = U⁻¹ (S, n, n): ``_linv_pair_solve``
    with the factor transposed (the first product over W's own row
    axis, no transposed copy)."""
    return _matvec_reduce(W, _rmatvec_reduce(W, b))


def _spd_inverse_from_uinv(W):
    """M⁻¹ = W Wᵀ = VᵀV, summed over the rows of V = Wᵀ (one transposed
    copy; V is lower): the UPPER triangle of blocks only, a column
    strip a group of block rows, each the rows down to its own end and
    summed from the strip's start on (V's rows above hold zeros
    there); the blocks below are the mirror image, bit for bit."""
    n = W.shape[-1]
    V = jnp.swapaxes(W, 1, 2)
    groups = _block_row_groups(n)
    X = jnp.concatenate([
        jnp.pad(jnp.sum(V[:, s:, :e, None] * V[:, s:, None, s:e], axis=1),
                ((0, 0), (0, n - e), (0, 0)))
        for s, e in groups], axis=2)
    group = sum((jnp.arange(n) >= e).astype(jnp.int32) for _s, e in groups)
    return jnp.where((group[:, None] <= group[None, :])[None],
                     X, jnp.swapaxes(X, 1, 2))


# Bytes of ONE (rows, n, n) float64 array a build works on at a time.
# A build holds about six such arrays (the matrix, U and its transposed
# copy, U⁻¹ and its transposed copy, the product's strips): for the
# whole stack at once over 7 GB of temporaries at (1024, 384, 384); in
# chunks of 64 rows the hot program's temporaries are 3.3 GB and the
# iter-0 program's 8.5 GB (compiled for a v5e; 2.6 and 8.4 GB with
# PR 45's four arrays a build). The trips cost nothing that shows
# beside a chunk's ~0.1–0.2 s of soft-float.
_F64_BUILD_BYTES = 128 << 20


def _in_row_chunks(build, *ops, keep=None):
    """``build(*ops)`` over the scenario axis, a chunk of rows at a
    time, the chunk sized by ``_F64_BUILD_BYTES``: per-scenario
    arithmetic, so the result is the whole stack's. A first build
    (``keep`` None) ``lax.map``s over the chunks in order (the last one
    padded with copies of row 0, trimmed after). A REbuild (``keep =
    (moved, old)``: the (S,) rows whose operands changed since ``old``
    (S, n, n) was built from them) gathers the moved rows, a chunk a
    trip of a loop whose count is the number of chunks they fill,
    builds those and writes them into ``old``: it costs the rows that
    moved and not the stack (the last chunk is filled up with rows that
    did not move, which rebuild to what they were)."""
    S, n = ops[0].shape[0], ops[0].shape[-1]
    trips = -(-S // max(1, _F64_BUILD_BYTES // (8 * n * n)))
    if trips <= 1:
        return build(*ops)
    # a count that divides S, if one is near: no padded copy of a stack
    trips = next((t for t in range(trips, 2 * trips) if S % t == 0), trips)
    chunk = -(-S // trips)
    if keep is not None:
        moved, old = keep
        order = jnp.argsort(jnp.logical_not(moved), stable=True)

        def rebuild(t, out):
            idx = jax.lax.dynamic_slice_in_dim(order, t * chunk, chunk)
            return out.at[idx].set(build(*(a[idx] for a in ops)))

        return jax.lax.fori_loop(
            0, (jnp.sum(moved) + chunk - 1) // chunk, rebuild, old)
    pad = trips * chunk - S
    if pad:
        ops = tuple(jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])]) for a in ops)
    out = jax.lax.map(lambda t: build(*t), tuple(
        a.reshape((trips, chunk) + a.shape[1:]) for a in ops))
    return out.reshape((trips * chunk,) + out.shape[2:])[:S]


def _penalty_uinv(A_b, rpA, diag):
    return _blocked_factor_inverse(_penalty_matrix(A_b, rpA, diag))


def _penalty_factor_blocked(A_b, rpA, diag):
    return _in_row_chunks(_penalty_uinv, A_b, rpA, diag)


def _kkt_inverse_blocked(A_s, rA, sigma, diag, keep=None):
    return _in_row_chunks(
        lambda A, r, d: _spd_inverse_from_uinv(_penalty_uinv(A, r, d)),
        A_s, rA, diag + sigma, keep=keep)


def f64_polish_form(A_s) -> str | None:
    """``"unrolled"`` / ``"blocked"`` / ``"library"``: how THIS
    process's backend runs the factor-and-substitute side of a float64
    polish over the scaled matrix ``A_s``; None where there is none (a
    SplitMatrix never polishes, an f32 polish is not float64 linalg).
    On the TPU a per-scenario (3-D) stack takes its own spelling
    (``_tpu_stack_form``: unrolled to n = ``_POLISH_UNROLL_MAX_N``,
    blocked above); a shared 2-D matrix, a stack too large for the
    blocked build, and every other backend keep
    ``jnp.linalg.cholesky`` and the ``triangular_solve`` pair."""
    if isinstance(A_s, SplitMatrix) or A_s.dtype != jnp.float64:
        return None
    if jax.default_backend() == "tpu":
        return _tpu_stack_form(A_s) or "library"
    return "library"


# the TPU's (factorize, solve) pair of each stack form: F is L⁻¹ under
# the unrolled pair and U⁻¹ under the blocked one
_TPU_PENALTY_PAIR = {
    "unrolled": (_penalty_factor_unrolled, _linv_pair_solve),
    "blocked": (_penalty_factor_blocked, _uinv_pair_solve)}


def _polish_linalg(A_s):
    """``(factorize, solve)`` of the polish's penalty systems over
    ``A_s``: ``F = factorize(A_b, rpA, diag)`` and ``x = solve(F, b)``.
    Where the TPU has a spelling of its own, the pair is chosen at
    lowering time per platform, as the batched products are (CPU and
    GPU keep the library calls, and a program compiled HERE for a
    described TPU takes the TPU form); F is then L on one platform and
    an explicit triangular inverse on the other, which only the pair's
    own solve ever reads."""
    form = _tpu_stack_form(A_s)
    if form is None:
        return _penalty_factor_library, _tri_solve
    factorize, solve = _TPU_PENALTY_PAIR[form]
    return (lambda A_b, rpA, diag: jax.lax.platform_dependent(
                A_b, rpA, diag, tpu=factorize,
                default=_penalty_factor_library),
            lambda F, b: jax.lax.platform_dependent(
                F, b, tpu=solve, default=_tri_solve))


# ---- the per-scenario float64 KKT inverse (ISSUE 42) ----
# _factorize's non-shared f64 branch. The library pair below is what
# every backend with native f64 linalg runs. The TPU's expansion of it
# is not trusted (_device_f64_linalg_trusted), so there the inverse was
# numpy's (_factorize_host) and rho could move only between device
# calls: a served farmer wheel of 8 spent 0.68 of its 0.71 s in ~57
# host round trips around 0.08 s of device work (PERF.md §6, PR 42).
# At the widths the polish unrolls, the SAME recurrences give the
# explicit inverse from element-wise float64 alone: M by _gram_reduce,
# L by _unrolled_cholesky, L⁻¹ by _unrolled_linv, M⁻¹ = L⁻ᵀ L⁻¹ by one
# more reduce-form product. On the served stack's own KKTs ((24, 12,
# 12), cond <= 6.3 after equilibration at every rho the clip allows)
# the chip reads |M·M⁻¹ − I|max <= 6e-14 (doc/kernels.md §3f), so the
# refactorization stays inside the solve program and a solve is one
# launch (kernels.resolve_mode).

def _kkt_factor_library(A_s, rA, sigma, diag):
    n = A_s.shape[-1]
    M = (A_s * rA[:, :, None]).swapaxes(1, 2) @ A_s
    M = M + jnp.eye(n, dtype=A_s.dtype) * sigma
    M = M + jax.vmap(jnp.diag)(diag)
    return jnp.linalg.cholesky(M)


def _kkt_inverse_library(A_s, rA, sigma, diag, keep=None):
    L = _kkt_factor_library(A_s, rA, sigma, diag)
    eye = jnp.broadcast_to(jnp.eye(L.shape[-1], dtype=L.dtype), L.shape)
    w = jax.lax.linalg.triangular_solve(L, eye, left_side=True, lower=True)
    return jax.lax.linalg.triangular_solve(L, w, left_side=True,
                                           lower=True, transpose_a=True)


def _kkt_inverse_unrolled(A_s, rA, sigma, diag, keep=None):
    Linv = _penalty_factor_unrolled(A_s, rA, diag + sigma)
    # L⁻ᵀ L⁻¹ as a multiply and a sum over L⁻¹'s own row axis
    return jnp.sum(Linv[:, :, :, None] * Linv[:, :, None, :], axis=1)


def f64_refactor_form(A_s) -> str | None:
    """``"unrolled"`` / ``"blocked"`` / ``"host"`` / ``"library"``:
    where and how THIS process's backend builds the explicit KKT
    inverse of float64 factors over the scaled matrix ``A_s``; None
    where the factor is no float64 inverse (a SplitMatrix, an f32
    matrix). A shared 2-D matrix always takes the device library (one
    unbatched factor, trusted on every backend measured). A
    per-scenario (3-D) one takes it where the batched f64 linalg is
    trusted (_device_f64_linalg_trusted); elsewhere the TPU's own
    spelling of its shape (``_tpu_stack_form``, the polish's test:
    "unrolled" at n <= ``_POLISH_UNROLL_MAX_N``, "blocked" above while
    the stack fits a rebuild) and "host" (numpy between device calls,
    _factorize_host) for a stack too large for that or on a backend
    nobody has measured."""
    if isinstance(A_s, SplitMatrix) or A_s.dtype != jnp.float64:
        return None
    if A_s.ndim == 2 or _device_f64_linalg_trusted():
        return "library"
    if jax.default_backend() == "tpu":
        return _tpu_stack_form(A_s) or "host"
    return "host"


def f64_loop_form(A_s) -> str | None:
    """``"resident"`` / ``"conditional"``: the shape of the ADMM loop
    that adapts rho INSIDE the solve program over float64 factors of
    the scaled matrix ``A_s`` (_solve_impl; doc/kernels.md §3g); None
    where no program does (the factor is no float64 inverse, or its
    rebuild is the host's: ``f64_refactor_form`` None / "host").
    "resident": a per-scenario stack at n <= ``_POLISH_UNROLL_MAX_N``,
    whose rebuild is cheap enough to run unconditionally once a
    four-check period, BETWEEN two inner loops that hold the inverse as
    a loop-invariant operand, so that no loop carrying it holds a
    ``conditional``. "conditional": the rebuild under a ``lax.cond`` in
    the loop's one body (a shared 2-D inverse; a wider stack, whose
    blocked rebuild costs a thousand ADMM iterations and must not run
    where rho did not move). Read from the shape alone: every backend
    traces the same structure, and the platform only chooses HOW the
    inverse is built (``f64_refactor_form``)."""
    if f64_refactor_form(A_s) in (None, "host"):
        return None
    return "resident" if _polish_unrollable(A_s) else "conditional"


# Bytes of the two float64 operands ((B, m, n) matrix, (B, n, n)
# inverse) that ONE block of scenarios may hold where the ADMM scan of
# a wide stack runs block by block (``f64_stack_block_rows``): half of
# the v5e's 128 MiB of VMEM. Step 0 of ISSUE 46, on the chip at (1024,
# 193, 384) (doc/kernels.md §3i): up to it (B <= 32, 57 MB) the
# compiler keeps every operand of a block on chip for the block's
# ``check_every`` iterations and a 1024-row ADMM iteration costs
# 4.28-4.44 ms where the whole-stack scan costs 6.24; over it part of a
# block stays in HBM (B = 64: 4.57) and by B = 128 most of the gain is
# gone (5.38, B = 256: 5.65).
_F64_LOOP_BLOCK_BYTES = 64 << 20


def f64_stack_block_rows(A_s) -> int | None:
    """Rows B of a block where ``_solve_impl``'s ADMM scan walks a
    per-scenario float64 stack in blocks of scenarios (doc/kernels.md
    §3i): the largest divisor of S whose rows' two operands fit
    ``_F64_LOOP_BLOCK_BYTES``. None where the scan stays ONE over all
    rows: everything that is not a wide stack (``_tpu_stack_form``
    "blocked": 3-D float64, n > ``_POLISH_UNROLL_MAX_N``, rebuilt on
    the device), a stack that fits the budget whole, a row that alone
    is over it, and an S whose largest divisor under the budget fills
    less than half of it (a prime S: blocks of one row, whose launches
    cost what the shape buys). Read from the shape alone, like
    ``f64_loop_form``: every backend traces the same structure."""
    if _tpu_stack_form(A_s) != "blocked":
        return None
    S, m, n = A_s.shape
    fit = _F64_LOOP_BLOCK_BYTES // (8 * (m * n + n * n))
    if not 0 < fit < S:
        return None
    B = next(B for B in range(fit, 0, -1) if S % B == 0)
    return B if 2 * B >= fit else None


_TPU_KKT_INVERSE = {"unrolled": _kkt_inverse_unrolled,
                    "blocked": _kkt_inverse_blocked}


def _kkt_inverse(A_s):
    """``inverse(A_s, rA, sigma, diag, keep)`` of _factorize's
    per-scenario float64 branch; like _polish_linalg, the TPU form is
    chosen at lowering time per platform (CPU and GPU keep the library
    calls bit for bit, and a program compiled HERE for a described TPU
    takes the TPU form). ``keep`` (None, or ``(moved, old)`` at a
    REbuild: ``_in_row_chunks``) is the blocked form's to use; the
    others build every row, which gives the rows that did not move what
    they were."""
    form = _tpu_stack_form(A_s)
    if form is None:
        return _kkt_inverse_library
    return lambda *ops: jax.lax.platform_dependent(
        *ops, tpu=_TPU_KKT_INVERSE[form], default=_kkt_inverse_library)


# Block size of the prepared substitution: the one XLA's triangular-
# solve expander uses on the TPU, so the prepared solve runs the SAME
# blocked algorithm the expander ran (doc/kernels.md §prepared factor).
_TRI_BLOCK = 128


class PreparedFactor(NamedTuple):
    """A shared (2-D) f32 Cholesky factor together with everything the
    x-update's two triangular solves need from L ALONE, built once per
    (re)factorization and carried in QPState.L the way LInv is.

    Why: ``lax.linalg.triangular_solve`` re-derives two things from L
    on EVERY call — a masked (n, n) copy of its triangle and the
    inverses of its 128 x 128 diagonal blocks — and a while_loop carry
    that a ``lax.cond`` may refactorize hides their loop-invariance
    from the compiler. At UC width (n = 13,056) those were 47% of the
    device's busy time in the f32 bulk (ledger, PR 26; PERF.md §6).
    ``_prepared_solve`` runs the same blocked substitution from the
    stored blocks and reads only the computed half of L: no mask, no
    per-call inversion, no transpose (the Lᵀ solve walks L's column
    panels). It is NOT the LInv trade: the off-diagonal part is still
    substituted block row by block row, so un-refined solves (the f32
    bulk) keep the accuracy they had (residuals equal to the pair's on
    the chip, on the UC factor). Off the TPU triangular_solve is a
    library call with nothing to hoist, and _chol_solve keeps the pair
    on ``tri`` there. doc/kernels.md §prepared factor."""
    tri: jax.Array          # (n, n) = L, lower Cholesky factor
    dinv: jax.Array         # (ceil(n/bs), bs, bs), bs = min(128, n):
    #                         inverses of L's diagonal blocks (a short
    #                         last block is padded with an identity)


def _tri_cuts(n, bs):
    return [(j, min(j + bs, n)) for j in range(0, n, bs)]


def _prepare_factor(L) -> PreparedFactor:
    """Traceable L -> PreparedFactor: slice the diagonal blocks and
    invert them with ONE batched triangular solve (on the TPU the same
    ``InvertDiagBlocksLowerTriangular`` the expander called per
    solve)."""
    n = L.shape[-1]
    bs = min(_TRI_BLOCK, n)
    blocks = []
    for j0, j1 in _tri_cuts(n, bs):
        d = L[j0:j1, j0:j1]
        if j1 - j0 < bs:
            d = jnp.eye(bs, dtype=L.dtype).at[:j1 - j0, :j1 - j0].set(d)
        blocks.append(d)
    diag = jnp.stack(blocks)
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=L.dtype), diag.shape)
    return PreparedFactor(L, jax.lax.linalg.triangular_solve(
        diag, eye, left_side=True, lower=True))


def _raw_factor(L):
    """The bare (n, n) Cholesky factor of a PreparedFactor (identity on
    anything else)."""
    return L.tri if isinstance(L, PreparedFactor) else L


# The substitution's matmuls state their precision: the triangular-
# solve pair they replace was never governed by the process's
# jax_default_matmul_precision (the TPU expander multiplies at
# HIGHEST), so the factor solve's accuracy must not hang on that flag
# either (bf16-pass substitution stalls near 1e-1, utils/runtime).
_TRI_PRECISION = jax.lax.Precision.HIGHEST


def _dot_nt(a, b):
    """a @ b.T without a transpose op (contract both minor axes)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_TRI_PRECISION)


def _prepared_solve(F: PreparedFactor, b):
    """Rows of x solve L Lᵀ x_i = b_i; b (S, n) in the factor's dtype.
    Blocked substitution over static slices, matmuls at HIGHEST:
    forward y_j = (b_j − y[:j] L[j, :j]ᵀ) D_j⁻ᵀ down L's block rows,
    backward x_j = (y_j − x[j+1:] L[j+1:, j]) D_j⁻¹ up its block
    columns, each block written in place."""
    L, dinv = F.tri, F.dinv
    n = L.shape[-1]
    cuts = _tri_cuts(n, dinv.shape[-1])
    y = b
    for k, (j0, j1) in enumerate(cuts):
        r = y[:, j0:j1]
        if j0:
            r = r - _dot_nt(y[:, :j0], L[j0:j1, :j0])
        y = y.at[:, j0:j1].set(_dot_nt(r, dinv[k, :j1 - j0, :j1 - j0]))
    x = y
    for k, (j0, j1) in reversed(list(enumerate(cuts))):
        r = x[:, j0:j1]
        if j1 < n:
            r = r - jnp.matmul(x[:, j1:], L[j1:, j0:j1],
                               precision=_TRI_PRECISION)
        x = x.at[:, j0:j1].set(jnp.matmul(r, dinv[k, :j1 - j0, :j1 - j0],
                                          precision=_TRI_PRECISION))
    return x


def _pair_solve(L, b):
    """Rows of x solve L Lᵀ x_i = b_i with the
    ``lax.linalg.triangular_solve`` pair on the bare (n, n) factor."""
    y = jax.lax.linalg.triangular_solve(L, b.T, left_side=True,
                                        lower=True, transpose_a=False)
    x = jax.lax.linalg.triangular_solve(L, y, left_side=True,
                                        lower=True, transpose_a=True)
    return x.T


class LInv(NamedTuple):
    """EXPLICIT inverse of a (shared, 2-D) Cholesky factor, carried in
    QPState.L alongside the factor itself: the x-update's M⁻¹ apply
    becomes TWO MXU MATMULS (x = L⁻ᵀ(L⁻¹b) — roofline headroom item 1,
    doc/roofline.md §5) instead of two sequential back-substitutions.
    What that buys is the substitution's 2·⌈n/128⌉ sequential block
    steps (~6 µs each on a v5e since PreparedFactor took the per-call
    preparations away); what it costs is twice the substitution's
    flops and bytes (full squares for triangles). So it pays on narrow
    factors (sslp's n = 520: ten steps against two thin products) and
    LOSES at UC width: n = 13,056 on the chip, one apply 1.92 ms
    against the prepared solve's 1.73 at 64 rows and 2.85 against 2.21
    at 128 (PERF.md §6, PR 41); ops/kernels' ``l_inv_profitable`` holds
    that comparison.

    Distinct from _factorize's f64 explicit M⁻¹: inverting M composes
    κ(M)·eps error (measured NaN blowups in f32 — see _factorize), but
    each triangular factor only carries κ(L)=sqrt(κ(M)) — and the df32
    x-update wraps every solve in iterative refinement whose residuals
    come from split matvecs, so the remaining ~sqrt(κ)·eps32 forward
    error is contracted exactly like the triangular solve's own (see
    _m_solve_ir). That contraction argument is the trade's WHOLE
    license, which is why ``tri`` (the raw factor) rides along: solves
    with NO refinement around them — the fused driver's f32 bulk phase
    — keep the componentwise-stable back-substitution (measured: an
    un-refined L⁻¹ bulk shifts the degenerate-UC plateau objective by
    ~0.5%, outside the packed path's calibrated band). Residency is
    two f32 (n, n) buffers — the same bytes as the one f64 factor the
    non-split path carries. Built by the ops/kernels layer behind a
    profitability check (the build must amortize over the iteration
    budget and the apply must beat the prepared substitution's), in
    column panels beyond ``_LINV_PANEL`` columns; every _chol_solve
    consumer dispatches on the container, so a state carrying L or
    L⁻¹ flows through the same solver code."""
    inv: jax.Array          # (n, n) = L⁻¹ (NOT M⁻¹), factor dtype
    tri: jax.Array          # (n, n) = L itself (non-IR consumers)

    @property
    def dtype(self):
        return self.inv.dtype

    @property
    def ndim(self):
        return self.inv.ndim

    @property
    def shape(self):
        return self.inv.shape


# Column-panel width of the explicit inverse's build: a whole number of
# _TRI_BLOCK blocks (17; UC's n = 13,056 is six panels exactly). Up to
# this width the inverse is ONE n-RHS triangular solve; beyond it that
# solve's expansion asks the v5e compiler for 32.65 GB at n = 13,056
# (chip run, PR 25), so the inverse is built panel by panel.
_LINV_PANEL = 17 * _TRI_BLOCK


def l_inv_panels(n) -> int:
    """Column panels ``_make_l_inv`` builds an (n, n) inverse in."""
    return -(-int(n) // _LINV_PANEL)


def _l_inv_by_panels(L, dinv):
    """L⁻¹ of the lower-triangular (n, n) ``L`` by forward substitution
    on the identity, one column panel of ``_LINV_PANEL`` at a time: in
    the panel that starts at column c0 the rows above c0 are zero, and
    below them block row i is X_i = D_i⁻¹ (I_i − L[i, c0:] X) with X the
    panel's rows so far (those not yet reached are still zero, so the
    product needs no mask). ``dinv``: the inverted diagonal blocks
    (PreparedFactor.dinv). A ``fori_loop`` over the block rows of each
    panel, a static loop over the panels (each has its own height): the
    working set is one (n − c0, panel) slab beside the output, and only
    blocks of the lower triangle are ever written. Products at
    _TRI_PRECISION, like the substitution the inverse stands in for."""
    n = L.shape[-1]
    bs = dinv.shape[-1]
    nb = dinv.shape[0]
    npad = nb * bs
    if npad != n:
        # a short last block: dinv carries an identity there, so the
        # padded inverse is [[L⁻¹, 0], [0, I]]
        L = jnp.pad(L, ((0, npad - n), (0, npad - n)))
    X = jnp.zeros((npad, npad), L.dtype)
    for c0 in range(0, npad, _LINV_PANEL):
        w = min(_LINV_PANEL, npad - c0)
        h = npad - c0
        cols = c0 + jnp.arange(w)[None, :]

        def block_row(i, Xp):       # traced at once, in this pass
            r0 = i * bs
            Li = jax.lax.dynamic_slice(L, (r0, c0), (bs, h))
            eye_i = (r0 + jnp.arange(bs)[:, None] == cols).astype(L.dtype)
            R = eye_i - jnp.matmul(Li, Xp, precision=_TRI_PRECISION)
            Xi = jnp.matmul(dinv[i], R, precision=_TRI_PRECISION)
            return jax.lax.dynamic_update_slice(Xp, Xi, (r0 - c0, 0))

        Xp = jax.lax.fori_loop(c0 // bs, nb, block_row,
                               jnp.zeros((h, w), L.dtype))
        X = jax.lax.dynamic_update_slice(X, Xp, (c0, c0))
    return X[:n, :n] if npad != n else X


def _make_l_inv(L) -> LInv:
    """Traceable L -> (L⁻¹, L). Up to ``_LINV_PANEL`` columns ONE n-RHS
    triangular solve against the identity (MXU-blocked); wider, the
    same substitution in column panels (``_l_inv_by_panels``), which
    the compiler can hold at UC width. A PreparedFactor contributes its
    raw factor and, to the panel build, its inverted diagonal blocks (a
    bare factor's are inverted here)."""
    tri = _raw_factor(L)
    if tri.shape[-1] <= _LINV_PANEL:
        eye = jnp.eye(tri.shape[-1], dtype=tri.dtype)
        return LInv(jax.lax.linalg.triangular_solve(
            tri, eye, left_side=True, lower=True), tri)
    dinv = L.dinv if isinstance(L, PreparedFactor) \
        else _prepare_factor(tri).dinv
    return LInv(_l_inv_by_panels(tri, dinv), tri)


_l_inv_jit = compile_serialized(jax.jit(lambda L: _make_l_inv(L).inv))


def make_l_inv(L) -> LInv:
    """Eager L -> LInv. Only the inverse comes out of the jit: a jit
    that passes a matrix through to its output makes XLA COPY it (see
    _setup_vectors), 0.68 GB at UC width; ``tri`` is the caller's own
    buffer."""
    return LInv(_l_inv_jit(L), _raw_factor(L))


make_l_inv.lower = _l_inv_jit.lower


def _refactor_like(factors, rho_scale, like, moved=None):
    """In-loop refactorization that preserves the CONTAINER of the
    carried factor: a state running the L⁻¹-matmul x-update must get a
    fresh L⁻¹ when rho adaptation refactorizes mid-solve, or the
    while_loop carry would change pytree structure. A PreparedFactor
    carry needs no help: _factorize returns a freshly prepared one
    (that IS the hoist — the preparation runs here, once per
    refactorization). The isinstance test is trace-time (pytree
    structure is static). ``moved`` (per-scenario rho only): the rows
    whose ``rho_scale`` differs from the one ``like`` was built at."""
    keep = None if moved is None or isinstance(like, (LInv, PreparedFactor)) \
        else (moved, like)
    L_new = _factorize(factors, rho_scale, keep)
    if isinstance(like, LInv):
        return _make_l_inv(L_new)
    return L_new


def _chol_solve(F, b):
    """Solve M x = b given _factorize's output F: an explicit inverse in
    f64 (one MXU matmul — M⁻¹ is symmetric) or a Cholesky factor in f32
    (triangular solves; see _factorize's docstring for why), or an LInv
    (explicit L⁻¹: two MXU matmuls of the same bytes as the triangular
    solves — the ops/kernels roofline trade), or a PreparedFactor (the
    shared f32 factor as _factorize hands it out: on the TPU the
    blocked substitution from stored diagonal-block inverses, elsewhere
    the triangular_solve pair on its ``tri``,
    doc/kernels.md §prepared factor). A bare f32 factor takes the
    lax.linalg.triangular_solve pair. An f64 b against an f32
    factor (the df32 x-update seed) solves in f32 and returns f64 — the
    refinement sweeps in _m_solve_ir own the accuracy."""
    if isinstance(F, LInv):
        out_dt = b.dtype
        u = b.astype(F.inv.dtype) @ F.inv.T     # u = L⁻¹ b (rows)
        return (u @ F.inv).astype(out_dt)       # x = L⁻ᵀ u
    if isinstance(F, PreparedFactor):
        # The prepared substitution IS the TPU expander's algorithm
        # minus its per-call preparations. Where triangular_solve is a
        # library call with nothing to hoist (LAPACK / cuBLAS trsm),
        # the pair on F.tri stays, so every backend keeps the
        # arithmetic it had. Chosen at lowering time, per platform: a
        # program compiled HERE for a described TPU takes the TPU form.
        return jax.lax.platform_dependent(
            F, b.astype(F.tri.dtype), tpu=_prepared_solve,
            default=lambda F, b: _pair_solve(F.tri, b)).astype(b.dtype)
    if F.dtype == jnp.float64:
        if F.ndim == 2:
            return b @ F
        return _batched_matvec(F, b)
    if F.ndim == 2:
        return _pair_solve(F, b.astype(F.dtype)).astype(b.dtype)
    return _tri_solve(F, b.astype(F.dtype)).astype(b.dtype)


@partial(jax.jit, static_argnames=("eq_boost", "shared"))
def _setup_vectors(P_diag, l, u, lb, ub, D, q_ref, rho_base, eq_boost,
                   shared):
    """Everything in qp_setup AFTER the scaled matrix exists: cost
    normalization + equality-boost rho patterns (vector math only).
    Deliberately takes/returns NO matrix: a jit that passes a matrix
    through to its output makes XLA COPY it per call — measured
    +2.7 GB per invocation at reference-UC scale. Returns
    (P_s, cost_scale, rho_A, rho_b); callers attach the matrix
    eagerly."""
    dt = D.dtype
    P_s = D * P_diag * D
    # cost normalization (OSQP sec 5.1): scale so the objective gradient is O(1)
    if q_ref is None:
        q_ref = jnp.zeros(lb.shape, dt)
    qs = D * q_ref
    gn_P = jnp.max(jnp.abs(P_s), axis=-1)
    gn_q = jnp.max(jnp.abs(qs), axis=-1)
    if shared:
        gnorm = jnp.maximum(gn_P, jnp.max(gn_q))          # scalar
        cost_scale = 1.0 / jnp.maximum(gnorm, 1.0)
        P_s = P_s * cost_scale
    else:
        gnorm = jnp.maximum(gn_P, gn_q)                   # (S,)
        cost_scale = 1.0 / jnp.maximum(gnorm, 1.0)
        P_s = P_s * cost_scale[:, None]

    def _is_eq(lo, hi):
        d_ = hi - lo
        return jnp.isfinite(d_) & (jnp.abs(d_)
                                   <= 1e-9 * (1.0 + jnp.abs(hi)))

    is_eq = _is_eq(l, u)      # (S, m)
    is_eq_b = _is_eq(lb, ub)  # (S, n)
    if shared:
        # a row must be an equality in EVERY scenario to earn the shared
        # boost (rho is only a stepsize, so the conservative AND is safe)
        is_eq = jnp.all(is_eq, axis=0)
        is_eq_b = jnp.all(is_eq_b, axis=0)
    rho_A = jnp.where(is_eq, rho_base * eq_boost, rho_base).astype(dt)
    rho_b = jnp.where(is_eq_b, rho_base * eq_boost, rho_base).astype(dt)
    return P_s, cost_scale, rho_A, rho_b


@partial(jax.jit, static_argnames=("eq_boost",))
def _qp_setup_dense(data: QPData, q_ref, rho_base, sigma, eq_boost):
    # one jit: A_s is CREATED inside, so returning it costs nothing
    # extra (unlike pass-through returns — see _setup_vectors)
    P_diag, A, l, u, lb, ub = data
    D, E, Eb = _ruiz_equilibrate(P_diag, A)
    A_s = E[..., :, None] * A * D[..., None, :]
    dt = A.dtype
    P_s, cost_scale, rho_A, rho_b = _setup_vectors(
        P_diag, l, u, lb, ub, D, q_ref, rho_base, eq_boost, A.ndim == 2)
    return QPFactors(sigma=jnp.asarray(sigma, dt), D=D, E=E, Eb=Eb,
                     cost_scale=cost_scale, A_s=A_s, P_s=P_s,
                     rho_A=rho_A, rho_b=rho_b)


@partial(jax.jit, static_argnames=("nblocks",))
def _scale_split_blocks(A: SplitMatrix, D, E, nblocks=8) -> SplitMatrix:
    """A_s = split(E·A·D) computed in ROW BLOCKS so the f64 value of
    the scaled matrix only ever exists one block at a time — the
    full-matrix form materializes several (m, n) f64 transients and
    OOMs a 16 G chip at reference-UC scale (measured)."""
    m = A.hi.shape[0]
    his, los = [], []
    bounds = [(m * i) // nblocks for i in range(nblocks + 1)]
    for i in range(nblocks):
        sl = slice(bounds[i], bounds[i + 1])
        blk = (A.hi[sl].astype(jnp.float64)
               + A.lo[sl].astype(jnp.float64)) \
            * E[sl, None] * D[None, :]
        hi = blk.astype(jnp.float32)
        los.append((blk - hi.astype(jnp.float64)).astype(jnp.float32))
        his.append(hi)
    return SplitMatrix(jnp.concatenate(his), jnp.concatenate(los))


def _qp_setup_split(data: QPData, q_ref, rho_base, sigma, eq_boost,
                    rows_per_call=None):
    """df32 setup: Ruiz on the f32 hi part (D/E/Eb are heuristic
    scalings — a 1e-7-relative view of |A| changes nothing), scaled
    split built blockwise, vector tail shared with the dense path. The
    QPFactors tuple is assembled EAGERLY so A_s never passes through a
    jit boundary (see _setup_vectors)."""
    A = data.A
    f64 = jnp.float64
    D32, E32, Eb32 = _ruiz_equilibrate(data.P_diag.astype(jnp.float32),
                                       A.hi)
    D, E, Eb = D32.astype(f64), E32.astype(f64), Eb32.astype(f64)
    A_s = _scale_split_blocks(A, D, E)
    if A.struct is not None:
        from .packed import pack, pack_profitable, packed_elems
        rows = data.l.shape[0] if rows_per_call is None else rows_per_call
        # a structure EXISTS (analyze_structure found one at ship
        # time); whether to USE it is decided here, once for every
        # consumer of these factors, from the shapes of the call that
        # will run the matvecs (ops/packed.pack_profitable). Left
        # dense, A_s keeps the skeleton and no packed values: the
        # matvecs are the dense split products, with Aᵀy's leading
        # pass summed by the skeleton's row classes (_ATy)
        A_s = A_s._replace(struct=A.struct)
        if pack_profitable(*A.shape, packed_elems(A.struct), rows):
            # gather the SCALED hi/lo into the packed matvec form (same
            # index skeleton for both — scaling preserves structure);
            # from here every hot-loop A-pass is packed
            A_s = A_s._replace(pk_hi=pack(A.struct, A_s.hi),
                               pk_lo=pack(A.struct, A_s.lo))
    P_s, cost_scale, rho_A, rho_b = _setup_vectors(
        data.P_diag, data.l, data.u, data.lb, data.ub, D, q_ref,
        rho_base, eq_boost, True)
    return QPFactors(sigma=jnp.asarray(sigma, f64), D=D, E=E, Eb=Eb,
                     cost_scale=cost_scale, A_s=A_s, P_s=P_s,
                     rho_A=rho_A, rho_b=rho_b)


def qp_setup(data: QPData, q_ref=None, rho_base=0.1, sigma=1e-6,
             eq_boost=1e3, rows_per_call=None):
    """Equilibrate and scale. Cheap relative to the solve; re-solves with a
    new q reuse everything. The equality-row rho boost pattern depends only
    on which rows/columns are pinned (l==u / lb==ub), so one setup serves
    every PH iteration of a mode. ``rows_per_call``: rows ONE device
    call solves with these factors, where that is not ``data``'s own
    row count (a chunked or sharded engine, a streamed source's 2-row
    surrogate): what a structured df32 matrix's packed form is held
    against (ops/packed.pack_profitable)."""
    if isinstance(data.A, SplitMatrix):
        return _qp_setup_split(data, q_ref, rho_base, sigma, eq_boost,
                               rows_per_call)
    return _qp_setup_dense(data, q_ref, rho_base, sigma, eq_boost)


@partial(jax.jit, static_argnames=("eq_boost", "shared"))
def _setup_like_vectors(P_diag, l, u, lb, ub, D, cost_scale, rho_base,
                        eq_boost, shared):
    csx = cost_scale if shared else cost_scale[:, None]
    P_s = D * P_diag * D * csx

    def _is_eq(lo, hi):
        d_ = hi - lo
        return jnp.isfinite(d_) & (jnp.abs(d_)
                                   <= 1e-9 * (1.0 + jnp.abs(hi)))

    is_eq = _is_eq(l, u)
    is_eq_b = _is_eq(lb, ub)
    if shared:
        is_eq = jnp.all(is_eq, axis=0)
        is_eq_b = jnp.all(is_eq_b, axis=0)
    dt = D.dtype
    rho_A = jnp.where(is_eq, rho_base * eq_boost, rho_base).astype(dt)
    rho_b = jnp.where(is_eq_b, rho_base * eq_boost, rho_base).astype(dt)
    return P_s, rho_A, rho_b


def qp_setup_like(base: QPFactors, data: QPData, rho_base=0.1,
                  eq_boost=1e3):
    """Factors for a RELATED mode (prox on/off, pinned boxes) REUSING
    ``base``'s equilibration and scaled matrix: only the scaled
    quadratic diagonal and the rho boost patterns are recomputed
    (vector math, jitted). The _replace happens EAGERLY — running it
    inside a jit would pass the multi-GB A_s through the jit boundary,
    which XLA copies per call (measured +2.7 GB per mode at
    reference-UC scale, the exact duplication this function exists to
    avoid). The Ruiz scalings are heuristic — a mode whose P differs
    on a diagonal block is equally well served by the base mode's
    D/E."""
    shared = base.A_s.ndim == 2
    P_s, rho_A, rho_b = _setup_like_vectors(
        data.P_diag, data.l, data.u, data.lb, data.ub, base.D,
        base.cost_scale, rho_base, eq_boost, shared)
    return base._replace(P_s=P_s, rho_A=rho_A, rho_b=rho_b)


def qp_reset_rho(factors: QPFactors, state: QPState) -> QPState:
    """Reset the adaptive-rho trajectory: rho_scale back to 1 with the
    matching refactorization — the recovery move for a warm-started
    state whose adaptation went pathological (the same pattern
    qp_cold_state and the mixed escalation's phase handoffs use).
    Iterates are kept; only the stepsize/factor reset."""
    ones = jnp.ones_like(state.rho_scale)
    return state._replace(rho_scale=ones, L=factorize_dispatch(factors, ones))


def _zero_state(factors: QPFactors, data: QPData, L) -> QPState:
    """The ONE cold-state literal (zeros + inf residuals + the given
    factor) — every QPState field addition must land here exactly once."""
    S, m = data.l.shape
    n = data.lb.shape[-1]
    dt = factors.A_s.dtype
    shared = factors.A_s.ndim == 2
    rho_scale = jnp.ones((), dt) if shared else jnp.ones((S,), dt)
    return QPState(x=jnp.zeros((S, n), dt), yA=jnp.zeros((S, m), dt),
                   yB=jnp.zeros((S, n), dt), zA=jnp.zeros((S, m), dt),
                   zB=jnp.zeros((S, n), dt), L=L, rho_scale=rho_scale,
                   iters=jnp.zeros((), jnp.int32),
                   iters_lo=jnp.zeros((), jnp.int32),
                   refactors=jnp.zeros((), jnp.int32),
                   pri_res=jnp.full((S,), jnp.inf, dt),
                   dua_res=jnp.full((S,), jnp.inf, dt),
                   pri_rel=jnp.full((S,), jnp.inf, dt),
                   dua_rel=jnp.full((S,), jnp.inf, dt))


def _cold_state_impl(factors: QPFactors, data: QPData) -> QPState:
    S = data.l.shape[0]
    dt = factors.A_s.dtype
    shared = factors.A_s.ndim == 2
    rho_scale = jnp.ones((), dt) if shared else jnp.ones((S,), dt)
    return _zero_state(factors, data, _factorize(factors, rho_scale))


_cold_state_jit = compile_serialized(jax.jit(_cold_state_impl))


def qp_cold_state(factors: QPFactors, data: QPData,
                  build_log=None) -> QPState:
    """A mode's cold state: zeros and the factor at ``rho_scale`` 1.
    Where that factor is a per-scenario float64 inverse built on the
    device, the build is the span ``qp.f64_refactor_build``; it waits
    for the inverse (a cold state's first solve follows, which nothing
    overlaps), so its seconds are the build's, and ``build_log`` (the
    plan's ``KernelPlan.f64_build``) adds them up as {builds, seconds,
    rows, n}."""
    if _needs_host_factor(factors):
        # host-exact inverse (see _device_f64_linalg_trusted) — not
        # worth a device program that would compute (and discard) the
        # garbage batched inverse
        S = data.l.shape[0]
        rho_scale = jnp.ones((S,), factors.A_s.dtype)
        return _zero_state(factors, data,
                           factorize_dispatch(factors, rho_scale))
    A_s = factors.A_s
    if f64_refactor_form(A_s) is None or A_s.ndim != 3:
        return _cold_state_jit(factors, data)
    shape = {"rows": int(A_s.shape[0]), "n": int(A_s.shape[-1])}
    with obs.span("qp.f64_refactor_build", cat="qp", args=shape) as sp:
        st = _cold_state_jit(factors, data)
        # lint: ok[SYNC001] a cold state's one eager build, ahead of its first solve: the span's seconds are the build's only if it waits
        jax.block_until_ready(st.L)
    if build_log is not None:
        build_log.update(shape, builds=build_log.get("builds", 0) + 1,
                         seconds=build_log.get("seconds", 0.0) + sp.seconds)
    return st


def _scaled_problem(factors: QPFactors, data: QPData, q):
    """The scaled problem vectors one solve iterates in:
    (g, l_s, u_s, lb_s, ub_s, csx, q_s)."""
    _, D, E, Eb, cs, A_s, _, _, _ = factors
    shared = A_s.ndim == 2
    g = Eb * D
    l_s, u_s = E * data.l, E * data.u
    lb_s, ub_s = Eb * data.lb, Eb * data.ub
    csx = cs if shared else cs[:, None]
    q_s = csx * D * q
    return g, l_s, u_s, lb_s, ub_s, csx, q_s


def _solve_impl(factors: QPFactors, data: QPData, q, state: QPState,
                max_iter=4000, check_every=25, eps_abs=1e-6, eps_rel=1e-6,
                alpha=1.6, adaptive_rho=True, polish=True, polish_iters=12,
                polish_chunk=0, eps_abs_dua=None, eps_rel_dua=None,
                stall_rel=0.0, ir_sweeps=1):
    """Traceable body of qp_solve (shared by the jitted single-precision
    entry and the mixed-precision escalation driver below).

    ``eps_*_dua`` (default: same as the primal pair) let a caller loosen
    the DUAL termination test independently: on degenerate LPs the ADMM
    dual residual plateaus (y drifts along redundant-row null spaces)
    orders of magnitude above the primal one, and a consumer that only
    needs primal iterates (the PH hot loop — bounds come from separate
    prox-off solves) would otherwise burn its whole iteration budget
    waiting on a test that cannot pass. The polish still runs and still
    recovers the best certified duals it can.

    STALL EXIT: degenerate LPs also plateau the PRIMAL residual above any
    tight tolerance (first-order methods converge slowly along degenerate
    faces). A scenario counts as finished when its residuals improved
    less than 5% since the previous check AND its primal residual is
    below the coarse ``stall_rel`` gate (relative) — at that point
    further iterations tread water and the active-set polish is the
    productive step. Checks immediately after a rho refactorize are
    exempt (the residual jump would false-trigger). OFF by default
    (stall_rel=0): exact consumers (tests, small well-conditioned
    models) keep the strict contract; plateau-prone model configs (UC)
    opt in via engine options.
    POLISH: detect the active set from the final slacks, factor the
    penalty KKT matrix restricted to active rows, and run a few
    augmented-Lagrangian refinement steps. First-order ADMM stalls on the
    dual residual for degenerate LPs (y drifts along redundant-constraint
    null spaces); polishing recovers near-exact primal/dual pairs — which
    every certified bound in the framework (Ebound, Lagrangian spokes,
    Benders cuts) consumes — at the cost of a few extra batched Choleskys.
    Polished results are accepted PER SCENARIO only where they improve
    max(pri, dua), and the returned duals are the per-scenario argmax of
    the certified dual objective over all candidates (any dual vector
    yields a valid bound, so the argmax is valid), so a wrong active-set
    guess can never degrade a solve.

    The polish factors are per-scenario (S, n, n) even in shared-structure
    mode (active sets differ per scenario). For large S set
    ``polish_chunk`` (must divide S) to bound that transient: the polish
    tail is lax.map'ed over S/polish_chunk chunks.

    Returns (state, x (S,n), yA (S,m), yB (S,n)) — all UNscaled; yA are the
    constraint-row duals, yB the variable-bound duals. `q` is the unscaled
    linear cost. Warm start by passing the previous state (its adapted rho
    and factor carry over); cold start with `qp_cold_state(factors, data)`.
    """
    sigma, D, E, Eb, cs, A_s, P_s, rho_A, rho_b = factors
    shared = A_s.ndim == 2
    if isinstance(A_s, SplitMatrix):
        # the polish broadcasts A_s per scenario ((S, n, n) penalty
        # factors) — structurally impossible at the scale the df32
        # representation exists for; duals come from the ADMM iterates
        # (still a VALID bound via qp_dual_objective) and exact
        # tightening, when needed, from the host oracle
        polish = False
    g, l_s, u_s, lb_s, ub_s, csx, q_s = _scaled_problem(factors, data, q)
    dt = A_s.dtype
    eps_abs = jnp.asarray(eps_abs, dt)
    eps_rel = jnp.asarray(eps_rel, dt)
    eps_abs_dua = eps_abs if eps_abs_dua is None else jnp.asarray(eps_abs_dua, dt)
    eps_rel_dua = eps_rel if eps_rel_dua is None else jnp.asarray(eps_rel_dua, dt)

    def rho_of(rho_scale):
        rs = rho_scale if shared else rho_scale[:, None]
        return rho_A * rs, rho_b * rs

    def _m_solve_ir(L, rhs, rA, rB):
        """df32 x-update: f32 triangular solves + ``ir_sweeps`` sweeps
        of mixed-precision iterative refinement. The residual
        r = rhs − Mx is computed through the SPLIT matvecs (f64
        accumulation of f32 MXU passes), so each sweep contracts the
        error by ~κ(M)·eps32 — the standard IR argument — landing well
        below the ADMM tolerance without a single f64 matmul. M is
        applied in factored form (P, σ, A_sᵀρA_s, bound rows); no
        (n, n) product is ever stored.

        ONE sweep is the default (r5): the f32 seed's relative error is
        ~κ(M)·eps32 ≈ 4e-4 on the equilibrated UC KKT (κ ≈ 6e3), so one
        sweep lands at ~(κ·eps32)² ≈ 2e-7 — two decades below the
        tightest tolerance any caller runs at df32 scale (1e-5) and
        below the split representation's own ~1e-7 accumulation floor.
        The second sweep bought nothing measurable while costing an
        extra m_apply + solve (~1/3 of the tail iteration's HBM
        traffic). ``subproblem_ir_sweeps`` raises it back."""
        def m_apply(v):
            return P_s * v + sigma * v + _ATy(A_s, rA * _Ax(A_s, v)) \
                + g * g * rB * v

        x = _chol_solve(L, rhs)
        for _ in range(ir_sweeps):
            with jax.named_scope("qp.ir_sweep"):
                x = x + _chol_solve(L, rhs - m_apply(x))
        return x

    def admm_chunk(x, yA, yB, zA, zB, L, rA, rB):
        split_mode = isinstance(A_s, SplitMatrix)
        # un-refined solves must NOT use an explicit L⁻¹ (see LInv: the
        # inverse is licensed only under IR contraction) — an LInv
        # carry hands them its raw factor, prepared HERE, once per
        # check_every iterations and not inside the scan
        F_plain = _prepare_factor(L.tri) \
            if isinstance(L, LInv) and not split_mode else L

        def iterations(carry, rows):
            """``check_every`` ADMM iterations on the scenarios whose
            operands ``rows`` holds: the whole batch, or one block of a
            wide float64 stack (below)."""
            A_s, F_plain, q_s, g, rA, rB, l_s, u_s, lb_s, ub_s = rows

            def one(carry, _):
                x, yA, yB, zA, zB = carry
                rhs = sigma * x - q_s + _ATy(A_s, rA * zA - yA) \
                    + g * (rB * zB - yB)
                with jax.named_scope("qp.kkt_solve"):
                    x_t = _m_solve_ir(L, rhs, rA, rB) if split_mode \
                        else _chol_solve(F_plain, rhs)
                x_new = alpha * x_t + (1 - alpha) * x
                zA_t = _Ax(A_s, x_t)
                zA_mix = alpha * zA_t + (1 - alpha) * zA
                zA_new = jnp.clip(zA_mix + yA / rA, l_s, u_s)
                yA_new = yA + rA * (zA_mix - zA_new)
                zB_t = g * x_t
                zB_mix = alpha * zB_t + (1 - alpha) * zB
                zB_new = jnp.clip(zB_mix + yB / rB, lb_s, ub_s)
                yB_new = yB + rB * (zB_mix - zB_new)
                return (x_new, yA_new, yB_new, zA_new, zB_new), None

            return jax.lax.scan(one, carry, None, length=check_every)[0]

        carry = (x, yA, yB, zA, zB)
        rows = (A_s, F_plain, q_s, g, rA, rB, l_s, u_s, lb_s, ub_s)
        B = f64_stack_block_rows(A_s)
        if B is None:
            return iterations(carry, rows)
        # a wide per-scenario float64 stack: the same iterations, a
        # block of B scenarios at a time (doc/kernels.md §3i). Between
        # two checks the scenarios are independent (rho is a row's own;
        # residuals, exits and the rebuild sit in ``check``), so block
        # by block the iterates are the whole-stack scan's numbers.
        # The blocks are a reshape of the leading axis (no copy of an
        # (S, n, n) array); a block's matrices are loop-invariant
        # inputs of an inner scan that holds no ``conditional``
        obs.counter_add("kernel.f64_stack_blocked")
        blocks = jax.tree.map(
            lambda a: a.reshape((-1, B) + a.shape[1:]), (carry, rows))
        out = jax.lax.map(lambda t: iterations(*t), blocks)
        return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)

    def residuals(x, yA, yB, zA, zB):
        return _unscaled_residuals(A_s, P_s, g, D, E, Eb, csx, q_s,
                                   x, yA, yB, zA, zB)

    def live(it, done):
        return jnp.logical_and(it < max_iter, jnp.logical_not(done))

    def check(vals, L, refactor):
        """One residual check of the loop: ``check_every`` iterations on
        the factor ``L``, the residuals, the exit tests and the rho
        adaptation's decision. ``refactor(need, rho_scale, L, moved)`` gives
        the factor the loop's carry holds next. Returns the carry's
        values (``L`` apart), that factor, and ``need`` / ``adapt_now``
        (whether rho moved; whether this was a period's fourth check:
        None where rho is frozen)."""
        (x, yA, yB, zA, zB, rho_scale, it, _, best_pri, best_dua,
         stall_ct, nref) = vals
        rA, rB = rho_of(rho_scale)
        x, yA, yB, zA, zB = admm_chunk(x, yA, yB, zA, zB, L, rA, rB)
        with jax.named_scope("qp.check"):
            pri, dua, pri_sc, dua_sc = residuals(x, yA, yB, zA, zB)
            conv_ok = jnp.logical_and(
                pri <= eps_abs + eps_rel * pri_sc,
                dua <= eps_abs_dua + eps_rel_dua * dua_sc)
        # stall exit (window-based, oscillation-robust): a scenario whose
        # BEST residual pair hasn't improved 5% in 4 consecutive checks
        # while its primal passes the coarse gate is plateaued — the
        # productive next step is the polish, not more iterations
        if stall_rel:
            improved = (pri <= 0.95 * best_pri) | (dua <= 0.95 * best_dua)
            best_pri = jnp.minimum(best_pri, pri)
            best_dua = jnp.minimum(best_dua, dua)
        rho_changed = jnp.zeros_like(conv_ok)   # per-scenario where possible
        need = adapt_now = moved = None
        # ``adaptive_rho``: a python bool (a jit static — False leaves
        # the adaptation out of the program) or a TRACED flag (the fused
        # df32 program: one executable serves the hot loop and the
        # frozen-rho incumbent pool)
        if adaptive_rho is not False:
            with jax.named_scope("qp.rho_adapt"):
                # OSQP-style infrequent adaptation: every 4th residual check;
                # adopt only when the ideal rho moved by > 5x. In shared mode
                # the scale is a single scalar (geometric mean of the
                # per-scenario ideals) so the factor stays shared.
                adapt_now = ((it // check_every) % 4) == 3
                not_conv = jnp.logical_not(jnp.all(conv_ok))
                ratio_s = jnp.sqrt((pri / pri_sc)
                                   / jnp.maximum(dua / dua_sc, 1e-30))
                if shared:
                    ratio = jnp.exp(jnp.mean(jnp.log(
                        jnp.clip(ratio_s, 1e-6, 1e6))))
                    new_scale = jnp.clip(rho_scale * ratio, 1e-6, 1e6)
                    change = jnp.maximum(new_scale / rho_scale,
                                         rho_scale / new_scale)
                    upd = (change > 5.0) & adapt_now & not_conv & adaptive_rho
                    rho_scale = jnp.where(upd, new_scale, rho_scale)
                    need = upd
                    # one shared scalar: a refactorize resets every
                    # scenario's stall window (their stepsize DID change)
                    rho_changed = jnp.broadcast_to(need, conv_ok.shape)
                else:
                    new_scale = jnp.clip(rho_scale * ratio_s, 1e-6, 1e6)
                    change = jnp.maximum(new_scale / rho_scale,
                                         rho_scale / new_scale)
                    mask = (change > 5.0) & adapt_now & not_conv \
                        & adaptive_rho
                    rho_scale = jnp.where(mask, new_scale, rho_scale)
                    need = jnp.any(mask)
                    # per-scenario rho: only the scenarios whose rho moved
                    # restart their stall window — an unrelated scenario's
                    # refactorize must not postpone another's plateau exit
                    # (ADVICE r2)
                    rho_changed = moved = mask
                L = refactor(need, rho_scale, L, moved)
                nref = nref + need.astype(nref.dtype)
        if stall_rel:
            # a rho refactorize resets the window (the residual jump is
            # expected, not a plateau)
            stall_ct = jnp.where(improved | rho_changed, 0, stall_ct + 1)
            stalled = (stall_ct >= 4) & (pri <= stall_rel * pri_sc)
        else:
            stalled = jnp.zeros_like(conv_ok)
        done = jnp.all(conv_ok | stalled)
        return ((x, yA, yB, zA, zB, rho_scale, it + check_every, done,
                 best_pri, best_dua, stall_ct, nref), L, need, adapt_now)

    # the loop's carry is (x, yA, yB, zA, zB, L, rho_scale, it, done,
    # best_pri, best_dua, stall_ct, nref); ``check`` takes L apart
    def apart(carry):
        return carry[:5] + carry[6:], carry[5]

    def whole(vals, L):
        return vals[:5] + (L,) + vals[5:]

    def cond(carry):
        return live(carry[7], carry[8])

    def body(carry):
        vals, L, _, _ = check(
            *apart(carry),
            lambda need, rho_scale, L, moved: jax.lax.cond(
                need, lambda: _refactor_like(factors, rho_scale, L, moved),
                lambda: L))
        return whole(vals, L)

    def period_body(carry):
        """The loop's body where the factor is a small per-scenario
        float64 inverse (``f64_loop_form`` "resident", doc/kernels.md
        §3g): one PERIOD of the adaptation a turn. The inner loop runs
        up to four checks on a factor that is its loop-invariant
        operand and leaves on ``done``, on ``max_iter`` or after the
        period's fourth check, the only one at which rho can move;
        then ONE unconditional rebuild from the ``rho_scale`` it left,
        kept where rho moved. Check for check the computation of
        ``body``, with no ``conditional`` in a loop that carries the
        factor: behind one the v5e compiler leaves the loop's 3-D
        operands in HBM."""
        vals, L = apart(carry)

        def in_period(c):
            vals, _, period_end = c
            it, done = vals[6:8]
            return jnp.logical_and(live(it, done),
                                   jnp.logical_not(period_end))

        def one_check(c):
            vals, _, need, adapt_now = check(
                c[0], L, lambda need, rho_scale, L, moved: L)
            return vals, need, adapt_now

        vals, need, _ = jax.lax.while_loop(
            in_period, one_check, (vals, jnp.array(False), jnp.array(False)))
        rho_scale = vals[5]
        with jax.named_scope("qp.rho_adapt"):
            L = jnp.where(need, _refactor_like(factors, rho_scale, L), L)
        return whole(vals, L)

    loop_form = None if adaptive_rho is False else f64_loop_form(A_s)
    if loop_form:
        # trace-time count of the float64 loops traced each way
        obs.counter_add(f"kernel.f64_loop_{loop_form}")
    S_ = data.l.shape[0]
    inf0 = jnp.full((S_,), jnp.inf, dt)
    ct0 = jnp.zeros((S_,), jnp.int32)
    x, yA, yB, zA, zB, L, rho_scale, it, _, _, _, _, nref = \
        jax.lax.while_loop(
            cond, period_body if loop_form == "resident" else body,
            (state.x, state.yA, state.yB, state.zA, state.zB, state.L,
             state.rho_scale, jnp.zeros((), jnp.int32), jnp.array(False),
             inf0, inf0, ct0, jnp.zeros((), jnp.int32)))

    pri, dua, pri_sc, dua_sc = residuals(x, yA, yB, zA, zB)
    # the ADMM iterates are what the NEXT solve warm-starts from (the
    # polished point sits exactly on the active set — a bad center when the
    # next q moves it)
    new_state = QPState(x=x, yA=yA, yB=yB, zA=zA, zB=zB, L=L,
                        rho_scale=rho_scale, iters=it,
                        iters_lo=jnp.zeros((), jnp.int32), refactors=nref,
                        pri_res=pri, dua_res=dua, pri_rel=pri / pri_sc,
                        dua_rel=dua / dua_sc)

    if not polish:
        return new_state, D * x, (E / csx) * yA, (Eb / csx) * yB

    # ---- polish tail (chunkable over the scenario axis) ----
    per = dict(x=x, yA=yA, yB=yB, zA=zA, zB=zB, q_s=q_s,
               l_s=l_s, u_s=u_s, lb_s=lb_s, ub_s=ub_s,
               l=data.l, u=data.u, lb=data.lb, ub=data.ub, q=q,
               pri=pri, dua=dua, pri_sc=pri_sc, dua_sc=dua_sc)
    if not shared:
        per.update(A_s=A_s, P_s=P_s, D=D, E=E, Eb=Eb, cs=cs,
                   Pd=data.P_diag, A_raw=data.A)

    @jax.named_scope("qp.polish")
    def tail(ps):
        A_l = ps.get("A_s", A_s)
        P_l = ps.get("P_s", P_s)
        D_l = ps.get("D", D)
        E_l = ps.get("E", E)
        Eb_l = ps.get("Eb", Eb)
        cs_l = ps.get("cs", cs)
        csx_l = cs_l if shared else cs_l[:, None]
        g_l = Eb_l * D_l
        # the dual-objective evaluation needs the UNSCALED problem data
        d_l = QPData(ps.get("Pd", data.P_diag), ps.get("A_raw", data.A),
                     ps["l"], ps["u"], ps["lb"], ps["ub"])
        out = _polish_select(
            A_l, P_l, g_l, D_l, E_l, Eb_l, cs_l, csx_l, sigma, d_l,
            ps["q"], ps["q_s"], ps["l_s"], ps["u_s"], ps["lb_s"], ps["ub_s"],
            ps["x"], ps["yA"], ps["yB"], ps["zA"], ps["zB"],
            ps["pri"], ps["dua"], ps["pri_sc"], ps["dua_sc"],
            polish_iters, shared, eps_abs, eps_rel)
        return out

    S = data.l.shape[0]
    if polish_chunk and 0 < polish_chunk < S:
        # pad to a chunk multiple with copies of scenario 0 so a
        # non-dividing chunk size still bounds the (chunk, n, n) transient
        # instead of silently falling back to the full-batch polish
        rem = (-S) % polish_chunk
        Sp = S + rem
        if rem:
            per = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (rem,) + a.shape[1:])]), per)
        nc = Sp // polish_chunk
        resh = lambda a: a.reshape((nc, polish_chunk) + a.shape[1:])
        unresh = lambda a: a.reshape((Sp,) + a.shape[2:])[:S]
        out = jax.lax.map(tail, jax.tree.map(resh, per))
        x_un, yA_un, yB_un, pri, dua, pri_sc = jax.tree.map(unresh, out)
    else:
        x_un, yA_un, yB_un, pri, dua, pri_sc = tail(per)

    # dua_rel keeps the pre-polish dual scale (the polish tail returns
    # no dua_sc); the rel metrics' consumer is the host rho adaptation,
    # which runs between LOOP segments, before any polish
    new_state = new_state._replace(pri_res=pri, dua_res=dua,
                                   pri_rel=pri / pri_sc)
    return new_state, x_un, yA_un, yB_un


_SOLVE_STATICS = ("max_iter", "check_every", "adaptive_rho", "polish",
                  "polish_iters", "polish_chunk", "stall_rel", "ir_sweeps")


# Jitted single-precision solve — see _solve_impl for the algorithm.
# Like every entry below that factors or applies an (n, n) operator, it
# compiles under the process-wide gate (utils/runtime.compile_serialized).
_qp_solve_jit = compile_serialized(
    jax.jit(_solve_impl, static_argnames=_SOLVE_STATICS), _SOLVE_STATICS)


# DONATED twin of _qp_solve_jit: the incoming QPState's buffers are handed
# to XLA for reuse (``jax.jit(donate_argnames=("state",))``), so a solve
# that carries L through unchanged ALIASES it into the output instead of
# materializing a fresh (n, n) copy per call — at reference-UC scale each
# warm-started segment call otherwise produces a new ~0.7 GB factor buffer
# (4 segments/solve ≈ the +2.7 GB-per-chunk churn noted at core/ph.py's
# assemble boundary). CALLER CONTRACT: every leaf of ``state`` must be
# uniquely owned — after the call the input state's arrays are DELETED
# (reads raise), including leaves the program only passed through. The
# chunked PH driver tracks ownership (first pass after a (re)build shares
# cold-state buffers across chunks and must not donate); everyone else
# defaults to the copying twin.
_qp_solve_jit_donated = compile_serialized(
    jax.jit(_solve_impl, static_argnames=_SOLVE_STATICS,
            donate_argnames=("state",)), _SOLVE_STATICS)


_WARNED_FROZEN_RHO = False


def qp_solve(factors: QPFactors, data: QPData, q, state: QPState,
             donate=False, **kw):
    """Single-precision solve (see _solve_impl). On backends whose f64
    device linalg is untrusted (see _device_f64_linalg_trusted),
    non-shared f64 solves run with IN-JIT rho refactorization disabled —
    the warm state's host-exact inverse (qp_cold_state / qp_reset_rho /
    the mixed handoff) stays valid for the whole call; the host
    refactorization happens between calls (_host_adapt_rho).

    ``donate=True`` routes through the donated jit (see
    _qp_solve_jit_donated): ``state``'s buffers are consumed — only pass
    a state no other live object references."""
    if kw.get("adaptive_rho", True) and _needs_host_factor(factors):
        kw["adaptive_rho"] = False
        # direct callers (not qp_solve_segmented, which substitutes
        # _host_adapt_rho at segment boundaries) silently lose rho
        # adaptation here, and badly scaled scenarios then keep dual
        # residuals orders of magnitude loose at rho_scale=1 (ADVICE
        # r3). Tell them once so they can route through
        # qp_solve_segmented instead.
        if not kw.pop("_segmented_caller", False):
            global _WARNED_FROZEN_RHO
            if not _WARNED_FROZEN_RHO:
                _WARNED_FROZEN_RHO = True
                import warnings

                warnings.warn(
                    "qp_solve: in-jit rho adaptation force-disabled "
                    "(non-shared f64 factors on a backend with "
                    "untrusted f64 device linalg). Dual residuals may "
                    "stay loose at the warm-start rho; use "
                    "qp_solve_segmented, which adapts rho host-side at "
                    "segment boundaries.", RuntimeWarning, stacklevel=2)
    else:
        kw.pop("_segmented_caller", None)
    fn = _qp_solve_jit_donated if donate else _qp_solve_jit
    return fn(factors, data, q, state, **kw)


def qp_solve_segmented(factors: QPFactors, data: QPData, q, state: QPState,
                       max_iter=4000, segment=500, donate=False, **kw):
    """Host-driven segmented solve: run the jitted loop in warm-started
    SEGMENTS of at most ``segment`` iterations (polish deferred to one
    final call), accumulating until convergence/stall or ``max_iter``.

    Segmenting costs one host dispatch per ``segment`` iterations
    (microseconds against tens of milliseconds of device work) and buys
    bounded execution times, warm-started continuation, and a natural
    place for host-side progress control (the host rho adaptation of
    untrusted-f64 backends rides the segment boundary). It is the
    recovery/hospital path's driver and the ``segmented`` kernel mode;
    the attached v5e itself sets no ceiling on one program's length
    (36,794 f64 matmul iterations ran 59.7 s in one program, CHANGES.md
    PR 24). Returns the same (state, x, yA, yB) contract.

    NOTE: segments always run FULL (``segment`` is a static jit arg),
    so the total can overshoot ``max_iter`` by up to one segment —
    ``max_iter=100, segment=500`` runs up to 500 iterations. Callers
    that need a hard ceiling pass ``segment <= max_iter``.

    ``donate`` applies to the CALLER's ``state`` only; once the first
    segment has produced a chain-owned successor, every later segment
    donates it regardless (the chain is this function's private state,
    so per-segment factor copies die even for non-donating callers)."""
    final_polish = kw.pop("polish", True)
    host_adapt = kw.get("adaptive_rho", True) and _needs_host_factor(factors)
    total = refs = 0
    owned = donate
    while total < max_iter:
        # always run FULL segments: max_iter is a static jit arg, so a
        # data-dependent remainder would compile a whole extra UC-sized
        # program per distinct remainder (~minutes each on a slow
        # compile path); overshoot is bounded by one segment and the
        # convergence/stall exit stops early anyway
        t_seg = time.perf_counter()
        # one device call plus its read-back: the grain a profiler
        # slice shorter than the solve phase still holds whole
        with obs.span("qp.segment", cat="qp"):
            state, _, _, _ = qp_solve(factors, data, q, state,
                                      max_iter=segment, polish=False,
                                      donate=owned,
                                      _segmented_caller=True, **kw)
            owned = True
            _trace_seg("hi-seg", t_seg, state)
            ran, ref = _segment_counts(state)
        total += ran
        refs += ref
        if ran < segment:   # early exit: converged or stalled
            break
        if host_adapt:
            # in-jit rho adaptation is disabled on untrusted-f64
            # backends (qp_solve); the segment boundary is the host's
            # natural stand-in — same OSQP ratio rule, host-exact
            # refactorization. Without it, badly scaled scenarios keep
            # a huge DUAL residual at rho_scale=1 (measured on farmer:
            # primal 1e-14 but dual objectives thousands of times too
            # loose), poisoning every certified bound.
            with obs.span("qp.host_rho_adapt", cat="qp"):
                state = _host_adapt_rho(factors, state)
    # final call: loop skipped (max_iter=0), polish runs
    with obs.span("qp.polish_call", cat="qp"):
        state, x, yA, yB = qp_solve(factors, data, q, state, max_iter=0,
                                    polish=final_polish, donate=owned,
                                    _segmented_caller=True, **kw)
    # HOST scalars: the driver holds these counts already, so a caller
    # that books them (core/ph._book_admm_iters) reads no device buffer
    # — the polish call above stays in flight behind it
    state = state._replace(iters=np.int32(total), iters_lo=np.int32(0),
                           refactors=np.int32(refs))
    return state, x, yA, yB


def _segment_counts(state):
    """(iters, refactors) of a finished segment as host ints — the
    segmented drivers' one blocking read per segment."""
    return (int(v) for v in jax.device_get((state.iters, state.refactors)))


def _host_adapt_rho(factors: QPFactors, state: QPState) -> QPState:
    """Per-scenario OSQP rho adaptation at a segment boundary, with the
    refactorization on the HOST (see _device_f64_linalg_trusted): adopt
    sqrt(pri_rel/dua_rel) when the ideal moved > 5x — the same rule the
    in-jit non-shared branch applies every 4th residual check."""
    pr = np.asarray(state.pri_rel)
    dr = np.asarray(state.dua_rel)
    if obs.enabled():
        obs.counter_add("xfer.d2h_bytes",
                        pr.nbytes + dr.nbytes
                        + int(state.rho_scale.nbytes))
    ratio = np.sqrt(np.maximum(pr, 1e-30) / np.maximum(dr, 1e-30))
    old = np.asarray(state.rho_scale)
    new = np.clip(old * np.clip(ratio, 1e-6, 1e6), 1e-6, 1e6)
    change = np.maximum(new / old, old / new)
    mask = np.isfinite(change) & (change > 5.0)
    if not mask.any():
        return state
    rho_np = np.where(mask, new, old)
    rho = jnp.asarray(rho_np, state.rho_scale.dtype)
    # invert only the changed scenarios' KKTs and scatter — a full
    # (S, n, n) host inversion per segment would grow linearly with S
    rows = np.flatnonzero(mask)
    obs.counter_add("qp.host_rho_refactors", rows.size)
    inv = _factorize_host(factors, rho_np, rows=rows)
    # ONE scatter program whatever the number of rows that moved: the
    # index vector is padded to S by repeating the first changed row
    # (duplicate writes of one block). A count-dependent shape would
    # compile a new program the first time a count appears — mid-run,
    # and against the serving layer's compile-once contract.
    fill = np.zeros(old.size - rows.size, np.intp)
    L_rows = jnp.asarray(inv[np.concatenate([np.arange(rows.size), fill])])
    if obs.enabled():
        # nbytes is metadata — no readback of the freshly shipped block
        obs.counter_add("xfer.h2d_bytes", int(L_rows.nbytes))
    at = jnp.asarray(np.concatenate([rows, rows[fill]]))
    return state._replace(rho_scale=rho, L=state.L.at[at].set(L_rows))


def _cast_floats(tree, dt):
    """Cast the floating leaves of a NamedTuple pytree; ints ride along."""
    return jax.tree.map(
        lambda a: a.astype(dt)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def qp_solve_mixed(factors: QPFactors, data: QPData, q, state: QPState,
                   max_iter=4000, tail_iter=1000, check_every=25,
                   eps_abs=1e-6, eps_rel=1e-6, alpha=1.6, adaptive_rho=True,
                   polish=True, polish_iters=12, polish_chunk=0,
                   eps_abs_dua=None, eps_rel_dua=None, stall_rel=0.0,
                   segment=500, segment_lo=None, ir_sweeps=1, donate=False):
    """Precision-escalated solve: an f32 bulk phase (MXU-friendly — the
    thousands of ADMM matmuls run at accelerator speed) followed by an f64
    tail (one refactorization + a few hundred iterations + the polish).

    Rationale: pure-f32 ADMM stalls at a relative-residual noise floor of
    ~1e-2 on badly scaled LPs (UC: costs spanning 1e1..5e3, loads ~2e3),
    far above the 1e-4..1e-6 the certified bounds and incumbent
    feasibility checks need; pure f64 wastes the accelerator on iterations
    that don't need the precision. The f32 phase does the convergence
    work, the f64 tail does the accuracy work. Everything (factors, data,
    state) arrives in f64; the f32 copies are cast inside the jit.

    BUDGET SEMANTICS: ``max_iter`` bounds only the f32 bulk phase and
    ``tail_iter`` the f64 tail — total work can reach max_iter +
    tail_iter (plus one segment of overshoot each, see
    qp_solve_segmented). PH's ``subproblem_max_iter`` therefore caps
    the bulk, not the sum, when subproblem_precision='mixed'; the tail
    is bounded separately by ``subproblem_tail_iter``. rho adaptation
    stays on in both phases (the tail refactorizes in f64 when the
    ratio moves >5x — worth it when the f32 handoff mis-scaled rho).
    Both phases run SEGMENTED like qp_solve_segmented; ``segment_lo``
    (default: ``segment``) sets the f32 phase's segment separately —
    fewer, longer f32 calls cut per-dispatch overhead. Returns the same
    (state, x, yA, yB) contract as qp_solve, with the state in f64.
    """
    lo = jnp.float32
    # df32 factors/data carry SplitMatrix A — the f32 bulk phase wants
    # the PLAIN hi part (one MXU pass per matvec, not three) and a plain
    # f32 Cholesky factor; a packed split hands the bulk its packed-hi
    # view (dense hi rides along for in-loop refactorization)
    if isinstance(factors.A_s, SplitMatrix):
        A_hi = factors.A_s.hi
        if factors.A_s.pk_hi is not None:
            A_hi = PackedMatrix(A_hi, factors.A_s.pk_hi)
        factors_lo_src = factors._replace(A_s=A_hi)
    else:
        factors_lo_src = factors
    data_lo_src = data._replace(A=data.A.hi) \
        if isinstance(data.A, SplitMatrix) else data
    f_lo = _cast_floats(factors_lo_src, lo)
    d_lo = _cast_floats(data_lo_src, lo)
    st_lo = _cast_floats(state, lo)
    if isinstance(factors.A_s, SplitMatrix):
        # df32 state already carries the f32 Cholesky of THIS M at the
        # state's rho — recomputing it per solve call would add an
        # (n, n) factorization (plus its transients) to every chunk
        # call for an identical result
        pass
    else:
        # jitted: the eager path materializes every factorization
        # transient (the weighted matrix, the product, the factor) as
        # separate buffers — at big scale ~4 GB of avoidable peak
        st_lo = st_lo._replace(L=_factorize_jit(f_lo, st_lo.rho_scale))
    # the f32 phase is a WARM START for the f64 phase: stop it at its
    # noise floor (~1e-3 relative on badly-scaled LPs) — iterating f32
    # past that treads water and, worse, feeds the rho adaptation noise
    eps_lo = jnp.maximum(jnp.asarray(eps_abs, lo), 1e-4)
    eps_rel_lo = jnp.maximum(jnp.asarray(eps_rel, lo), 1e-3)
    # the f32 dual residual plateaus well above the primal one; require
    # only a coarse dual level before handing off
    eps_rel_lo_dua = jnp.maximum(
        jnp.asarray(eps_rel if eps_rel_dua is None else eps_rel_dua, lo),
        1e-2)
    if segment_lo is not None and int(segment_lo) <= 0:
        raise ValueError("segment_lo must be positive (None = use "
                         "`segment` for both phases)")
    seg_lo = segment if segment_lo is None else int(segment_lo)
    # donation ownership through the f32 chain: the initial st_lo is
    # fresh casts of the caller's f64 state EXCEPT two leaves that alias
    # it outright — iters (int, never cast) and, in df32 mode, the f32
    # factor L (same-dtype astype is a no-op). So the FIRST lo segment
    # may donate only when the caller donated AND the factor is not the
    # aliased df32 one; every later segment owns its input outright.
    split = isinstance(factors.A_s, SplitMatrix)
    owned_lo = donate and not split
    lo_ran = False
    q_lo = q.astype(lo)
    lo_total = lo_refs = 0
    while lo_total < max_iter:
        # constant segment size — see qp_solve_segmented on why the
        # remainder must not become a fresh static max_iter
        t_seg = time.perf_counter()
        fn_lo = _solve_lo_jit_donated if owned_lo else _solve_lo_jit
        with obs.span("qp.segment", cat="qp"):
            st_lo, _, _, _ = fn_lo(f_lo, d_lo, q_lo, st_lo, seg_lo,
                                   check_every, eps_lo, eps_rel_lo, alpha,
                                   adaptive_rho, polish_iters,
                                   eps_rel_lo_dua, stall_rel)
            owned_lo = True
            lo_ran = True
            _trace_seg("lo-seg", t_seg, st_lo)
            ran, ref = _segment_counts(st_lo)
        lo_total += ran
        lo_refs += ref
        if ran < seg_lo:
            break
    dt_hi = state.x.dtype
    rho_hi = st_lo.rho_scale.astype(dt_hi)
    # swap L out for a scalar before the cast: _cast_floats would
    # otherwise materialize a throwaway f64 copy of the (n, n) factor
    L_lo = st_lo.L
    st_hi = _cast_floats(st_lo._replace(L=jnp.zeros((), jnp.float32)),
                         dt_hi)
    if isinstance(factors.A_s, SplitMatrix):
        # the df32 tail's factor IS an f32 Cholesky of the same M at
        # the same (adapted) rho the bulk phase ended on — reuse it
        # instead of recomputing (the factorization's (n, n) transients
        # are the biggest allocations in the whole solve path)
        L_hi = L_lo
    else:
        L_hi = factorize_dispatch(factors, rho_hi)
    st_hi = st_hi._replace(L=L_hi, rho_scale=rho_hi)
    # the f64 tail is the real solver: full termination test, rho
    # adaptation on (it refactorizes in f64 when needed), early exit when
    # the warm start was already good (prox-regularized solves).
    # Ownership of st_hi: its float leaves are fresh f32->f64 casts and
    # L_hi is either the lo chain's output (df32, lo_ran) or a fresh
    # factorization — but iters passes through uncast, so when the lo
    # loop never ran it still aliases the CALLER's state (and in df32
    # L_hi aliases the caller's factor too); donate only when the chain
    # ran or the caller consented on a non-split state.
    st_hi, x, yA, yB = qp_solve_segmented(
        factors, data, q, st_hi, max_iter=tail_iter, segment=segment,
        check_every=check_every, eps_abs=eps_abs, eps_rel=eps_rel,
        alpha=alpha, adaptive_rho=adaptive_rho, polish=polish,
        polish_iters=polish_iters, polish_chunk=polish_chunk,
        eps_abs_dua=eps_abs_dua, eps_rel_dua=eps_rel_dua,
        stall_rel=stall_rel, ir_sweeps=ir_sweeps,
        donate=lo_ran or (donate and not split))
    # total iteration count across both phases, and the bulk's share
    # (host scalars, like qp_solve_segmented's)
    st_hi = st_hi._replace(iters=np.int32(lo_total + int(st_hi.iters)),
                           iters_lo=np.int32(lo_total),
                           refactors=np.int32(lo_refs
                                              + int(st_hi.refactors)))
    return st_hi, x, yA, yB


def _solve_lo_impl(f_lo, d_lo, q_lo, st_lo, max_iter, check_every, eps_abs,
                   eps_rel, alpha, adaptive_rho, polish_iters, eps_rel_dua,
                   stall_rel):
    """One polish-free f32 segment of qp_solve_mixed."""
    st_lo, _, _, _ = _solve_impl(f_lo, d_lo, q_lo, st_lo, max_iter,
                                 check_every, eps_abs, eps_rel, alpha,
                                 adaptive_rho, False, polish_iters, 0,
                                 eps_abs, eps_rel_dua, stall_rel)
    return st_lo, None, None, None


_LO_STATICS = ("max_iter", "check_every", "adaptive_rho", "polish_iters",
               "stall_rel")
_solve_lo_jit = compile_serialized(
    jax.jit(_solve_lo_impl, static_argnames=_LO_STATICS), _LO_STATICS)
# donated twin — same ownership contract as _qp_solve_jit_donated; the
# f32 chain is qp_solve_mixed's private state after the first segment
_solve_lo_jit_donated = compile_serialized(
    jax.jit(_solve_lo_impl, static_argnames=_LO_STATICS,
            donate_argnames=("st_lo",)), _LO_STATICS)


@jax.jit
def _stack_rows(rows):
    # ONE program: eager ``jnp.stack`` launches an expand_dims per row
    # before its concatenate (~0.2 ms of host each on the chip)
    return jnp.stack(rows)


def stacked_residuals(states, field="pri_rel"):
    """One device-side stack of per-chunk residual vectors ->
    (n_chunks, chunk). The chunked PH quality gates read EVERY chunk's
    residuals each iteration; transferring them one chunk at a time
    costs ceil(S/chunk) blocking D2H syncs — stacking on device first
    means the caller pays exactly ONE host transfer
    (``np.asarray(stacked_residuals(...))``) per PH iteration. Sharded
    chunk states all carry the same mesh placement (colocate passes
    through); the stack compiles to a sharded (n_chunks, chunk) array
    and the host read gathers it in one transfer.

    ``field`` may be a TUPLE of field names: the same ONE stack program
    and ONE transfer then carry every named row, field-major, as
    (len(field) * n_chunks, chunk) — the host reshapes for free, where
    a device reshape would be a second launch. The PH gate reads
    ``EXIT_ROWS`` this way: the residuals the loop's exit test saw."""
    from ..parallel.mesh import colocate
    fields = (field,) if isinstance(field, str) else field
    return _stack_rows(tuple(colocate([getattr(s, f) for f in fields
                                       for s in states])))


# the per-row residuals of a returned state, in the order the PH
# engine's exit booking reads them (core/ph._book_exits): ``pri_rel``
# first, so that row block IS the recovery gate's matrix
EXIT_ROWS = ("pri_rel", "pri_res", "dua_res", "dua_rel")


@jax.jit
def _pack_exit(counts, rows):
    return jnp.concatenate(
        [jnp.stack(counts).astype(rows[0].dtype), *rows])


def packed_exit(state):
    """A returned state's three scalar counts (``iters``, ``iters_lo``,
    ``refactors``: exact in any float dtype at the budgets a solve can
    have) and its ``EXIT_ROWS``, as ONE (3 + 4 S,) device vector: one
    launch, so that the un-chunked PH body, which has no gate to read
    the rows at, reads counts and rows in ONE transfer where the counts
    alone were three."""
    return _pack_exit((state.iters, state.iters_lo, state.refactors),
                      tuple(getattr(state, f) for f in EXIT_ROWS))


def _unscaled_residuals(A_s, P_s, g, D, E, Eb, csx, q_s, x, yA, yB, zA, zB):
    """UNSCALED residuals (OSQP's default termination convention): the
    scaled ones can be orders of magnitude smaller than problem-unit
    errors, which would poison the dual-objective bounds."""
    Ax = _Ax(A_s, x)
    Aty = _ATy(A_s, yA)
    Einv = 1.0 / E
    Ebinv = 1.0 / Eb
    Dinv_c = 1.0 / (D * csx)
    pri = jnp.maximum(
        jnp.max(jnp.abs(Einv * (Ax - zA)), axis=1),
        jnp.max(jnp.abs(D * x - Ebinv * zB), axis=1))
    dua = jnp.max(jnp.abs(Dinv_c * (P_s * x + q_s + Aty + g * yB)), axis=1)
    pri_sc = jnp.maximum(jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(Einv * Ax), axis=1),
                    jnp.max(jnp.abs(Einv * zA), axis=1)),
        jnp.maximum(jnp.max(jnp.abs(D * x), axis=1),
                    jnp.max(jnp.abs(Ebinv * zB), axis=1))), 1e-6)
    dua_sc = jnp.maximum(jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(Dinv_c * P_s * x), axis=1),
                    jnp.max(jnp.abs(Dinv_c * q_s), axis=1)),
        jnp.maximum(jnp.max(jnp.abs(Dinv_c * Aty), axis=1),
                    jnp.max(jnp.abs(Dinv_c * g * yB), axis=1))), 1e-6)
    return pri, dua, pri_sc, dua_sc


def _polish_select(A_s, P_s, g, D, E, Eb, cs, csx, sigma, data, q, q_s,
                   l_s, u_s, lb_s, ub_s, x, yA, yB, zA, zB,
                   pri, dua, pri_sc, dua_sc, polish_iters, shared,
                   eps_abs=1e-6, eps_rel=1e-6):
    """Active-set polish (OSQP sec 5.2, batched) + dual-candidate
    selection. Three candidates are produced:

      1. proximal AL on the slack-detected active set (exact for
         non-degenerate scenarios),
      2. the same after dropping rows whose round-1 dual has the wrong
         sign (fixes weakly-active misdetection),
      3. a sign-projected AL (per-iteration projection of each active dual
         onto its valid orthant) — never catastrophic under degeneracy,
         merely a little loose.

    The returned x/pri/dua are the best-KKT point among {ADMM, 1, 2}; the
    returned duals are the per-scenario argmax of the certified dual
    objective over {ADMM, 1, 2, 3} (any dual vector yields a valid bound,
    so the argmax is valid)."""
    dt = A_s.dtype
    rho_big = jnp.asarray(1e5, dt)
    S = x.shape[0]
    A_b = A_s if A_s.ndim == 3 else jnp.broadcast_to(A_s, (S,) + A_s.shape)
    Pdiag_b = P_s if P_s.ndim == 2 else jnp.broadcast_to(P_s, (S,) + P_s.shape)

    def residuals(x_, yA_, yB_, zA_, zB_):
        return _unscaled_residuals(A_s, P_s, g, D, E, Eb, csx, q_s,
                                   x_, yA_, yB_, zA_, zB_)

    # active-set detection tolerance adapts to the achieved primal
    # accuracy: with pri_rel at the tolerance floor, a fixed 1e-5 cutoff
    # misclassifies marginal rows and every polish candidate inherits the
    # bad set
    act_tol = jnp.maximum(1e-5, 10.0 * (pri / pri_sc))[:, None]

    def act(lo, hi, zv):
        a_l = jnp.isfinite(lo) & (zv - lo <= act_tol * (1.0 + jnp.abs(lo)))
        a_u = jnp.isfinite(hi) & (hi - zv <= act_tol * (1.0 + jnp.abs(hi)))
        b = jnp.where(a_u, jnp.where(jnp.isfinite(hi), hi, 0.0),
                      jnp.where(a_l, jnp.where(jnp.isfinite(lo), lo, 0.0),
                                0.0))
        return a_l | a_u, b

    # how the factor-and-substitute side lowers: the library calls, or
    # on the TPU at small n the unrolled recurrences (f64_polish_form)
    form = f64_polish_form(A_s)
    factorize, solve = _polish_linalg(A_s)

    def penalty_factor(actA, actB):
        rpA = jnp.where(actA, rho_big, 0.0)
        rpB = jnp.where(actB, rho_big, 0.0)
        if form is not None:
            # trace-time count of the factorizations lowered each way
            obs.counter_add(f"kernel.f64_polish_{form}")
        with jax.named_scope("qp.polish_factor"):
            Fp = factorize(A_b, rpA, Pdiag_b + sigma + g * g * rpB)

        @jax.named_scope("qp.polish_solve")
        def solve_Mp(b):
            return solve(Fp, b)

        def apply_Mp(v):
            return Pdiag_b * v + sigma * v \
                + _ATy(A_b, rpA * _Ax(A_b, v)) + g * g * rpB * v

        return rpA, rpB, solve_Mp, apply_Mp

    def polish_round(actA, bA, actB, bB, x0):
        """Proximal augmented-Lagrangian solve on the guessed active set.
        The per-scenario penalty factor is always batched (active sets
        differ per scenario): M_p = P + sigma I + A'diag(rpA)A +
        diag(g^2 rpB). Each inner solve gets two rounds of iterative
        refinement (the penalty system's conditioning is ~rho_big/sigma;
        the Cholesky solve alone leaves O(100) stationarity error at
        problem scale), and sigma*x_prev in the rhs cancels the
        regularization bias at the fixed point. Duals start from ZERO:
        stalled ADMM duals carry huge drift components along degenerate
        dual rays."""
        rpA, rpB, solve_Mp, apply_Mp = penalty_factor(actA, actB)

        def al_step(carry, _):
            x_prev, yA_p, yB_p = carry
            rhs = sigma * x_prev - q_s + _ATy(A_b, rpA * bA - yA_p) \
                + g * (rpB * bB - yB_p)
            x_p = solve_Mp(rhs)
            x_p = x_p + solve_Mp(rhs - apply_Mp(x_p))
            x_p = x_p + solve_Mp(rhs - apply_Mp(x_p))
            yA_p = yA_p + rpA * (_Ax(A_b, x_p) - bA)
            yB_p = yB_p + rpB * (g * x_p - bB)
            return (x_p, yA_p, yB_p), None

        (x_p, yA_p, yB_p), _ = jax.lax.scan(
            al_step, (x0, jnp.zeros_like(yA), jnp.zeros_like(yB)),
            None, length=polish_iters)
        return x_p, yA_p, yB_p

    def sign_projected_round(alA, auA, eqA, bA, alB, auB, eqB, bB, x0,
                             iters):
        """AL with per-iteration dual SIGN PROJECTION (upper-active duals
        >= 0, lower-active <= 0, equalities free): wrong-sign junk along
        degenerate dual rays cannot persist, at the cost of slower
        convergence. Used as a safe dual CANDIDATE."""
        rpA, rpB, solve_Mp, apply_Mp = penalty_factor(alA | auA, alB | auB)

        def clampy(y, al, au, eq):
            y = jnp.where(au & ~eq, jnp.maximum(y, 0.0), y)
            y = jnp.where(al & ~eq, jnp.minimum(y, 0.0), y)
            return jnp.where(al | au, y, 0.0)

        def step_(carry, _):
            x_prev, yA_p, yB_p = carry
            rhs = sigma * x_prev - q_s + _ATy(A_b, rpA * bA - yA_p) \
                + g * (rpB * bB - yB_p)
            x_p = solve_Mp(rhs)
            x_p = x_p + solve_Mp(rhs - apply_Mp(x_p))
            yA_p = clampy(yA_p + rpA * (_Ax(A_b, x_p) - bA), alA, auA, eqA)
            yB_p = clampy(yB_p + rpB * (g * x_p - bB), alB, auB, eqB)
            return (x_p, yA_p, yB_p), None

        (x_p, yA_p, yB_p), _ = jax.lax.scan(
            step_, (x0, jnp.zeros_like(yA), jnp.zeros_like(yB)),
            None, length=iters)
        return x_p, yA_p, yB_p

    def accept(x, yA, yB, pri, dua, pri_sc, dua_sc, x_p, yA_p, yB_p):
        zA_p = jnp.clip(_Ax(A_b, x_p), l_s, u_s)
        zB_p = jnp.clip(g * x_p, lb_s, ub_s)
        pri_p, dua_p, pri_sc_p, dua_sc_p = residuals(x_p, yA_p, yB_p,
                                                     zA_p, zB_p)
        score = jnp.maximum(pri / pri_sc, dua / dua_sc)
        score_p = jnp.maximum(pri_p / pri_sc_p, dua_p / dua_sc_p)
        # a candidate may trade primal for dual accuracy on the max-score
        # ONLY while staying inside the requested primal tolerance band —
        # PH/incumbent consumers read x for primal feasibility, and a
        # polish that "improves" a converged point to 1e-3 violation
        # breaks them (duals still improve via the separate dual-argmax)
        band = jnp.maximum(pri, eps_abs + eps_rel * pri_sc)
        ok = ((score_p < score) & (pri_p <= band))[:, None]
        return (jnp.where(ok, x_p, x), jnp.where(ok, yA_p, yA),
                jnp.where(ok, yB_p, yB),
                jnp.where(ok[:, 0], pri_p, pri),
                jnp.where(ok[:, 0], dua_p, dua),
                jnp.where(ok[:, 0], pri_sc_p, pri_sc),
                jnp.where(ok[:, 0], dua_sc_p, dua_sc))

    # round 1: active set from the ADMM slacks
    actA, bA = act(l_s, u_s, zA)
    actB, bB = act(lb_s, ub_s, zB)
    x_p, yA_p, yB_p = polish_round(actA, bA, actB, bB, x)
    cand1 = (yA_p, yB_p)
    x, yA, yB, pri, dua, pri_sc, dua_sc = accept(
        x, yA, yB, pri, dua, pri_sc, dua_sc, x_p, yA_p, yB_p)

    # round 2: re-detect at the polished point and drop rows whose
    # polished dual has the WRONG SIGN (weakly-active/degenerate rows
    # wrongly pinned in round 1); equalities are exempt
    def refilter(lo, hi, zv, yv):
        a, b = act(lo, hi, zv)
        eq = jnp.isfinite(hi - lo) & (jnp.abs(hi - lo)
                                      <= 1e-9 * (1.0 + jnp.abs(hi)))
        at_u = a & (b == jnp.where(jnp.isfinite(hi), hi, 0.0)) \
            & (zv >= hi - act_tol * (1.0 + jnp.abs(hi)))
        wrong = jnp.where(at_u, yv < 0.0, yv > 0.0) & ~eq
        return a & ~wrong, b

    zA_p = jnp.clip(_Ax(A_b, x_p), l_s, u_s)
    zB_p = jnp.clip(g * x_p, lb_s, ub_s)
    actA2, bA2 = refilter(l_s, u_s, zA_p, yA_p)
    actB2, bB2 = refilter(lb_s, ub_s, zB_p, yB_p)
    x_p2, yA_p2, yB_p2 = polish_round(actA2, bA2, actB2, bB2, x_p)
    cand2 = (yA_p2, yB_p2)
    x, yA, yB, pri, dua, pri_sc, dua_sc = accept(
        x, yA, yB, pri, dua, pri_sc, dua_sc, x_p2, yA_p2, yB_p2)

    # round 3: sign-projected candidate
    def act2(lo, hi, zv):
        a_l = jnp.isfinite(lo) & (zv - lo <= act_tol * (1.0 + jnp.abs(lo)))
        a_u = jnp.isfinite(hi) & (hi - zv <= act_tol * (1.0 + jnp.abs(hi)))
        return a_l, a_u, a_l & a_u

    alA, auA, eqA = act2(l_s, u_s, zA)
    alB, auB, eqB = act2(lb_s, ub_s, zB)
    _, yA_p3, yB_p3 = sign_projected_round(
        alA, auA, eqA, bA, alB, auB, eqB, bB, x, 3 * polish_iters)
    cand3 = (yA_p3, yB_p3)

    def unscale_y(yA_, yB_):
        return (E / csx) * yA_, (Eb / csx) * yB_

    x_un = D * x
    yA_un, yB_un = unscale_y(yA, yB)
    # the certified-bound consumer wants the dual pair with the BEST dual
    # objective — evaluate every candidate and keep the winner. NaN
    # candidates (a degenerate active set can break the penalty Cholesky)
    # must never poison best_val, so it only updates where strictly better.
    best_val = qp_dual_objective(data, q, 0.0, yA_un, yB_un, x_witness=x_un)
    best_val = jnp.where(jnp.isnan(best_val), -jnp.inf, best_val)
    for yA_c, yB_c in (cand1, cand2, cand3):
        yA_cu, yB_cu = unscale_y(yA_c, yB_c)
        val = qp_dual_objective(data, q, 0.0, yA_cu, yB_cu, x_witness=x_un)
        better = (val > best_val)[:, None]
        yA_un = jnp.where(better, yA_cu, yA_un)
        yB_un = jnp.where(better, yB_cu, yB_un)
        best_val = jnp.where(better[:, 0], val, best_val)
    return x_un, yA_un, yB_un, pri, dua, pri_sc


def qp_objective(data: QPData, q, c0, x):
    """½x'Px + q'x + c0 per scenario (unscaled)."""
    return 0.5 * jnp.sum(data.P_diag * x * x, axis=-1) \
        + jnp.sum(q * x, axis=-1) + c0


@jax.jit
def qp_state_duals(factors: QPFactors, state: QPState):
    """UNSCALED (yA, yB) dual iterates straight from a warm solver
    state — the dual-extraction entry for bound consumers that want
    the current iterates WITHOUT another solve call (e.g. a bounder
    publishing between warm-started passes). The unscaling is the one
    _solve_impl applies to its return values; any dual vector yields a
    valid bound via qp_dual_objective, so mid-trajectory iterates are
    legitimate (if loose) bound sources."""
    cs = factors.cost_scale
    shared = factors.A_s.ndim == 2
    csx = cs if shared else cs[:, None]
    return (factors.E / csx) * state.yA, (factors.Eb / csx) * state.yB


@jax.jit
def qp_repair_duals(l, u, lb, ub, yA, yB):
    """Project unscaled duals onto the dual-feasible cone: zero every
    component pushing on an infinite bound (always sign-infeasible
    there). This is a *choice of a different valid dual vector*, not an
    approximation — the repaired pair certifies a bound wherever the
    raw pair would certify −inf. Run it on device BEFORE pulling duals
    to host for certification (utils/certify): the repaired arrays
    compress losslessly to f32 for the transfer (quantized duals are
    still exact duals)."""
    return (_sanitize_row_duals(l, u, yA),
            _sanitize_row_duals(lb, ub, yB))


def _boxmin(P, r, lb, ub):
    """Coordinate-wise min of ½P x² + r x over [lb, ub] (P >= 0 diagonal).
    Returns -inf where a linear piece descends toward an infinite bound."""
    x_unc = jnp.where(P > 0, -r / jnp.where(P > 0, P, 1.0), 0.0)
    x_star = jnp.clip(x_unc, lb, ub)
    quad_val = 0.5 * P * x_star * x_star + r * x_star
    lin_lo = jnp.where(r > 0, jnp.where(jnp.isneginf(lb), -jnp.inf, r * lb), 0.0)
    lin_hi = jnp.where(r < 0, jnp.where(jnp.isposinf(ub), -jnp.inf, r * ub), 0.0)
    return jnp.where(P > 0, quad_val, lin_lo + lin_hi)


def _sanitize_row_duals(lo, hi, y):
    """Zero dual components that push on an infinite bound (always
    sign-infeasible there). Any dual vector gives a valid bound, so this
    only trades a guaranteed -inf for a finite, witness-penalized term."""
    y = jnp.where(jnp.isposinf(hi) & (y > 0), 0.0, y)
    return jnp.where(jnp.isneginf(lo) & (y < 0), 0.0, y)


def _sup_rows(l, u, y, inf_tol=1e-9):
    """sup over the row box of y'z: u'y+ − l'y−, +inf when a positive dual
    pushes on an infinite bound. Shared by qp_dual_objective/benders_cut."""
    yp = jnp.maximum(y, 0.0)
    ym = jnp.maximum(-y, 0.0)
    u_fin = jnp.where(jnp.isfinite(u), u, 0.0)
    l_fin = jnp.where(jnp.isfinite(l), l, 0.0)
    return jnp.sum(u_fin * yp - l_fin * ym, axis=-1) \
        + jnp.sum(jnp.where((jnp.isposinf(u) & (yp > inf_tol))
                            | (jnp.isneginf(l) & (ym > inf_tol)), jnp.inf, 0.0),
                  axis=-1)


def _column_bound(P, q, r, y_b, lb, ub, x_witness, r_rel_tol):
    """Per-column contribution to the dual bound: best of (a) keep the
    bound-row dual, (b) drop it; plus the witness fallback when both are
    -inf. Shared by qp_dual_objective/benders_cut (see the docstrings
    there for the derivation)."""
    tol = r_rel_tol * jnp.maximum(1.0, jnp.abs(q))
    r_a = jnp.where(jnp.abs(r) <= tol, 0.0, r)
    ybp = jnp.maximum(y_b, 0.0)
    ybm = jnp.maximum(-y_b, 0.0)
    ub_fin = jnp.where(jnp.isfinite(ub), ub, 0.0)
    lb_fin = jnp.where(jnp.isfinite(lb), lb, 0.0)
    sup_b = ub_fin * ybp - lb_fin * ybm \
        + jnp.where((jnp.isposinf(ub) & (ybp > 1e-9))
                    | (jnp.isneginf(lb) & (ybm > 1e-9)), jnp.inf, 0.0)
    contrib_a = _boxmin(P, r_a, lb, ub) - sup_b
    contrib_b = _boxmin(P, r - y_b, lb, ub)
    best = jnp.maximum(contrib_a, contrib_b)
    if x_witness is not None:
        def clamped(rv):
            r_fix = jnp.where(jnp.isposinf(ub) & (rv < 0), 0.0, rv)
            r_fix = jnp.where(jnp.isneginf(lb) & (r_fix > 0), 0.0, r_fix)
            penalty = jnp.abs(rv - r_fix) * (2.0 * jnp.abs(x_witness) + 1.0)
            return _boxmin(P, r_fix, lb, ub) - penalty

        # two fallbacks, mirroring (a) and (b): keeping y_b is useless when
        # sup_b itself is +inf (a wrong-sign dual pushing on an infinite
        # bound), so the dropped-y_b clamp must exist independently
        fallback = jnp.maximum(clamped(r_a) - sup_b, clamped(r - y_b))
        best = jnp.maximum(best, jnp.where(jnp.isneginf(best), fallback, best))
    return best


def qp_dual_objective(data: QPData, q, c0, yA, yB, x_witness=None,
                      r_rel_tol=1e-6):
    """Per-scenario LOWER bound on min ½x'Px + q'x + c0 s.t. l <= Ax <= u,
    lb <= x <= ub, from (approximately) dual-feasible (yA, yB).

    An inexact *primal* solution over-estimates the subproblem minimum, so
    bounds built from primal objectives (what the reference gets for free
    from its exact MIP solver, ref. phbase.py:314 Ebound) would be invalid
    here. Instead evaluate a Lagrangian dual at y. *Any* choice of
    bound duals yB yields a valid bound when x is also kept in its box, so
    per coordinate we take the better of:

      (a) keep yB_j:  boxmin(½Px² + r_j x) - (ub_j yB_j+ - lb_j yB_j-)
          with r = q + AᵀyA + yB the full dual residual, entries below
          r_rel_tol*max(1,|q_j|) zeroed (epsilon-valid convention), and
      (b) drop yB_j:  boxmin(½Px² + (r_j - yB_j) x)   [pure reduced cost]

    plus, where both are -inf (an infinite-direction residual above
    tolerance), a witness fallback: clamp the offending residual part and
    pay |clamped|*(2|x_witness_j| + 1) — valid whenever the true optimum
    satisfies |x*_j| <= 2|x_witness_j| + 1.

    The total is  -sup_c + sum_j best_j + c0  with
    sup_c = u'yA+ - l'yA- over the constraint rows.

    Wrong-sign dual components at INFINITE bounds (drift artifacts of a
    degenerate solve) would make the sup terms +inf and the bound -inf;
    since any dual vector yields a valid bound, those components are
    zeroed first — the error moves into r where the per-column machinery
    absorbs it.
    """
    yA = _sanitize_row_duals(data.l, data.u, yA)
    yB = _sanitize_row_duals(data.lb, data.ub, yB)
    r = q + _ATy(data.A, yA) + yB
    best = _column_bound(data.P_diag, q, r, yB, data.lb, data.ub,
                         x_witness, r_rel_tol)
    sup_c = _sup_rows(data.l, data.u, yA)
    return jnp.sum(best, axis=-1) - sup_c + c0


def benders_cut(data: QPData, q, c0, yA, yB, param_mask, b0,
                r_rel_tol=1e-6):
    """Affine minorant of the *value function* V(b) =
    min ½x'Px + q'x + c0 s.t. l <= Ax <= u, box bounds, with the columns in
    `param_mask` fixed at b (their boxes carry lb=ub=b in `data`).

    Returns (const (S,), g (S, n) zero outside param_mask) such that
    V(b) >= const + g·b[param] for all b, up to the r_rel_tol
    residual-zeroing convention — the L-shaped optimality cut (the
    reference gets these from exact solver duals via
    pyomo.contrib.benders, ref. mpisppy/opt/lshaped.py:639; here they come
    from ADMM dual vectors, so inexact subproblem solves still yield
    tolerance-valid cuts).

    Derivation: dropping the bound dual yB on the parameterized columns,
    the dual function's dependence on b is
      sum_{j in param} [ (q + AᵀyA)_j b_j + ½P_j b_j² ],
    and the quadratic is linearized at b0 (valid: a convex function's
    tangent is a global minorant). Non-parameter columns contribute the
    same per-coordinate best-of-two boxmin terms as qp_dual_objective.
    No x_witness fallback here: its validity box is tied to the solve at
    b0, but a cut must minorize V at EVERY b — a -inf free column simply
    yields an inactive (-inf) cut instead."""
    pm = param_mask  # (n,) bool
    P = data.P_diag

    yA = _sanitize_row_duals(data.l, data.u, yA)
    yB = _sanitize_row_duals(data.lb, data.ub, yB)
    r = q + _ATy(data.A, yA) + yB
    r_c = r - yB     # residual without the bound dual

    # parameterized columns: affine in b, quadratic linearized at b0
    g = jnp.where(pm, r_c + P * b0, 0.0)
    const_param = jnp.sum(jnp.where(pm, -0.5 * P * b0 * b0, 0.0), axis=-1)

    best = _column_bound(P, q, r, yB, data.lb, data.ub, None, r_rel_tol)
    const_free = jnp.sum(jnp.where(pm, 0.0, best), axis=-1)
    sup_c = _sup_rows(data.l, data.u, yA)
    return const_param + const_free - sup_c + c0, g
