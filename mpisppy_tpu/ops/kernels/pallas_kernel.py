"""Pallas TPU backend: the whole ADMM iteration block — x-update
matmul, relaxed z-projections, dual updates, and the stacked residual
reduction — as ONE kernel whose operands load into VMEM once and stay
there for every iteration, instead of the XLA program's one-HBM-round-
trip-per-op dataflow.

An OPT-IN experiment, not the production backend (the XLA fused-scan
``reference`` backend is, at every width): the block's premise is that
the whole working set — the (m, n) A, the (n, n) solve operator and a
tile of iterates — is VMEM-resident, and Mosaic's scoped-VMEM limit on
the v5e is 16 MiB. Under the engine's HIGHEST-precision f32 matmuls
that admits dense shapes up to roughly n = 384 / m = 768 at 64 rows
(``vmem_bytes_estimate``; compiled for a described v5e in
tests/test_chip_compile.py). At reference-UC width the solve
operator alone is n² x 4 B = 682 MB, so the premise cannot hold there
at all; a streaming (HBM-tiled) spelling would be a different kernel.
``pallas_scope_reason`` refuses out-of-scope solves up front — by
dtype, operator form and the explicit bytes-vs-VMEM estimate — instead
of letting the compiler run out. The kernel is compiled, never
interpreted, wherever the program runs it; ``interpret=True`` is
something a test passes in (tier-1 covers the block's MATH on the CPU
that way; the parity test pins it against the reference backend).

Deliberate scope:

 - f32 operands only: Mosaic has no f64 type (a "native" f64 engine's
   M⁻¹ cannot be served);
 - SHARED-structure dense operands only (one (m, n) A, one solve
   operator) — the representation the chunked PH loop requires anyway;
 - the solve operator is an EXPLICIT inverse: the kernel layer's f32
   L⁻¹ pair (two matmuls — qp_solver.LInv), or, under a test's
   interpret mode, the f64 M⁻¹ the shared factorization carries (one
   matmul). Triangular back-substitution has no efficient Pallas
   spelling, which is the same latency argument behind roofline
   headroom item 1;
 - rho is FIXED for the duration of one block (the OSQP adaptation
   rule needs a refactorization the kernel cannot express) — the
   driver folds ``state.rho_scale`` into the row patterns and the
   reference path handles adaptation between blocks;
 - SCENARIO-AXIS GRID TILING: per-scenario operands (q/l/u/lb/ub and
   the five iterate blocks) split into ``scen_tile``-row blocks over a
   1-D grid while the shared operands (A, the solve operator,
   scalings) broadcast to every program instance. Scenario rows are
   independent through the entire iteration block (A/F are shared;
   projections, dual updates, and the residual maxima are row-local),
   so tiling is exact — the parity test pins tiled == untiled
   bit-for-bit under interpret mode. ``scen_tile=None`` picks the
   largest divisor of S at or under SCEN_TILE_TARGET (S itself when S
   is small); ``scen_tile=0`` disables tiling (one program instance
   owns the whole chunk).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..qp_solver import LInv, _raw_factor, _scaled_problem

from jax.experimental import pallas as pl

__all__ = ["pallas_scope_reason", "pallas_supported", "fused_admm_block",
           "pick_scen_tile", "vmem_bytes_estimate", "SCEN_TILE_TARGET",
           "VMEM_LIMIT_BYTES"]

# Mosaic's scoped-VMEM limit for one kernel on the v5e — the figure the
# compiler itself quotes when it refuses ("limit 16.00M"), read from
# AOT compiles for a described v5e (tests/test_chip_compile.py)
VMEM_LIMIT_BYTES = 16 * 2 ** 20

# target rows per grid tile: small enough that a tile's iterate
# working set stays VMEM-resident beside the shared operator at
# compacted-block sizes, large enough to keep the MXU matmuls square-
# ish. Power of two on purpose (the chunked loop's row counts are).
SCEN_TILE_TARGET = 128


def pick_scen_tile(S: int, target: int = SCEN_TILE_TARGET) -> int:
    """Largest divisor of S that is <= target (pallas grids need exact
    tiling — padding the scenario axis would fabricate rows whose
    residual maxima pollute the fused reduction). S itself when S is
    already at or under the target; 1-row tiles only for prime S."""
    S = int(S)
    if S <= target:
        return S
    for tile in range(target, 1, -1):
        if S % tile == 0:
            return tile
    return 1    # prime S: row tiles


def vmem_bytes_estimate(rows, n, m, itemsize=4, tiled=False):
    """Scoped-VMEM bytes one program instance of the block needs, as
    an upper envelope of what Mosaic allocates for a described v5e
    under the engine's precision policy (HIGHEST f32 matmuls — the
    multi-pass operand splitting is what makes the shared operands
    cost ~4.5 copies): 4.5 x the shared operands (A, the solve
    operator, the scaling vectors) + 1.2 x ``rows`` rows of the ten
    per-scenario inputs and seven outputs (2.2 x on a grid, whose
    blocked operands are double-buffered) + 4 MiB. Fitted to the
    allocation sizes the compiler reports across n = 256..768,
    8..512 rows, tiled and untiled (it over-estimates each measured
    point by 0-18%, so it refuses whenever the compiler would;
    tests/test_chip_compile.py holds both sides of the boundary)."""
    shared = (m * n + n * n + 6 * n + 2 * m) * itemsize
    per_row = ((6 * n + 4 * m) + (3 * n + 2 * m + 2)) * itemsize
    return int(4.5 * shared + (2.2 if tiled else 1.2) * rows * per_row
               + 4 * 2 ** 20)


def pallas_scope_reason(factors, state, scen_tile=None):
    """Why THIS solve's operands are outside the TPU kernel's scope, or
    None when it can serve them: shared dense f32 A, an explicit f32
    L⁻¹ solve operator (qp_solver.LInv), and a working set under the
    scoped-VMEM limit."""
    A_s = factors.A_s
    if getattr(A_s, "ndim", 0) != 2 or not isinstance(A_s, jax.Array):
        return ("A is not one shared dense (m, n) array (batched, split "
                "or packed operands are out of scope)")
    L = state.L
    if not isinstance(L, LInv):
        if getattr(L, "ndim", 0) == 2 and L.dtype == jnp.float64:
            return ("the solve operator is an f64 M⁻¹ and Mosaic has no "
                    "f64 type")
        return ("the solve operator is not an explicit inverse (the "
                "block cannot back-substitute a Cholesky factor)")
    for name, arr in (("A", A_s), ("L⁻¹", L.inv), ("x", state.x)):
        if arr.dtype != jnp.float32:
            return (f"operand {name} is {arr.dtype}; the TPU kernel "
                    "serves f32 only (Mosaic has no f64 type)")
    S = state.x.shape[0]
    m, n = A_s.shape
    tile = pick_scen_tile(S) if scen_tile is None else int(scen_tile)
    tiled = 0 < tile < S
    need = vmem_bytes_estimate(tile if tiled else S, n, m, tiled=tiled)
    if need > VMEM_LIMIT_BYTES:
        return (f"the block's working set needs ~{need / 2**20:.1f} MiB "
                f"of VMEM at n={n}, m={m}, {tile if tiled else S} rows "
                f"per program (limit {VMEM_LIMIT_BYTES / 2**20:.0f} MiB)")
    return None


def pallas_supported(factors, state, scen_tile=None) -> bool:
    return pallas_scope_reason(factors, state, scen_tile) is None


def _admm_block_kernel(A_ref, F_ref, Ps_ref, g_ref, q_ref,
                       l_ref, u_ref, lb_ref, ub_ref, rA_ref, rB_ref,
                       Einv_ref, Ebinv_ref, Dinvc_ref, D_ref,
                       x_ref, yA_ref, yB_ref, zA_ref, zB_ref,
                       ox_ref, oyA_ref, oyB_ref, ozA_ref, ozB_ref,
                       opri_ref, odua_ref, *, n_steps, sigma, alpha,
                       l_inv_pair):
    """The fused iteration block. Mirrors ops/qp_solver._solve_impl's
    ``one()`` update and ``_unscaled_residuals`` EXACTLY — the parity
    test compares against those, so any drift here is a test failure,
    not a silent divergence. ``sigma``/``alpha`` are compile-time
    constants (closing traced values over a pallas kernel body is not
    expressible; sigma is constant per factorization anyway)."""
    A = A_ref[:]
    F = F_ref[:]
    Ps, g, q_s = Ps_ref[:], g_ref[:], q_ref[:]
    l_s, u_s, lb_s, ub_s = l_ref[:], u_ref[:], lb_ref[:], ub_ref[:]
    rA, rB = rA_ref[:], rB_ref[:]

    def m_solve(rhs):
        if l_inv_pair:
            # x = L⁻ᵀ (L⁻¹ rhs): two MXU matmuls of the factor's bytes
            return (rhs @ F.T) @ F
        return rhs @ F          # explicit symmetric M⁻¹: one matmul

    def one(i, c):
        x, yA, yB, zA, zB = c
        rhs = sigma * x - q_s + (rA * zA - yA) @ A + g * (rB * zB - yB)
        x_t = m_solve(rhs)
        x_new = alpha * x_t + (1 - alpha) * x
        zA_t = x_t @ A.T
        zA_mix = alpha * zA_t + (1 - alpha) * zA
        zA_new = jnp.clip(zA_mix + yA / rA, l_s, u_s)
        yA_new = yA + rA * (zA_mix - zA_new)
        zB_t = g * x_t
        zB_mix = alpha * zB_t + (1 - alpha) * zB
        zB_new = jnp.clip(zB_mix + yB / rB, lb_s, ub_s)
        yB_new = yB + rB * (zB_mix - zB_new)
        return x_new, yA_new, yB_new, zA_new, zB_new

    x, yA, yB, zA, zB = jax.lax.fori_loop(
        0, n_steps, one,
        (x_ref[:], yA_ref[:], yB_ref[:], zA_ref[:], zB_ref[:]))
    ox_ref[:] = x
    oyA_ref[:] = yA
    oyB_ref[:] = yB
    ozA_ref[:] = zA
    ozB_ref[:] = zB
    # stacked residual reduction, fused: the UNSCALED primal/dual
    # maxima of _unscaled_residuals, computed while the iterates are
    # still VMEM-resident (the chunked PH gate consumes exactly these)
    Einv, Ebinv, Dinv_c, D = (Einv_ref[:], Ebinv_ref[:], Dinvc_ref[:],
                              D_ref[:])
    Ax = x @ A.T
    Aty = yA @ A
    # (rows, 1) outputs, not rank-1 (rows,): a rank-1 f32[S] array's
    # XLA layout tiles by S while a (scen_tile,) block asks Mosaic for
    # T(scen_tile) — the tiled grid fails Mosaic's layout check; the
    # 2-D column blocks (scen_tile, 1) pass it
    opri_ref[:] = jnp.maximum(
        jnp.max(jnp.abs(Einv * (Ax - zA)), axis=1, keepdims=True),
        jnp.max(jnp.abs(D * x - Ebinv * zB), axis=1, keepdims=True))
    odua_ref[:] = jnp.max(
        jnp.abs(Dinv_c * (Ps * x + q_s + Aty + g * yB)), axis=1,
        keepdims=True)


@partial(jax.jit,
         static_argnames=("sigma", "n_steps", "alpha", "interpret",
                          "l_inv_pair", "scen_tile"))
def _block_call(A, F, Ps, g, q_s, l_s, u_s, lb_s, ub_s, rA, rB,
                Einv, Ebinv, Dinv_c, D, x, yA, yB, zA, zB, sigma,
                n_steps, alpha, interpret, l_inv_pair, scen_tile=0):
    S, n = x.shape
    m = zA.shape[1]
    dt = x.dtype
    kern = partial(_admm_block_kernel, n_steps=n_steps, sigma=sigma,
                   alpha=alpha, l_inv_pair=l_inv_pair)
    out_shape = [jax.ShapeDtypeStruct((S, n), dt),   # x
                 jax.ShapeDtypeStruct((S, m), dt),   # yA
                 jax.ShapeDtypeStruct((S, n), dt),   # yB
                 jax.ShapeDtypeStruct((S, m), dt),   # zA
                 jax.ShapeDtypeStruct((S, n), dt),   # zB
                 jax.ShapeDtypeStruct((S, 1), dt),   # pri
                 jax.ShapeDtypeStruct((S, 1), dt)]   # dua
    operands = (A, F, Ps, g, q_s, l_s, u_s, lb_s, ub_s, rA, rB,
                Einv, Ebinv, Dinv_c, D, x, yA, yB, zA, zB)
    def rank1_residuals(outs):
        *iterates, pri, dua = outs
        return (*iterates, pri[:, 0], dua[:, 0])

    if not scen_tile or scen_tile >= S:
        # one program instance owns the whole chunk
        return rank1_residuals(pl.pallas_call(
            kern, out_shape=out_shape, interpret=interpret)(*operands))
    # scenario-axis grid (doc/kernels.md production tiling): shared
    # operands broadcast (index map pinned at block 0), per-scenario
    # operands and ALL outputs tile the leading axis. Scenario rows
    # are independent through the whole iteration block, so this is
    # exact — no halo, no cross-tile reduction.
    assert S % scen_tile == 0, "scen_tile must divide the chunk rows"
    grid = (S // scen_tile,)

    # block indices are spelled int32: under jax_enable_x64 (every
    # production entry point) a bare Python 0 traces as i64, which
    # Mosaic refuses in an index map
    def shared(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, _n=nd: (jnp.int32(0),) * _n)

    def scen(shape):
        nd = len(shape)
        return pl.BlockSpec(
            (scen_tile,) + shape[1:],
            lambda i, _n=nd: (i,) + (jnp.int32(0),) * (_n - 1))

    def scaling(arr):
        # factor scalings are (m,)/(n,) for shared factorizations; a
        # batched spelling (rank 2, leading S) tiles with the rows
        return scen(arr.shape) if arr.ndim == 2 else shared(arr.shape)

    in_specs = [shared(A.shape), shared(F.shape), scaling(Ps),
                scaling(g), scen(q_s.shape), scen(l_s.shape),
                scen(u_s.shape), scen(lb_s.shape), scen(ub_s.shape),
                scaling(rA), scaling(rB), scaling(Einv),
                scaling(Ebinv), scaling(Dinv_c),
                scaling(D), scen(x.shape), scen(yA.shape),
                scen(yB.shape), scen(zA.shape), scen(zB.shape)]
    out_specs = [scen((S, n)), scen((S, m)), scen((S, n)),
                 scen((S, m)), scen((S, n)), scen((S, 1)), scen((S, 1))]
    return rank1_residuals(pl.pallas_call(
        kern, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, interpret=interpret)(*operands))


def fused_admm_block(factors, data, q, state, n_steps, interpret=False,
                     sigma=None, scen_tile=None):
    """Run ``n_steps`` fused ADMM iterations on the scaled problem
    (factors, data, q) from ``state``; returns (x, yA, yB, zA, zB,
    pri, dua) — SCALED iterates (the QPState carry convention) plus the
    unscaled residual maxima. Scaling comes from the shared
    qp_solver._scaled_problem helper so this block iterates the exact
    problem _solve_impl would.

    ``sigma``: the host float of ``factors.sigma`` (a compile-time
    constant of the kernel). kernel_solve passes the plan's copy read
    once at prepare() time; the fallback below is for direct callers
    (parity tests) and pays one scalar D2H per block.

    ``scen_tile``: rows per grid tile over the scenario axis (None =
    pick_scen_tile's auto choice, 0 = untiled single program — see the
    module docstring; tiling is exact, pinned by the parity test).

    ``interpret``: False everywhere the program calls this — the kernel
    is compiled for the device it runs on. Tests pass True to cover the
    block's math on the CPU."""
    if sigma is None:
        # lint: ok[SYNC001] direct-caller fallback: kernel_solve passes the plan's host sigma (read once per factorization)
        sigma = float(factors.sigma)
    g, l_s, u_s, lb_s, ub_s, csx, q_s = _scaled_problem(factors, data, q)
    rs = state.rho_scale
    rA = factors.rho_A * rs
    rB = factors.rho_b * rs
    Einv = 1.0 / factors.E
    Ebinv = 1.0 / factors.Eb
    Dinv_c = 1.0 / (factors.D * csx)
    L = state.L
    l_inv_pair = isinstance(L, LInv)
    F = L.inv if l_inv_pair else _raw_factor(L)
    if scen_tile is None:
        scen_tile = pick_scen_tile(state.x.shape[0])
    return _block_call(factors.A_s, F, factors.P_s, g, q_s,
                       l_s, u_s, lb_s, ub_s, rA, rB, Einv, Ebinv,
                       Dinv_c, factors.D, state.x, state.yA, state.yB,
                       state.zA, state.zB, sigma=sigma,
                       n_steps=int(n_steps), alpha=1.6,
                       interpret=bool(interpret),
                       l_inv_pair=l_inv_pair, scen_tile=int(scen_tile))
