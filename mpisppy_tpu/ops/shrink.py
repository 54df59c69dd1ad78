"""Progressive problem shrinking: device-native fixing counters,
active-set compaction plans, and per-slot adaptive rho (ROADMAP item 5,
doc/extensions.md §shrinking).

Three device-paced mechanics that make late-wheel per-iteration cost
track the ACTIVE set instead of the original model:

1. ``fixer_update`` — the WW-style Fixer's test-and-fix
   (extensions/fixer.py, ref. mpisppy/extensions/fixer.py:50) as ONE
   jitted op over the sharded (S, K) hub state: per-slot
   consecutive-converged counters, bound-parking votes, and the
   accumulated fix mask/values, with a single scalar (the fixed-slot
   count) for the host to read AFTER the iteration's existing
   convergence sync — no big-array D2H per ``miditer`` (the host
   Fixer pulled xbar/xsqbar/x down every pass).

2. ``ShrinkPlan`` / ``build_plan`` + the gather/fold/expand ops —
   active-set compaction: when the fixed fraction crosses a bucketed
   threshold, the unfixed columns (and the constraint rows they touch)
   are gathered into a smaller packed system; fixed-variable
   contributions fold into per-scenario constants (``c0_fold``, rhs
   shifts) so the EXPANDED solution of the compacted system equals the
   uncompacted pinned solve to solver tolerance. Bucketed thresholds
   keep the compacted shapes few: a wheel pays at most one XLA compile
   per bucket transition, tracked through the module-level
   shape-bucket registry (fingerprinted like serve/cache buckets).

3. ``per_slot_rho_update`` — NormRhoUpdater's residual balancing
   (Boyd et al. §3.4.1) per SLOT instead of per whole vector: a jitted
   op producing the vector rho for the prox diagonal plus one packed
   (3,) stats row ([changed, prim_sum, dual_sum]) so the host pays one
   tiny D2H per update, not one per history sample.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..ckpt.bundle import config_fingerprint
from ..utils.config import parse_shrink_buckets as parse_buckets  # noqa: F401
#   (re-exported: the jax-free parser lives in utils/config so CLI/serve
#   validation never imports this jax-touching module)
from .qp_solver import QPData, ScaledView, SplitMatrix

# "never fix" threshold sentinel: must survive an int32 cast (x64-off
# environments) — 2^30 consecutive converged iterations is never
INT_NEVER = 2 ** 30


# ---------------- device fixer counters ----------------

@jax.jit
def fixer_update(conv_count, lb_count, ub_count, fixed_mask,
                 fixed_vals, xbar, xsqbar, xn, slot_lb, slot_ub,
                 tol, boundtol, nbc, lbc, ubc, imask):
    """One ``miditer`` of the WW fixer as a device op. Mirrors
    extensions/fixer.py Fixer.miditer EXACTLY (the parity test pins
    identical fix decisions): variance test per slot, parked-at-bound
    streaks, lb > ub > nb precedence, integral snap, accumulate-only
    fixing. Returns the updated counters/mask/values plus the fixed
    slot count as a device scalar — the ONE number the host reads."""
    var = jnp.max(jnp.abs(xsqbar - xbar * xbar), axis=0)
    agree = var <= tol * tol + 1e-15
    conv_count = jnp.where(agree, conv_count + 1, 0)
    at_lb = jnp.all(jnp.abs(xn - slot_lb) <= boundtol, axis=0)
    at_ub = jnp.all(jnp.abs(xn - slot_ub) <= boundtol, axis=0)
    lb_count = jnp.where(agree & at_lb, lb_count + 1, 0)
    ub_count = jnp.where(agree & at_ub, ub_count + 1, 0)
    fix_lb = lb_count >= lbc
    fix_ub = (ub_count >= ubc) & ~fix_lb
    fix_nb = (conv_count >= nbc) & ~fix_lb & ~fix_ub
    newly = (fix_lb | fix_ub | fix_nb) & ~fixed_mask[0]
    value = jnp.where(fix_lb[None, :], slot_lb,
                      jnp.where(fix_ub[None, :], slot_ub, xbar))
    value = jnp.where(imask[None, :], jnp.round(value), value)
    fixed_vals = jnp.where(newly[None, :], value, fixed_vals)
    fixed_mask = fixed_mask | newly[None, :]
    n_fixed = jnp.sum(fixed_mask[0].astype(jnp.int32))
    return conv_count, lb_count, ub_count, fixed_mask, fixed_vals, n_fixed


# ---------------- per-slot adaptive rho ----------------

@jax.jit
def per_slot_rho_update(rho, prob, xn, xbar, xbar_prev, mult, factor):
    """Residual-balancing rho update PER NONANT SLOT (the vector
    analog of extensions/norm_rho_updater.py): prim_k is the
    probability-weighted primal residual of slot k, dual_k the
    rho-scaled dual residual; slots with prim > mult*dual scale up,
    dual > mult*prim scale down. rho stays uniform across scenarios
    (the update factor is per-slot), so the single-factor prox path
    keeps working. Returns (new_rho, stats) with stats a packed (3,)
    row [changed, prim_sum, dual_sum] — one tiny D2H for the host."""
    S = xn.shape[0]
    prim = jnp.einsum("s,sk->k", prob, jnp.abs(xn - xbar))
    dual = jnp.mean(rho, axis=0) \
        * jnp.sum(jnp.abs(xbar - xbar_prev), axis=0) / S
    up = prim > mult * dual
    down = (dual > mult * prim) & ~up
    scale = jnp.where(up, factor, jnp.where(down, 1.0 / factor, 1.0))
    new_rho = rho * scale[None, :]
    changed = jnp.any(up | down).astype(rho.dtype)
    stats = jnp.stack([changed, jnp.sum(prim), jnp.sum(dual)])
    return new_rho, stats


# ---------------- active-set compaction ----------------

@dataclass
class ShrinkPlan:
    """One compacted system: device tensors + host metadata. Built by
    :func:`build_plan` at a bucket transition; the engine solves the
    compacted system and expands solutions back through
    :func:`expand_solution`."""
    bucket: float                 # the threshold fraction crossed
    fingerprint: str              # shape-bucket id (serve-style hash)
    n_full: int
    m_full: int
    n_c: int                      # kept columns
    m_c: int                      # kept rows
    n_fixed_slots: int
    free_slots: np.ndarray        # (K_c,) host slot ids kept
    fixed_slots: np.ndarray       # (K_f,) host slot ids folded out
    # device arrays
    keep_cols: jax.Array          # (n_c,) original column ids
    fixed_cols: jax.Array         # (n_f,) folded column ids
    free_slots_dev: jax.Array     # (K_c,)
    fixed_slots_dev: jax.Array = None   # (K_f,) for the dual fold
    idx_c: jax.Array = None       # (K_c,) free-slot positions in keep_cols
    fixed_colvals: jax.Array = None     # (S, n_f) folded values
    data_c: QPData = None         # compacted problem data
    c_c: jax.Array = None         # (S, n_c) compacted linear cost
    c0_fold: jax.Array = None     # (S,) c0 + fixed-var cost contributions
    rhs_shift: jax.Array = None   # (S, m_c) folded rhs shift (l/u moved
    #                               by -shift; transplant re-centers row
    #                               slacks through it)
    keep_rows_np: np.ndarray = None   # (m_c,) host row ids kept
    keep_cols_np: np.ndarray = None   # (n_c,) host column ids kept
    fac_base: object = None       # df32: first-mode QPFactors — pinned
    #                               here because data_c.A becomes the
    #                               ScaledView after that build, so
    #                               later rebuilds need this base's
    #                               equilibration (core/ph
    #                               _shrink_get_factors)
    meta: dict = field(default_factory=dict)


@jax.jit
def _fold_compact(A, l, u, lb, ub, P_diag, c, c0, keep_rows, keep_cols,
                  fixed_cols, fv):
    """Device-side compaction of one system: gather the kept
    rows/columns and fold the fixed columns' contributions into the
    rhs (l/u shifts) and the objective constant. Handles the shared
    (m, n) layout AND the batched per-scenario (S, m, n) layout (the
    branch is on static rank, one trace each). Exact arithmetic — the
    expanded solution is the pinned full solve to solver tolerance
    (the equivalence suite pins this)."""
    if A.ndim == 2:
        A_keep = A[keep_rows]
        A_c = A_keep[:, keep_cols]
        A_f = A_keep[:, fixed_cols]
        shift = fv @ A_f.T                     # (S, m_c)
    else:
        A_keep = A[:, keep_rows]
        A_c = A_keep[..., keep_cols]
        A_f = A_keep[..., fixed_cols]          # (S, m_c, n_f)
        shift = jnp.einsum("smf,sf->sm", A_f, fv)
    l_c, u_c, lb_c, ub_c, P_c, c_c, c0_fold = _fold_vectors(
        l, u, lb, ub, P_diag, c, c0, keep_rows, keep_cols, fixed_cols,
        fv, shift)
    return A_c, l_c, u_c, lb_c, ub_c, P_c, c_c, c0_fold, shift


@jax.jit
def _fold_vectors(l, u, lb, ub, P_diag, c, c0, keep_rows, keep_cols,
                  fixed_cols, fv, shift):
    """The vector half of :func:`_fold_compact` with the rhs shift
    supplied externally — the df32 paths compute the shift from the
    split/scaled fixed-column block (see ``_split_fixed_shift``) and
    share these folds with the dense path bit-for-bit."""
    l_c = l[:, keep_rows] - shift
    u_c = u[:, keep_rows] - shift
    lb_c = lb[:, keep_cols]
    ub_c = ub[:, keep_cols]
    P_c = P_diag[..., keep_cols]
    c_c = c[:, keep_cols]
    c0_fold = c0 + jnp.sum(c[:, fixed_cols] * fv, axis=1) \
        + 0.5 * jnp.sum(P_diag[..., fixed_cols] * fv * fv, axis=-1)
    return l_c, u_c, lb_c, ub_c, P_c, c_c, c0_fold


@jax.jit
def _split_fixed_shift(hi_f, lo_f, inv_e, inv_d_f, fv):
    """rhs shift of the folded columns from a df32 fixed-column block:
    the f64 value of the (already row/col-gathered) split block,
    unscaled by ``inv_e``/``inv_d_f`` (ones for a raw SplitMatrix;
    1/E / 1/D for a ScaledView), contracted with the folded values.
    The block is (m_c, n_f) — small next to A — so one f64
    materialization per bucket transition is fine."""
    A_f = (hi_f.astype(jnp.float64) + lo_f.astype(jnp.float64)) \
        * inv_e[:, None] * inv_d_f[None, :]
    return fv @ A_f.T


@partial(jax.jit, static_argnames=("nblocks",))
def _unscale_split_blocks(hi, lo, inv_e, inv_d, nblocks=8):
    """Unscale an (already gathered) compacted ScaledView block back to
    a raw df32 pair: blk = (hi+lo)·(1/E)·(1/D) re-split, in ROW BLOCKS
    so the f64 transient exists one block at a time (the
    _scale_split_blocks discipline in reverse)."""
    m = hi.shape[0]
    his, los = [], []
    bounds = [(m * i) // nblocks for i in range(nblocks + 1)]
    for i in range(nblocks):
        sl = slice(bounds[i], bounds[i + 1])
        blk = (hi[sl].astype(jnp.float64) + lo[sl].astype(jnp.float64)) \
            * inv_e[sl, None] * inv_d[None, :]
        h = blk.astype(jnp.float32)
        los.append((blk - h.astype(jnp.float64)).astype(jnp.float32))
        his.append(h)
    return jnp.concatenate(his), jnp.concatenate(los)


@partial(jax.jit, static_argnames=("w_on", "prox_on"))
def dual_fold(c0_fold, vals, W, xbar, rho, wscale, *, w_on, prox_on):
    """Per-iteration dual-bound constant of the compacted system: the
    assembled-objective contribution of the FOLDED columns. The base
    fold (c·v + ½P·v², computed once at compaction) rides ``c0_fold``;
    the W / prox-center terms move every PH iteration, so they fold
    here from the fixed-slot blocks — the same wvec combination
    core/ph._ph_assemble scatters for the free slots. With this
    constant, the compacted solve's qp_dual_objective certifies
    exactly the bound the uncompacted PINNED solve would."""
    Weff = W if wscale is None else W * wscale
    if w_on and prox_on:
        wvec = Weff - rho * xbar
    elif w_on:
        wvec = Weff
    elif prox_on:
        wvec = -rho * xbar
    else:
        wvec = jnp.zeros_like(W)
    fold = c0_fold + jnp.sum(wvec * vals, axis=1)
    if prox_on:
        fold = fold + 0.5 * jnp.sum(rho * vals * vals, axis=1)
    return fold


@jax.jit
def expand_solution(x_c, fv, keep_cols, fixed_cols, n_template):
    """Scatter a compacted solution block back to full width:
    x_full[:, keep] = x_c, x_full[:, fixed] = the folded values.
    ``n_template`` is a (n,)-shaped array (shape carrier only — a
    static int would re-trace per call site)."""
    S = x_c.shape[0]
    out = jnp.zeros((S, n_template.shape[0]), x_c.dtype)
    out = out.at[:, keep_cols].set(x_c)
    return out.at[:, fixed_cols].set(fv)


# shape-bucket registry (module-level, process-global like the jit
# cache it mirrors): fingerprint -> shapes. A wheel pays at most one
# XLA compile per bucket transition; a SECOND wheel of the same
# fingerprint reuses the first's traced programs entirely (the jit
# cache keys on shapes, which the fingerprint determines) — counters
# ``shrink.bucket.compile`` / ``shrink.bucket.cache_hit`` record which
# happened, the serve/cache.py discipline applied to compaction.
_BUCKET_REGISTRY: dict = {}


def bucket_fingerprint(fields: dict) -> str:
    """Stable 16-hex shape-bucket id (same hashing as serve/cache and
    checkpoint fingerprints — ckpt/bundle.config_fingerprint)."""
    return config_fingerprint(fields)


def bucket_registry():
    """Read-only view for tests/telemetry."""
    return dict(_BUCKET_REGISTRY)


def build_plan(qp_data: QPData, c, c0, nonant_idx, fixed_mask,
               fixed_vals, bucket, *, dtype, ident=None) -> ShrinkPlan | None:
    """Build the compaction plan for the CURRENT fixed set against the
    ORIGINAL full system (plans are always derived from the full data,
    never incrementally — transitions stay independent and exact).

    Host staging happens ONCE per bucket transition (never per
    iteration): the fixed-slot mask comes down as one (S, K) bool
    block, and the kept-row pattern is a device reduction read back as
    one (m,) bool vector. Returns None when nothing (or everything)
    would compact."""
    fm = np.asarray(fixed_mask)            # one D2H per bucket transition
    slot_fixed = fm.all(axis=0)
    idx_np = np.asarray(nonant_idx)
    fixed_slots = np.flatnonzero(slot_fixed)
    free_slots = np.flatnonzero(~slot_fixed)
    if fixed_slots.size == 0 or free_slots.size == 0:
        return None
    A = qp_data.A
    n = int(A.shape[-1])
    m = int(A.shape[-2])
    fixed_cols = np.sort(idx_np[fixed_slots])
    keep_cols = np.setdiff1d(np.arange(n), fixed_cols)
    # rows that still touch a kept column IN ANY SCENARIO; rows whose
    # every nonzero is a fixed column reduce to constants and are
    # dropped with them. df32 representations read the pattern off the
    # split pair (a ScaledView's A_s shares A's zero pattern — Ruiz
    # scalings are diagonal and positive)
    keep_dev = jnp.asarray(keep_cols)
    pat = A.A_s if isinstance(A, ScaledView) else A
    if isinstance(pat, SplitMatrix):
        touched = (pat.hi[:, keep_dev] != 0) | (pat.lo[:, keep_dev] != 0)
    else:
        touched = pat[..., keep_dev] != 0
    row_touch = np.asarray(
        jnp.any(touched, axis=(0, 2) if touched.ndim == 3 else 1))
    keep_rows = np.flatnonzero(row_touch)                # (m,) one D2H
    if keep_rows.size == 0:
        return None
    fixed_cols_d = jnp.asarray(fixed_cols)
    keep_rows_d = jnp.asarray(keep_rows)
    # folded values per ORIGINAL column order (nonant slots -> columns)
    order = np.argsort(idx_np[fixed_slots])
    fv = jnp.asarray(fixed_vals, dtype)[:, jnp.asarray(fixed_slots[order])]
    if isinstance(A, (SplitMatrix, ScaledView)):
        # df32 compacted gather: exact hi/lo row/column gathers of the
        # split pair; a ScaledView gathers the SCALED pair and unscales
        # blockwise back to a raw split (the compacted system gets its
        # own Ruiz pass in _shrink_get_factors, so plans carry the raw
        # representation either way). Packed layouts are screened out
        # by the engine guard (core/ph.maybe_compact) before this.
        if isinstance(A, ScaledView):
            if isinstance(A.A_s, SplitMatrix):
                hi, lo = A.A_s.hi, A.A_s.lo
            else:       # dense scaled matrix: two-term split, exact
                hi = A.A_s.astype(jnp.float32)
                lo = (A.A_s - hi.astype(jnp.float64)) \
                    .astype(jnp.float32)
            inv_e = 1.0 / A.E
            inv_d = 1.0 / A.D
        else:
            hi, lo = A.hi, A.lo
            inv_e = jnp.ones((m,), jnp.float64)
            inv_d = jnp.ones((n,), jnp.float64)
        hi_k, lo_k = hi[keep_rows_d], lo[keep_rows_d]
        shift = _split_fixed_shift(
            hi_k[:, fixed_cols_d], lo_k[:, fixed_cols_d],
            inv_e[keep_rows_d], inv_d[fixed_cols_d], fv)
        if isinstance(A, ScaledView):
            hi_c, lo_c = _unscale_split_blocks(
                hi_k[:, keep_dev], lo_k[:, keep_dev],
                inv_e[keep_rows_d], inv_d[keep_dev])
        else:
            hi_c, lo_c = hi_k[:, keep_dev], lo_k[:, keep_dev]
        A_c = SplitMatrix(hi_c, lo_c)
        l_c, u_c, lb_c, ub_c, P_c, c_c, c0_fold = _fold_vectors(
            qp_data.l, qp_data.u, qp_data.lb, qp_data.ub,
            qp_data.P_diag, c, c0, keep_rows_d, keep_dev, fixed_cols_d,
            fv, shift)
    else:
        A_c, l_c, u_c, lb_c, ub_c, P_c, c_c, c0_fold, shift = \
            _fold_compact(
                A, qp_data.l, qp_data.u, qp_data.lb, qp_data.ub,
                qp_data.P_diag, c, c0, keep_rows_d, keep_dev,
                fixed_cols_d, fv)
    data_c = QPData(P_c, A_c, l_c, u_c, lb_c, ub_c)
    idx_c = np.searchsorted(keep_cols, idx_np[free_slots])
    fp = bucket_fingerprint({
        "bucket": float(bucket), "n": n, "m": m,
        "n_c": int(keep_cols.size), "m_c": int(keep_rows.size),
        "K_c": int(free_slots.size), "dtype": str(dtype),
        **(ident or {})})
    seen = fp in _BUCKET_REGISTRY
    _BUCKET_REGISTRY[fp] = (int(keep_rows.size), int(keep_cols.size))
    if seen:
        obs.counter_add("shrink.bucket.cache_hit")
    else:
        obs.counter_add("shrink.bucket.compile")
    return ShrinkPlan(
        bucket=float(bucket), fingerprint=fp, n_full=n, m_full=m,
        n_c=int(keep_cols.size), m_c=int(keep_rows.size),
        n_fixed_slots=int(fixed_slots.size),
        free_slots=free_slots, fixed_slots=fixed_slots,
        keep_cols=keep_dev, fixed_cols=fixed_cols_d,
        free_slots_dev=jnp.asarray(free_slots),
        fixed_slots_dev=jnp.asarray(fixed_slots),
        idx_c=jnp.asarray(idx_c), fixed_colvals=fv,
        data_c=data_c, c_c=c_c, c0_fold=c0_fold,
        rhs_shift=shift, keep_rows_np=keep_rows, keep_cols_np=keep_cols,
        meta={"bucket_cached": seen})


# ---------------- cross-bucket warm transplant ----------------

@jax.jit
def _transplant_rescale(x, yA, yB, zA, zB, pos_cols, pos_rows,
                        D_old, D_new, E_old, E_new, Eb_old, Eb_new,
                        cs_ratio, shift_old, shift_new, ok):
    """Gather + rescale one mode's SCALED warm ADMM iterates from the
    old width into a new compacted width (full→compacted or
    compacted→compacted; the host caller verifies the new kept set is
    a subset of the old and builds ``pos_cols``/``pos_rows`` — new
    position j came from old position pos[j]).

    Scaling algebra (all quantities scaled, per ops/qp_solver): an
    UNSCALED iterate x_u relates to the scaled one by x = x_u / D, row
    duals by yA = cs·y_u/E, bound duals by yB = cs·y_u/Eb, row slacks
    by zA = E·(A x_u − shift) (the compacted rhs moved by −shift), and
    bound slacks by zB = Eb·x_u. Re-expressing the same unscaled point
    under the new factors' (D, E, Eb, cost_scale, shift):

        x'  = x[pos_c]  · D_old[pos_c] / D_new
        yA' = yA[pos_r] · cs_ratio · E_old[pos_r] / E_new
        yB' = yB[pos_c] · cs_ratio · Eb_old[pos_c] / Eb_new
        zA' = E_new · (zA[pos_r]/E_old[pos_r] + shift_old[:,pos_r]
                       − shift_new)
        zB' = zB[pos_c] · Eb_new / Eb_old[pos_c]

    Scaling vectors may be shared (1-D) or per-scenario (2-D, batched-A
    or per-scenario-rho factors); ``cs_ratio`` scalar or (S,). Both
    sides normalize to broadcastable (1|S, ·) rows, so old and new
    factor forms can even differ.

    ``ok`` is an (S,) keep mask (hospital/dirty scenarios excluded):
    excluded rows multiply to exactly the cold-state zeros."""
    def b2(v):
        return v if v.ndim == 2 else v[None, :]

    csr = cs_ratio if jnp.ndim(cs_ratio) == 0 else cs_ratio[:, None]
    okf = ok.astype(x.dtype)[:, None]
    x_n = x[:, pos_cols] * b2(D_old)[:, pos_cols] / b2(D_new) * okf
    yA_n = yA[:, pos_rows] * csr \
        * (b2(E_old)[:, pos_rows] / b2(E_new)) * okf
    yB_n = yB[:, pos_cols] * csr \
        * (b2(Eb_old)[:, pos_cols] / b2(Eb_new)) * okf
    zA_n = (b2(E_new)
            * (zA[:, pos_rows] / b2(E_old)[:, pos_rows]
               + shift_old[:, pos_rows] - shift_new)) * okf
    zB_n = zB[:, pos_cols] * (b2(Eb_new) / b2(Eb_old)[:, pos_cols]) * okf
    return x_n, yA_n, yB_n, zA_n, zB_n
