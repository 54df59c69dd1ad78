"""Progressive Hedging: PHBase primitives + synchronous PH driver.

The reference's PHBase (ref. mpisppy/phbase.py:31) attaches mutable Params
(W, rho, xbars, w_on, prox_on) to every Pyomo scenario, rewrites each
objective to  f_s(x) + w_on·Wᵀx + prox_on·(ρ/2)‖x−x̄‖²  (ref. phbase.py:
1184-1209), and loops: solve every subproblem with a commercial solver
(solve_loop :999), Allreduce x̄/x̄² per tree node (Compute_Xbar :144),
dual update W += ρ(x−x̄) (Update_W :224), scaled-L1 convergence (:254).

TPU redesign — one jitted step per PH iteration over the whole batch:
- the objective rewrite is a *linear-term assembly*: q = c with
  (w_on·W − prox_on·ρ·x̄) scattered into the nonant columns, and the prox
  quadratic is ρ on the nonant diagonal of P. Because ρ enters the ADMM
  KKT matrix, toggling prox switches between two cached factorizations
  (with-prox for PH, without for Lagrangian/xhat work) instead of editing
  expressions (ref. phbase.py:712-751 _disable/_reenable_W_and_prox).
- Compute_Xbar/Update_W/convergence are fused into the same jitted step as
  the batched solve; the per-node reduction is the membership matmul from
  SPBase.compute_xbar (psum-ready under sharding).
- warm starts: the ADMM state (x, y, z) persists across PH iterations and
  the factor cache persists for the whole run (q is the only thing PH
  changes), replacing persistent-solver set_objective (ref. phbase.py:903).
- the prox linearizer (ref. utils/prox_approx.py) is unnecessary by
  construction: the quadratic prox is native to the QP kernel. The
  `linearize_proximal_terms` option is accepted and ignored.
"""

from __future__ import annotations

import contextlib
import logging
import time as _time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import global_toc, log as _log_setup, obs  # noqa: F401  (log import
#   installs the quiet "mpisppy_tpu" root handler the child logger
#   propagates to)
from ..obs import resource as _obs_resource
from ..ir.batch import ScenarioBatch
from ..ops.qp_solver import (LInv, QPData, QPState, qp_setup, qp_solve,
                             qp_solve_mixed, qp_solve_segmented,
                             qp_cold_state, qp_dual_objective,
                             qp_reset_rho, stacked_residuals, packed_exit,
                             EXIT_ROWS)
from .spbase import SPBase, compute_xbar

_log = logging.getLogger("mpisppy_tpu.ph")

# phase -> span name, precomputed so the hot loop's per-lap cost is a
# dict read, never a string allocation
_PHASE_SPAN = {"assemble": "ph.assemble", "solve": "ph.solve",
               "gate": "ph.gate", "reduce": "ph.reduce"}


def _new_phase_entry():
    """One solve mode's ``_phase_times`` entry: the seconds, the ADMM
    iterations and the cross-chip combines of the SAME solve passes,
    reset together."""
    return {"acc": {"assemble": 0.0, "solve": 0.0, "gate": 0.0,
                    "reduce": 0.0},
            "admm": {"bulk": 0, "tail": 0, "refactors": 0,
                     "linv_builds": 0, "linv_applies": 0},
            # how the same solves ENDED (``_book_exits``)
            "exits": {"solves": 0, "bulk_hist": {}, "tail_hist": {},
                      "bulk_capped": 0, "tail_capped": 0, "rows_read": 0,
                      "rows_over": 0, "rows_over_pri_only": 0,
                      "rows_over_dua_only": 0, "rows_over_both": 0,
                      "worst_pri": 0.0, "worst_dua": 0.0,
                      "rows_over_uncapped": 0, "rows_over_gate": 0,
                      "capped_calls": 0, "capped_solve_seconds": 0.0,
                      "per_call": [], "last": None, "tally": None},
            "collective": {"combines": 0, "bytes": 0},
            # dispatch-masked passes of this mode (APH φ-dispatch):
            # counted at the launch sites, timed by their two spans
            "dispatch": {"passes": 0, "chunks": 0, "solved": 0,
                         "skipped": 0, "gather_seconds": 0.0,
                         "scatter_seconds": 0.0, "bucket_compiles": 0,
                         "gather_programs": 0, "scatter_programs": 0},
            "calls": 0, "gate_syncs": 0, "assemble_programs": 0,
            "devices": 1, "mode": "host"}


class _PhaseClock:
    """One solve pass's phase anatomy, on both clocks at once. Each
    phase is an ``obs.span`` opened by NAME at its start (the
    profiler's ``TraceMe`` needs the name then), and the span's own
    ``perf_counter`` marks are what ``acc`` accumulates: the seconds
    have one source, so the session's span totals equal
    ``phase_timing`` totals and a ``jax.profiler`` capture shows the
    same phases with no session at all. A pass that raises leaves its
    open phase to the ``TraceMe``'s destructor and books nothing for
    it."""

    __slots__ = ("_acc", "_args", "_phase", "_span")

    def __init__(self, acc, args, phase="assemble"):
        self._acc = acc
        self._args = args
        self._open(phase)

    def _open(self, phase):
        self._phase = phase
        self._span = obs.span(_PHASE_SPAN[phase], cat="ph",
                              args=self._args).__enter__()

    def lap(self, nxt=None):
        """Close the open phase and open ``nxt`` (None: the pass is
        over)."""
        sp = self._span
        sp.__exit__(None, None, None)
        self._acc[self._phase] += sp.seconds
        if nxt is not None:
            self._open(nxt)

    @contextlib.contextmanager
    def outside(self, waiting):
        """Enter ``waiting`` (a wait that is not this pass's own: its
        turn in the wheel's arbiter) with the open phase CLOSED, and
        open it again once inside: the phase's seconds and spans stay
        this engine's work (a call then holds several spans of the
        phase, as the sequential opt-out's do)."""
        phase = self._phase
        self.lap()
        with waiting:
            self._open(phase)
            yield


def _mode_str(key):
    """Human mode tag for telemetry span args: the solve-mode key of
    _solve_loop_chunked / solve_loop ((fixed,) prox bool)."""
    if isinstance(key, tuple):
        return f"fixed+{'prox' if key[1] else 'noprox'}"
    return "prox" if key else "noprox"


def _assemble_rows(lb, ub, c, W, xbar, rho, idx, fixed_mask, fixed_vals,
                   wscale, w_on, prox_on):
    """Stage 1's expression: objective-rewrite + nonant pinning (cheap
    elementwise), traced by ``_ph_assemble`` (one chunk, or the whole
    batch) and by the staging programs (every chunk at once), so there
    is ONE formula.

    ``wscale`` ((S, K), or None for the uniform case) is the ratio
    variable-probability / scenario-probability. The W term enters each
    scenario objective scaled by it: the implied Lagrangian multipliers
    are then lambda = vprob*W, which sum to zero per (node, slot) by the
    vprob-weighted Compute_Xbar — keeping the Lagrangian/Ebound
    CERTIFICATE valid under variable probabilities (the reference leaves
    W unscaled there and its bounds silently lose validity in this
    rarely-used corner; with uniform probabilities wscale == 1 and the
    two coincide). Zero-probability entries get no W pressure at all —
    the generalization of the reference's w_coeff mask
    (ref. spbase.py:355, phbase.py:245-251)."""
    Weff = W if wscale is None else W * wscale
    wvec = Weff - rho * xbar if (w_on and prox_on) else (
        Weff if w_on else (-rho * xbar if prox_on else jnp.zeros_like(W)))
    q = c.at[:, idx].add(wvec)
    # fixed nonants: pin boxes (ref. phbase.py:413 _fix_nonants)
    bl = lb.at[:, idx].set(jnp.where(fixed_mask, fixed_vals, lb[:, idx]))
    bu = ub.at[:, idx].set(jnp.where(fixed_mask, fixed_vals, ub[:, idx]))
    return q, bl, bu


@partial(jax.jit, static_argnames=("w_on", "prox_on"))
def _ph_assemble(data, c, W, xbar, rho, idx, fixed_mask, fixed_vals,
                 wscale, *, w_on, prox_on):
    """Stage 1 as its own program (``_assemble_rows`` has the formula).
    Returns VECTORS only — the caller re-attaches them to its QPData
    eagerly. Returning data._replace(...) from this jit would pass
    the (possibly multi-GB) constraint matrix through the jit
    boundary, which XLA COPIES per call (measured +2.7 GB per chunk
    at reference-UC scale)."""
    return _assemble_rows(data.lb, data.ub, c, W, xbar, rho, idx,
                          fixed_mask, fixed_vals, wscale, w_on, prox_on)


# per-scenario operands the assembly consumes and no later pass reads
_STAGE_CONSUMED = ("xbar", "rho", "fm", "fv", "ws")
# the QPState fields a dispatch pass's row store keeps per scenario: what
# its gather takes out for a chunk and its placement puts back
_STORE_ROWS = ("x", "yA", "yB", "zA", "zB", "pri_res", "dua_res",
               "pri_rel", "dua_rel")


def _stage_chunk(rows, idx, *, w_on, prox_on):
    """One chunk's staged operands from its rows of every per-scenario
    vector (``PHBase._per_scen_operands``' names): ``l`` / ``u`` as
    they are, ``lb`` / ``ub`` / ``q`` assembled by ``_assemble_rows``,
    and what pass 3 evaluates the objectives against, so that nothing
    is gathered a second time: ``c``, ``c0``, ``P0``, ``W`` (under a
    shrink plan the full-width ``cF`` / ``WF`` and the fold operands
    in place of the compacted ``c`` / ``W`` the assembly consumed)."""
    q, bl, bu = _assemble_rows(
        rows["lb"], rows["ub"], rows["c"], rows["W"], rows["xbar"],
        rows["rho"], idx, rows["fm"], rows["fv"], rows.get("ws"),
        w_on, prox_on)
    drop = _STAGE_CONSUMED + (("c", "W") if "cF" in rows else ())
    out = {k: v for k, v in rows.items() if k not in drop}
    out.update(lb=bl, ub=bu, q=q)
    return out


@partial(jax.jit, static_argnames=("w_on", "prox_on"))
def _ph_stage_chunks(per, idx, ids, *, w_on, prox_on):
    """ASSEMBLE for every chunk of a resident host-chunked pass in ONE
    device program (per chunk: ten eager gathers and ``_ph_assemble``,
    ~22 launches of ~1 ms each with an idle device behind them, before
    this). ``per``: the full-width per-scenario VECTORS by name — never
    ``data`` or an (n, n) / packed leaf. ``ids``: the chunks' scenario
    ids stacked (n_chunks, chunk), an OPERAND, so dispatch passes whose
    ids change every iteration reuse the program per chunk COUNT.
    Returns a TUPLE of per-chunk dicts (``_stage_chunk``): Python
    unpacks it with no further launch, where a stacked result indexed
    ``[ci]`` on the host would put the eager index ops straight back."""
    return tuple(
        _stage_chunk({k: v[ids[ci]] for k, v in per.items()}, idx,
                     w_on=w_on, prox_on=prox_on)
        for ci in range(ids.shape[0]))


@partial(jax.jit, static_argnames=("w_on", "slot_slices"))
def _ph_reduce(x, yA, yB, d, q, c, c0, P0, prob, xbar_w, memberships, idx,
               W, rho, wmask, *, w_on, slot_slices):
    """Stage 3: Compute_Xbar + Update_W + convergence + objectives +
    certified dual bound (cheap reductions). ``wmask`` (None, or (S, K)
    bool) zeroes the W of zero-probability entries — the reference's
    w_coeff mask (ref. phbase.py:245-251). Pure COMPOSITION of
    _ph_chunk_objs + _ph_combine so the fused and chunked paths share
    one implementation of every formula (a second copy would silently
    drift)."""
    xn, base_obj, solved_obj, dual_obj = _ph_chunk_objs(
        x, yA, yB, d, q, c, c0, P0, idx, W, w_on=w_on)
    xbar_new, xsqbar_new, W_new, conv = _ph_combine(
        xn, prob, xbar_w, memberships, W, rho, wmask,
        slot_slices=slot_slices)
    return xn, xbar_new, xsqbar_new, W_new, conv, base_obj, solved_obj, \
        dual_obj


@partial(jax.jit, static_argnames=("w_on",))
def _ph_chunk_objs(x, yA, yB, d, q, c, c0, P0, idx, W, *, w_on):
    """Per-chunk tail of the PH step under scenario microbatching:
    everything that needs only THIS chunk's solve products (objectives +
    certified dual bound). The cross-scenario reductions live in
    _ph_combine."""
    xn = x[:, idx]
    base_obj = jnp.sum(c * x, axis=1) + c0 \
        + 0.5 * jnp.sum(P0 * x * x, axis=1)
    solved_obj = base_obj + (jnp.sum(W * xn, axis=1) if w_on else 0.0)
    dual_obj = qp_dual_objective(d, q, c0, yA, yB, x_witness=x)
    return xn, base_obj, solved_obj, dual_obj


@partial(jax.jit, static_argnames=("slot_slices",))
def _ph_combine(xn, prob, xbar_w, memberships, W, rho, wmask, *,
                slot_slices):
    """Cross-scenario tail of the chunked PH step: Compute_Xbar +
    Update_W + convergence over the FULL reassembled nonant block (the
    membership reductions need every scenario; chunk solves don't)."""
    K = xn.shape[1]
    xbar_new = compute_xbar(memberships, slot_slices, xbar_w, xn)
    xsqbar_new = compute_xbar(memberships, slot_slices, xbar_w, xn * xn)
    W_new = W + rho * (xn - xbar_new)
    if wmask is not None:
        W_new = jnp.where(wmask, W_new, 0.0)
    conv = jnp.dot(prob, jnp.sum(jnp.abs(xn - xbar_new), axis=1)) / K
    return xbar_new, xsqbar_new, W_new, conv


@jax.jit
def _pool_rows_zeroed(x, yA, yB, zA, zB, keep):
    """Zero the warm-start iterates of the pool rows whose candidate
    came back INFEASIBLE: an infeasible solve's iterates are diverged
    (huge duals) and warm-starting the next round's candidate from them
    can mis-converge under the corrupt scale (the calculate_incumbent
    poisoning fix, batched). Zero iterates are a valid warm start under
    any rho_scale, so the factor/scale trajectory is kept. Iterate
    VECTORS only cross this jit — the state's (possibly multi-GB)
    factor container must not ride a jit boundary (XLA copies it)."""
    r = lambda a: jnp.where(keep[:, None] if a.ndim > 1 else keep, a, 0.0)
    return r(x), r(yA), r(yB), r(zA), r(zB)


@jax.jit
def _pool_assemble(lb, ub, l, u, c, c0, vals, pin_mask, idx, sidx, pidx):
    """Chunk assembly for the batched incumbent-pool evaluation
    (ops/incumbent, doc/incumbents.md): gather the chunk's scenario rows
    and pin the candidates' nonant boxes (l = u = x̂ on the pinned
    slots). Row r of a pool solve is (candidate pidx[r], scenario
    sidx[r]) — the pool axis rides the existing batch axis, so the
    chunk is an ordinary shared-factor solve. MODULE-LEVEL like
    _ph_assemble: every engine shares one jit cache entry per shape,
    and nothing large is baked in as a literal."""
    lb_c, ub_c = lb[sidx], ub[sidx]
    v = vals[pidx]                                   # (rows, K)
    lb_c = lb_c.at[:, idx].set(jnp.where(pin_mask, v, lb_c[:, idx]))
    ub_c = ub_c.at[:, idx].set(jnp.where(pin_mask, v, ub_c[:, idx]))
    return lb_c, ub_c, l[sidx], u[sidx], c[sidx], c0[sidx]


@partial(jax.jit, static_argnames=("w_on",))
def _shrink_objs(x_full, c, c0, P0, W, idx, *, w_on):
    """Objectives of an EXPANDED compacted solve (ops/shrink,
    doc/extensions.md §shrinking): evaluated on the full-width
    solution block against the FULL cost structures, so base/solved
    objectives are bit-comparable with the uncompacted path (the fixed
    columns contribute their folded constants through x_full). The
    dual bound stays on the compacted system (_shrink_dual)."""
    xn = x_full[:, idx]
    base = jnp.sum(c * x_full, axis=1) + c0 \
        + 0.5 * jnp.sum(P0 * x_full * x_full, axis=1)
    solved = base + (jnp.sum(W * xn, axis=1) if w_on else 0.0)
    return xn, base, solved


@jax.jit
def _shrink_dual(d, q, c0_fold, yA, yB, x_c):
    """Certified dual bound of a compacted solve: qp_dual_objective on
    the compacted system plus the fixed-variable fold constant — the
    dual bound of the PINNED full problem, which is exactly what the
    uncompacted path certifies when the fixer has pinned those boxes
    (lb = ub makes their dual contribution the same constant)."""
    return qp_dual_objective(d, q, c0_fold, yA, yB, x_witness=x_c)


def _hot_eps(prox_on, sub_eps, sub_eps_hot):
    """The effective primal tolerance of a solve — THE policy both the
    dispatch and any quality gate (chunk recovery) must share."""
    return sub_eps_hot if (prox_on and sub_eps_hot is not None) else sub_eps


def _exit_tests(*, prox_on, precision, sub_max_iter, sub_eps, sub_eps_hot,
                sub_eps_dua_hot, tail_iter, **_):
    """What ends one ``_solver_call``'s ADMM loops, from the call's own
    keyword values: ``(e_pri, e_dua, bulk budget, tail budget)``. The
    tolerances are the ones the solvers take as ``eps_abs = eps_rel``
    (primal) and ``eps_abs_dua = eps_rel_dua``; a mixed / df32 solve
    gives its f32 bulk ``sub_max_iter`` iterations and its accurate
    tail ``tail_iter``, any other precision has no bulk phase and its
    one loop (booked as tail: ``QPState.iters_lo`` is 0) has
    ``sub_max_iter``. ``_solver_call`` derives its tolerances HERE, so
    the exit booking (``_book_exits``) tests rows against what the loop
    tested them against."""
    e_pri = _hot_eps(prox_on, sub_eps, sub_eps_hot)
    e_dua = sub_eps_dua_hot if (prox_on and sub_eps_dua_hot is not None) \
        else sub_eps
    if precision in ("mixed", "df32"):
        return e_pri, e_dua, int(sub_max_iter), int(tail_iter)
    return e_pri, e_dua, 0, int(sub_max_iter)


def _solver_call(factors, d, q, qp_state, *, prox_on, precision,
                 sub_max_iter, sub_eps, sub_eps_hot, sub_eps_dua_hot,
                 tail_iter, stall_rel, segment, polish_hot, polish_chunk,
                 segment_lo=None, ir_sweeps=1, donate=False, kernel=None,
                 adaptive_rho=True):
    """The ONE precision-policy + solver dispatch, shared by the fused
    step and the chunked loop (a second copy would silently drift).

    The PH hot loop consumes only primal iterates (bounds come from
    prox-off solves), and on degenerate LPs the ADMM residuals plateau
    far above tight tolerances — a tight test would burn the whole
    iteration budget every PH iteration. Model configs that hit the
    plateau (UC) opt in via subproblem_eps_hot / subproblem_eps_dua_hot
    / subproblem_stall_rel: the LOOP criteria loosen for prox-on solves
    and the active-set polish carries the point to machine accuracy
    (measured: polish reaches ~1e-14 relative from a 1e-4-stalled loop
    point on UC). Defaults keep the strict contract everywhere. The
    polish serves DUAL accuracy (certified bounds) and final primal
    refinement, so prox-on solves can skip it (subproblem_polish_hot).

    ``kernel`` (ops/kernels.KernelPlan or None): a fused-mode plan
    routes the solve through ONE device program (doc/kernels.md)
    instead of the host-segmented drivers below; None — including
    every recovery/hospital caller, which deliberately clears it — is
    today's segmented path, bit-for-bit.

    ``adaptive_rho=False`` freezes the stepsize trajectory: the
    incumbent-pool evaluator requires it because shared-mode rho
    adaptation is computed from the geometric mean over ALL batch rows
    — a pool's infeasible members contaminate the shared scalar and
    the feasible candidates mis-converge (measured 13% objective
    inflation on the UC fixture; doc/incumbents.md)."""
    e_pri, e_dua, _, _ = _exit_tests(
        prox_on=prox_on, precision=precision, sub_max_iter=sub_max_iter,
        sub_eps=sub_eps, sub_eps_hot=sub_eps_hot,
        sub_eps_dua_hot=sub_eps_dua_hot, tail_iter=tail_iter)
    do_polish = polish_hot or not prox_on
    if kernel is not None and kernel.mode == "fused":
        from ..ops import kernels as _kernels
        return _kernels.kernel_solve(
            kernel, factors, d, q, qp_state, precision=precision,
            max_iter=sub_max_iter, tail_iter=tail_iter, e_pri=e_pri,
            e_dua=e_dua, stall_rel=stall_rel, polish=do_polish,
            polish_chunk=polish_chunk, ir_sweeps=ir_sweeps,
            adaptive_rho=adaptive_rho, donate=donate)
    if precision in ("mixed", "df32"):
        # df32 differs from mixed only in the data representation (the
        # engine's A is a SplitMatrix, see spbase) — the driver is the
        # same f32-bulk + accurate-tail escalation, with the tail's
        # matvecs/factor in split-f32 instead of emulated f64
        # f32 bulk + f64 tail (see qp_solve_mixed): data/state stay f64
        return qp_solve_mixed(factors, d, q, qp_state,
                              max_iter=sub_max_iter, tail_iter=tail_iter,
                              eps_abs=e_pri, eps_rel=e_pri,
                              polish_chunk=polish_chunk,
                              eps_abs_dua=e_dua, eps_rel_dua=e_dua,
                              stall_rel=stall_rel, segment=segment,
                              segment_lo=segment_lo, polish=do_polish,
                              ir_sweeps=ir_sweeps,
                              adaptive_rho=adaptive_rho, donate=donate)
    return qp_solve_segmented(factors, d, q, qp_state,
                              max_iter=sub_max_iter, segment=segment,
                              eps_abs=e_pri, eps_rel=e_pri,
                              polish_chunk=polish_chunk,
                              eps_abs_dua=e_dua, eps_rel_dua=e_dua,
                              stall_rel=stall_rel, polish=do_polish,
                              ir_sweeps=ir_sweeps,
                              adaptive_rho=adaptive_rho, donate=donate)


def _linv_wraps(plan, st):
    """How many explicit inverses the fused solve's ENTRY builds for the
    state ``st`` under ``plan``: 1 for a cold state's bare factor
    (``fused_mixed_solve`` wraps it into a ``qp_solver.LInv`` eagerly),
    0 for a state that already carries one; None where the plan keeps
    no explicit inverse (``_book_admm_iters`` then books none)."""
    if plan is None or not plan.l_inv:
        return None
    return int(not isinstance(st.L, LInv))


def _book_admm_iters(admm, states, fused, linv_wraps=None, packed=None,
                     ir_sweeps=1):
    """Book the ADMM iterations of the solves that produced ``states``
    into ``admm`` (the "admm" dict of a mode's ``_phase_times`` entry):
    ``bulk`` = the low-precision phase's (``QPState.iters_lo``),
    ``tail`` = the rest, ``refactors`` = their in-loop rho
    refactorizations (``QPState.refactors``: how often the factor was
    prepared anew) — the work of exactly the solves the solve lap
    times, with or without a telemetry session. ``linv_builds``, where
    the plan keeps an explicit inverse (``linv_wraps`` is not None:
    ``_linv_wraps`` of the call's first state): the eager wraps plus
    the refactorizations, each of which leaves the inverse to be built
    anew (the handoff builds ONE however often the bulk refactored, so
    the count is exact while a bulk refactors at most once, and an
    upper bound beyond); and ``linv_applies``, the L⁻¹ products the
    same solves ran: every tail iteration's x-update solves
    ``1 + ir_sweeps`` times (the seed and the refinement sweeps) and an
    ``LInv`` solve is two products; 0 where the plan keeps no inverse
    (the x-update then substitutes), so the pair says which form the
    timed solves ran. No new device wait:
    fused plans' callers sit AFTER the phase-honesty block they pay
    anyway (scalar copies, not stalls), and the segmented drivers hand
    back HOST scalars (they read their counts segment by segment), for
    which ``device_get`` is the identity.

    Returns ``(its, res)`` for ``_book_exits``: the per-solve
    ``(total, bulk)`` counts, kept apart, and, with ``packed`` (the
    un-chunked fused body, which has no gate to read them at: each
    state's ``qp_solver.packed_exit`` vector, already waited for), the
    states' ``EXIT_ROWS`` as one (4, solves, width) host array: counts
    and rows then come in ONE transfer a state where the counts alone
    were three; None otherwise."""
    if packed is not None:
        got = jax.device_get(packed)
        res = np.stack([g[3:].reshape(len(EXIT_ROWS), -1) for g in got],
                       axis=1)
    else:
        got = jax.device_get([(st.iters, st.iters_lo, st.refactors)
                              for st in states])
        res = None
    its = [(int(g[0]), int(g[1])) for g in got]
    total, bulk = (sum(col) for col in zip(*its))
    refs = sum(int(g[2]) for g in got)
    admm["bulk"] += bulk
    admm["tail"] += total - bulk
    admm["refactors"] += refs
    applies = 0
    if linv_wraps is not None:
        admm["linv_builds"] += linv_wraps + refs
        applies = (total - bulk) * (1 + int(ir_sweeps)) * 2
        admm["linv_applies"] += applies
    if obs.enabled():
        obs.counter_add("kernel.l_inv_applies", applies)
        obs.counter_add("kernel.bulk_iters", bulk)
        obs.counter_add("kernel.tail_iters", total - bulk)
        obs.counter_add("kernel.factor_prepares", refs)
        if fused:
            obs.counter_add("kernel.fused_iters", total)
    return its, res


_PER_CALL_KEPT = 256    # calls whose exit record ``exits["per_call"]`` keeps


def _rows_over(res, e_pri, e_dua):
    """The loop's per-row exit test (``qp_solver._solve_impl``:
    ``conv_ok = (pri <= eps_abs + eps_rel * pri_sc) & (dua <=
    eps_abs_dua + eps_rel_dua * dua_sc)``, ``eps_abs = eps_rel``)
    recomputed in host float64 from a returned state's ``EXIT_ROWS``
    (``res``: pri_rel, pri_res, dua_res, dua_rel, any common shape).
    The scales are recovered as residual / relative residual (a zero
    residual passes under any scale). Returns ``(over_pri, over_dua,
    pri_res / its tolerance, dua_res / its tolerance)``; a NaN row is
    over by both tests."""
    pri_rel, pri, dua, dua_rel = res
    with np.errstate(divide="ignore", invalid="ignore"):
        pri_tol = e_pri + e_pri * np.where(pri_rel > 0, pri / pri_rel, 0.0)
        dua_tol = e_dua + e_dua * np.where(dua_rel > 0, dua / dua_rel, 0.0)
        return (~(pri <= pri_tol), ~(dua <= dua_tol),
                pri / pri_tol, dua / dua_tol)


def _book_exits(ex, its, tests, solve_s, res=None, ids=None, live=None):
    """Book how the chunk solves of ONE ``solve_loop`` call ended into
    ``ex`` (the "exits" dict of the mode's ``_phase_times`` entry,
    reset with the seconds; ``_exits_view`` documents every field).
    ``its``: ``_book_admm_iters``' per-solve (total, bulk)
    counts; ``tests``: ``_exit_tests`` of the keyword values
    ``_solver_call`` was handed; ``solve_s``: the ``ph.solve`` span's
    seconds of this call.

    A phase is CAPPED when it ran its whole budget. At a tail-capped
    exit the loop left through ``it < max_iter``, not through
    ``done = all(conv_ok | stalled)``, so some row still failed
    ``conv_ok``: with ``res`` ((4, solves, width) host array of the
    returned states' ``EXIT_ROWS``) the rows are classified by the
    loop's OWN test on the loop's own last residuals (``_rows_over``;
    with no polish, the returned residuals are computed on the final
    ADMM iterates). ``ids`` / ``live`` ((solves,
    width)): each row's GLOBAL scenario id, and whether it counts
    (chunk pads and zero-probability mesh pads do not). At an UNCAPPED
    exit the rows still over are the ones the stall rule let go
    (``rows_over_uncapped``). Host numpy over (solves, width):
    microseconds. Returns the call's ``[tail iterations of each solve,
    rows over at its capped exits]`` record."""
    e_pri, e_dua, bulk_cap, tail_cap = tests
    bulks = np.array([b for _, b in its])
    tails = np.array([t - b for t, b in its])
    capped = (tails >= tail_cap) if tail_cap > 0 else np.zeros(len(its), bool)
    ex["solves"] += len(its)
    for hist, vals in ((ex["bulk_hist"], bulks), (ex["tail_hist"], tails)):
        for v in vals.tolist():
            hist[v] = hist.get(v, 0) + 1
    if bulk_cap > 0:
        ex["bulk_capped"] += int((bulks >= bulk_cap).sum())
    n_cap = int(capped.sum())
    ex["tail_capped"] += n_cap
    if n_cap:
        ex["capped_calls"] += 1
        ex["capped_solve_seconds"] += solve_s
    n_over = 0
    if res is not None:
        over_p, over_d, by_pri, by_dua = _rows_over(res, e_pri, e_dua)
        over_p, over_d = over_p & live, over_d & live
        over = over_p | over_d
        ex["rows_over_uncapped"] += int(over[~capped].sum())
        if n_cap:
            ex["rows_read"] += n_cap
            op, od, ov = over_p[capped], over_d[capped], over[capped]
            n_over = int(ov.sum())
            ex["rows_over"] += n_over
            ex["rows_over_pri_only"] += int((op & ~od).sum())
            ex["rows_over_dua_only"] += int((od & ~op).sum())
            ex["rows_over_both"] += int((op & od).sum())
            for name, r in (("worst_pri", by_pri), ("worst_dua", by_dua)):
                r = r[capped][live[capped] & np.isfinite(r[capped])]
                if r.size:
                    ex[name] = max(ex[name], float(r.max()))
            ex["tally"] += np.bincount(ids[capped][ov],
                                       minlength=ex["tally"].size)
    rec = [tails.tolist(), n_over]
    if len(ex["per_call"]) < _PER_CALL_KEPT:
        ex["per_call"].append(rec)
    if obs.enabled():
        obs.counter_add("kernel.tail_capped", n_cap)
        obs.counter_add("kernel.rows_over", n_over)
    ex["last"] = rec
    return rec


def _exits_view(ex, top_n=8):
    """``phase_timing()["exits"]``: the booked totals of a mode's
    pass-1 chunk solves since the reset, as plain host values.

    ``solves``; ``bulk_hist`` / ``tail_hist`` ({ADMM iterations of the
    phase: solves}: multiples of ``check_every`` up to the budget, so
    at most 16 / 4 keys under the UC recipe; their iterations sum to
    ``admm_iters_per_call`` x calls exactly); ``bulk_capped`` /
    ``tail_capped`` (solves whose phase ran its whole budget: see
    ``_exit_tests``). At the tail-capped exits whose residual rows the
    host had (``rows_read`` of them: all but the un-chunked
    host-segmented path's): ``rows_over`` (rows that still failed the
    loop's own ``conv_ok``), split ``rows_over_pri_only`` /
    ``_dua_only`` / ``_both`` by the test they failed, and
    ``worst_pri`` / ``worst_dua`` (largest finite residual / its
    tolerance there); ``scenarios_over`` (distinct scenarios ever over
    at a capped exit) and ``top`` (the ``top_n`` over at the most
    capped exits, ``[[scenario, exits], ...]``, global ids).
    ``rows_over_uncapped``: rows still over at exits that did NOT run
    to the cap, i.e. let go by the stall rule. ``capped_calls`` /
    ``capped_solve_seconds``: ``solve_loop`` calls with at least one
    tail-capped solve, and the ``ph.solve`` span's seconds of exactly
    those calls. ``rows_over_gate``: rows above the recovery gate
    after passes 2 / 2b (what the ``ph.standing`` note narrates).
    ``per_call``: the first 256 calls since the reset, each ``[tail
    iterations of each chunk solve, rows over at its capped exits]``."""
    out = {k: v for k, v in ex.items() if k not in ("tally", "last")}
    out["bulk_hist"] = dict(sorted(ex["bulk_hist"].items()))
    out["tail_hist"] = dict(sorted(ex["tail_hist"].items()))
    out["per_call"] = list(ex["per_call"])
    tally = ex["tally"]
    hit = np.flatnonzero(tally) if tally is not None else ()
    out["scenarios_over"] = len(hit)
    # most exits first, the lower id first among equals
    order = sorted(hit, key=lambda g: (-tally[g], g))[:top_n]
    out["top"] = [[int(g), int(tally[g])] for g in order]
    return out


def _row_map(ids, reals, n_scen):
    """``(ids, live)`` of a pass's chunk rows for ``_book_exits``:
    ``ids`` (n_chunks, chunk) global scenario ids on the host, ``live``
    whether a row counts: not a chunk pad (column >= the chunk's
    ``real``), not a zero-probability mesh pad (id >= ``n_scen``)."""
    cols = np.arange(ids.shape[1])[None, :]
    return ids, (cols < np.asarray(reals)[:, None]) & (ids < n_scen)


def _ph_step(qp_state, factors, data, c, c0, P0, prob, xbar_w, memberships,
             idx, W, xbar, rho, fixed_mask, fixed_vals, wscale=None, *,
             w_on, prox_on, slot_slices, sub_max_iter, sub_eps,
             polish_chunk, precision="native", tail_iter=1000,
             sub_eps_hot=None, sub_eps_dua_hot=None, stall_rel=0.0,
             segment=500, polish_hot=True, segment_lo=None, ir_sweeps=1,
             lap=None, combine_fn=None, kernel=None, admm=None,
             exits=None, turn=None):
    """The PH iteration: batched subproblem solve + Compute_Xbar +
    Update_W + convergence + objectives + certified dual bound, staged as
    THREE jitted programs (assemble / solve / reduce) rather than one
    fused monolith: the fused UC-sized program crashed the experimental
    TPU backend's worker above S≈64 and compiled minutes-slower, while
    the three-call split dispatches in microseconds and shares the
    solver's jit cache with every other qp_solve consumer.

    MODULE-LEVEL on purpose: every engine instance in the process (hub +
    each spoke cylinder owns its own engine) shares ONE jit cache entry
    per (mode, shapes) — per-instance closures would recompile the same
    UC-sized program once per cylinder. Everything large (factors, data,
    costs) is an ARGUMENT, not a closure constant: closing over batch
    tensors would bake them into the lowered program as literals
    (gigabytes at UC scale)."""
    q, bl, bu = _ph_assemble(data, c, W, xbar, rho, idx, fixed_mask,
                             fixed_vals, wscale, w_on=w_on,
                             prox_on=prox_on)
    d = data._replace(lb=bl, ub=bu)
    if lap is not None:
        # phase-anatomy hook (``_PhaseClock.lap``): the fused path
        # books the same assemble/solve/reduce laps as the chunked
        # loop. Dispatch is async, so "assemble"/"reduce" book enqueue
        # cost while "solve" absorbs the device wait (segment iteration
        # readbacks block).
        lap("solve")
    wraps = _linv_wraps(kernel, qp_state)
    # ``turn``: an engine of an in-process wheel takes the solve as ONE
    # turn of the wheel's arbiter (``PHBase._wheel_turn``): the solve
    # lands in the solve lap, the wait for the turn in no lap (it is the
    # arbiter's to book), the reduce outside the turn
    with contextlib.nullcontext() if turn is None else turn():
        qp_state, x, yA, yB = _solver_call(
            factors, d, q, qp_state, prox_on=prox_on, precision=precision,
            sub_max_iter=sub_max_iter, sub_eps=sub_eps,
            sub_eps_hot=sub_eps_hot, sub_eps_dua_hot=sub_eps_dua_hot,
            tail_iter=tail_iter, stall_rel=stall_rel, segment=segment,
            polish_hot=polish_hot, polish_chunk=polish_chunk,
            segment_lo=segment_lo, ir_sweeps=ir_sweeps, kernel=kernel)
        if turn is not None:
            # lint: ok[SYNC001] the wheel's admission grain: the turn ends when the device is free again
            jax.block_until_ready(qp_state.pri_rel)
    if lap is not None:
        fused = kernel is not None and kernel.mode == "fused"
        if fused:
            # phase honesty: a fused program never blocks mid-solve
            # (the segmented drivers' iteration readbacks did), so the
            # device wait would otherwise escape the lap anatomy
            # entirely — it lands at the caller's float(conv) sync,
            # outside every phase. What is waited for is the solve's
            # packed exit vector: there is no gate to read the exit rows
            # at, so they are packed with the counts by one small
            # program enqueued BEHIND the solve (its launch costs the
            # host nothing the device waits for) and come in the one
            # read below (the host-segmented drivers' rows are not on
            # the host and are not fetched)
            packed = [packed_exit(qp_state)]
            # lint: ok[SYNC001] phase honesty: the fused wait must land inside the solve lap (see comment above)
            jax.block_until_ready(packed)
        # the solve's ADMM iterations beside its seconds (``admm``
        # and ``exits`` come with ``lap``: the same ``_phase_times``
        # entry)
        its, res = _book_admm_iters(admm, [qp_state], fused, wraps,
                                    packed if fused else None, ir_sweeps)
        lap("reduce")
    wmask = None if wscale is None else wscale > 0
    if combine_fn is None:
        (xn, xbar_new, xsqbar_new, W_new, conv, base_obj, solved_obj,
         dual_obj) = _ph_reduce(x, yA, yB, d, q, c, c0, P0, prob, xbar_w,
                                memberships, idx, W, rho, wmask, w_on=w_on,
                                slot_slices=slot_slices)
    else:
        # sharded engines: the membership matmuls are replaced by the
        # explicit segment-sum + psum combine (parallel/mesh
        # ShardedScenarioOps) — same math, collective spelling
        xn, base_obj, solved_obj, dual_obj = _ph_chunk_objs(
            x, yA, yB, d, q, c, c0, P0, idx, W, w_on=w_on)
        xbar_new, xsqbar_new, W_new, conv = combine_fn(
            xn, prob, xbar_w, W, rho, wmask)
    if lap is not None:
        # how the solve ended, booked once the solve span's seconds are
        # closed and the reduce is enqueued: the host's numpy runs
        # beside the device's reduce, not in front of it
        exits(its, res)
    return qp_state, x, yA, yB, xn, xbar_new, xsqbar_new, W_new, \
        conv, base_obj, solved_obj, dual_obj


class _ChunkStateView:
    """Lazy concatenated view over per-chunk QPStates. The state
    consumers (iter-0 feasibility checks, incumbent feasibility, bench
    prints, warm-start transplants) read it occasionally, while the
    chunked hot loop runs every PH iteration — eagerly concatenating
    zA/zB (O(S·(m+n)) device copies) per solve call would tax the hot
    loop for readers that may never come. Attribute access
    concatenates on demand and caches on the instance."""

    _FIELDS = ("x", "yA", "yB", "zA", "zB", "pri_res", "dua_res",
               "pri_rel", "dua_rel")

    def __init__(self, states, trims, precomputed=None, concat_fn=None):
        self._states = list(states)
        self._trims = list(trims)
        # sharded chunks reassemble through the mesh's local concat
        # (chunk rows are strided over devices); host-chunked states
        # concatenate plainly
        self._concat = concat_fn
        for k, v in (precomputed or {}).items():
            setattr(self, k, v)

    def __getattr__(self, name):
        if name in _ChunkStateView._FIELDS:
            parts = [getattr(s, name)[:r]
                     for s, r in zip(self._states, self._trims)]
            val = jnp.concatenate(parts) if self._concat is None \
                else self._concat(parts)
            setattr(self, name, val)
            return val
        raise AttributeError(name)


class PHBase(SPBase):
    def __init__(self, batch: ScenarioBatch, options=None, rho_setter=None,
                 extensions=None, converger=None, dtype=None, mesh=None,
                 variable_probability=False):
        super().__init__(batch, options, dtype, mesh=mesh,
                         variable_probability=variable_probability)
        batch = self.batch  # possibly mesh-padded
        opts = self.options
        self.rho_default = float(opts.get("defaultPHrho", 1.0))
        self.max_iterations = int(opts.get("PHIterLimit", 100))
        self.convthresh = float(opts.get("convthresh", 1e-4))
        self.verbose = bool(opts.get("verbose", False))
        self.sub_max_iter = int(opts.get("subproblem_max_iter", 5000))
        # 1e-8 keeps the dual-objective bounds tight (f64); loosen on f32
        self.sub_eps = float(opts.get("subproblem_eps", 1e-8))
        # "native": solve at self.dtype. "mixed": f32 bulk + f64 tail
        # (requires dtype=f64 / x64 enabled) — the TPU-fast path that
        # still meets certified-bound tolerances on badly-scaled LPs
        self.sub_precision = str(opts.get("subproblem_precision", "native"))
        self.sub_tail_iter = int(opts.get("subproblem_tail_iter", 1000))
        # opt-in fast path for plateau-prone models (see _ph_step): loose
        # hot-loop criteria + stall exit; None/0 = strict (default)
        _h = opts.get("subproblem_eps_hot", None)
        self.sub_eps_hot = None if _h is None else float(_h)
        _hd = opts.get("subproblem_eps_dua_hot", None)
        self.sub_eps_dua_hot = None if _hd is None else float(_hd)
        self.sub_stall_rel = float(opts.get("subproblem_stall_rel", 0.0))
        # per-device-call iteration segment of the host-segmented
        # drivers; the f32 bulk phase of mixed solves may use a LONGER
        # segment (fewer dispatches)
        self.sub_segment = int(opts.get("subproblem_segment", 500))
        _sl = opts.get("subproblem_segment_lo", None)
        self.sub_segment_lo = None if _sl is None else int(_sl)
        # df32 x-update IR sweeps (see qp_solver._m_solve_ir: one sweep
        # lands at ~(κ·eps32)² ≈ 2e-7, far below any df32-scale
        # tolerance; raise for pathologically conditioned models)
        self.sub_ir_sweeps = int(opts.get("subproblem_ir_sweeps", 1))
        self.sub_polish_hot = bool(opts.get("subproblem_polish_hot", True))
        # kernel-mode selection (ops/kernels, doc/kernels.md):
        # "segmented" = the host-segmented qp_solver drivers bit-for-bit,
        # "fused" = one device program per solve, "auto" (default) =
        # fused wherever the solve is eligible. Validated HERE so a
        # typo'd programmatic option fails at engine construction, not
        # as a silent segmented fallback; the fused+ir_sweeps band rule
        # mirrors utils/config.AlgoConfig.validate (the CLI surface).
        from ..utils.config import (FUSED_IR_SWEEPS, KERNEL_L_INV_MODES,
                                    KERNEL_MODES)
        self.sub_kernel_mode = str(opts.get("subproblem_kernel_mode",
                                            "auto"))
        self.sub_kernel_l_inv = str(opts.get("subproblem_kernel_l_inv",
                                             "auto"))
        for val, known, name in (
                (self.sub_kernel_mode, KERNEL_MODES, "mode"),
                (self.sub_kernel_l_inv, KERNEL_L_INV_MODES, "l_inv")):
            if val not in known:
                raise ValueError(f"unknown subproblem_kernel_{name} "
                                 f"{val!r}; known: {known}")
        if self.sub_kernel_mode == "fused" \
                and self.sub_ir_sweeps not in FUSED_IR_SWEEPS:
            raise ValueError(
                f"subproblem_kernel_mode='fused' supports "
                f"subproblem_ir_sweeps in [{FUSED_IR_SWEEPS.start}, "
                f"{FUSED_IR_SWEEPS.stop - 1}] (the fused program "
                f"unrolls the sweeps statically); got "
                f"{self.sub_ir_sweeps}")
        self._kernel_plans = {}  # (factor key, s_chunk) -> KernelPlan
        if self.sub_precision in ("mixed", "df32") \
                and self.dtype != jnp.float64:
            raise ValueError(f"subproblem_precision={self.sub_precision!r}"
                             " needs dtype=float64 (enable "
                             f"jax_enable_x64); got {self.dtype}")
        self.rho_setter = rho_setter
        # ---- progressive problem shrinking (ops/shrink,
        # doc/extensions.md §shrinking) ----
        self._shrink = None            # active ops/shrink.ShrinkPlan
        self._shrink_factors = {}      # prox_on -> (factors, data_c)
        self._shrink_allowed = True    # engines may opt out; APH's
        #                                PR 13 opt-out is lifted
        #                                (doc/aph.md §composition)
        self._shrink_status = None     # bench/analyze stamp (plain
        #                                host dict: signal-safe reads)
        if opts.get("shrink_fix") or opts.get("shrink_compact") \
                or opts.get("shrink_rho"):
            if opts.get("shrink_compact") and not opts.get("shrink_fix"):
                raise ValueError("shrink_compact needs shrink_fix (the "
                                 "compaction triggers on the device "
                                 "fixer's fixed-fraction trajectory)")
            from ..utils.config import parse_shrink_buckets
            self._shrink_buckets = parse_shrink_buckets(
                opts.get("shrink_buckets", "0.25,0.5,0.75"))
            self._shrink_status = {
                "fixed": 0, "free": batch.K, "compactions": 0,
                "bucket": 0.0, "n_cols": int(batch.n),
                "m_rows": int(batch.m),
                "transplants": 0, "transplant_cold": 0,
                "est_hbm_bytes_per_iter": self._shrink_est_hbm(
                    int(batch.n), int(batch.m))}
            # CLI/serve wiring: options carry the knobs but the ctor
            # got no extension objects — attach the device fixer / rho
            # updater here so `--shrink-fix` works without programmatic
            # composition. A caller passing its own extensions owns the
            # composition (and can include DeviceFixer itself).
            if extensions is None:
                from ..extensions.extension import MultiExtension
                from ..extensions.fixer import DeviceFixer
                from ..extensions.norm_rho_updater import \
                    DeviceNormRhoUpdater
                exts = []
                if opts.get("shrink_fix"):
                    exts.append(DeviceFixer(opts))
                if opts.get("shrink_rho"):
                    exts.append(DeviceNormRhoUpdater(opts))
                if exts:
                    extensions = exts[0] if len(exts) == 1 \
                        else MultiExtension(exts)
        self.extensions = extensions
        self.converger_cls = converger
        self.converger = None

        self._rho_moved = False  # invalidate_factors() since the last
        #                          run began (reset_run reads it)
        self._init_run_state()
        # variable-probability W scaling (see _ph_assemble): vprob/p,
        # with zero-probability scenarios mapped to 0 (their subproblems
        # carry no objective weight; an eps-floor division would overflow
        # the assembled q instead)
        self._w_scale = None if self.vprob is None else jnp.where(
            self.prob[:, None] > 0, self.vprob
            / jnp.where(self.prob[:, None] > 0, self.prob[:, None], 1.0),
            0.0)
        # wheel forensics (ops/forensics.py, doc/forensics.md):
        # device-resident attribution carry + the latest unpacked
        # sample (plain host dict: signal-safe reads). Sampled every
        # forensics_interval iterations inside iteration_record, so
        # the whole layer is zero-cost when telemetry is off.
        self._forensics_every = int(opts.get("forensics_interval", 5))
        self._forensic_state = None
        self._forensic_last = None

        self._factors = {}       # prox_on -> QPFactors
        self._qp_states = {}     # prox_on -> QPState (L/rho are per-mode)
        # chunks whose reset-rho recovery retry didn't help, and
        # (chunk, row) scenarios the hospital failed to improve, per
        # mode key (see _solve_loop_chunked passes 2/2b). Blacklists are
        # NOT permanent: the assembled objective q = c + (W − ρx̄) moves
        # every PH iteration, so a row incurable at iter k may be easy
        # at iter k+N — entries are re-admitted every
        # ``subproblem_blacklist_readmit`` solves of their mode
        # (VERDICT r3 #6), tracked by _blacklist_calls below.
        self._chunk_no_retry = {}
        self._hospital_no_retry = {}
        self._blacklist_calls = {}
        # timing splits (ref. spbase.py:261-269 display_timing, a
        # secret-menu option there too): wall seconds per solve_loop
        # call, keyed by mode; off by default (the timing sync would
        # serialize host work behind device compute)
        self._timing = bool(opts.get("display_timing", False))
        self._solve_times = {}
        # pipelined chunk dispatch (see _solve_loop_chunked): per-mode
        # donation eligibility (a key enters after its first completed
        # pass — before that, chunk states share cold-state buffers and
        # donating one chunk's would delete its siblings') and the
        # per-phase wall-clock/sync accounting the bench and tests read
        self._chunk_donatable = set()
        # the admission port of an in-process wheel's arbiter
        # (utils/runtime.WheelArbiter; set by spin_the_wheel when the
        # wheel has spokes). None everywhere else: a hub-only engine, a
        # mesh, the serve path and APH's dispatch never take a turn
        self._wheel_port = None
        self._freed_modes = set()
        # batched incumbent-pool evaluation (ops/incumbent): per-
        # (pool, chunk) warm-start states + the donation crash window,
        # exactly the chunked loop's pattern (see evaluate_incumbent_pool)
        self._pool_states = {}
        self._pool_dirty = set()
        # the last batched screen's per-row objectives (row p * S + s)
        self._pool_obj_rows = None
        # modes whose donating pass is in flight: set before pass 1
        # consumes the warm-start buffers, cleared once pass 3 stores
        # their successors — a crash in between leaves the cached
        # states referencing DELETED arrays, and the next call must
        # rebuild cold instead of warm-starting from them
        self._chunk_dirty = set()
        self._phase_times = {}
        # whole PH runs (run_span) and their resets (reset_run), booked
        # beside the phases' seconds and reset with them
        self._run_times = {"count": 0, "seconds": 0.0,
                           "reset_seconds": 0.0}

    # ------------- a run's state, and its reset -------------
    def _init_run_state(self):
        """The state a PH run starts from: rho as constructed (the rho
        setter's, else ``defaultPHrho``), W = x̄ = x̄² = 0, no x, no conv,
        iteration 0, no bound, nothing fixed."""
        batch = self.batch
        S, K = batch.S, batch.K
        t = self.dtype
        # per-(scenario, slot) rho like the reference's per-variable rho Param
        if self.rho_setter is not None:
            # lint: ok[SYNC001] a rho setter returns HOST data (per-variable rho from the batch's costs); once per run, not per solve
            rho0 = np.broadcast_to(np.asarray(self.rho_setter(batch),
                                              dtype=np.float64), (K,))
        else:
            rho0 = np.full(K, self.rho_default)
        self.rho = jnp.asarray(np.broadcast_to(rho0, (S, K)), t)
        self.W = jnp.zeros((S, K), t)
        self.xbar = jnp.zeros((S, K), t)
        self.xsqbar = jnp.zeros((S, K), t)
        if self.mesh is not None:
            from ..parallel.mesh import scenario_sharding
            sh = scenario_sharding(self.mesh, 2)
            self.rho, self.W, self.xbar, self.xsqbar = (
                jax.device_put(a, sh) for a in (self.rho, self.W, self.xbar,
                                                self.xsqbar))
        self.x = None            # (S, n) latest subproblem solutions
        self.conv = None
        self._iter = 0
        self.best_bound = -float("inf")  # outer (lower, for min) bound
        self._fixed_mask = jnp.zeros((S, K), bool)   # fixer/xhat support
        self._fixed_vals = jnp.zeros((S, K), t)

    def reset_run(self):
        """Re-arm a warm engine for a new PH run from a cold W: the
        run's state back to ``_init_run_state``, and every per-run
        artifact dropped (warm-start QP states, recovery blacklists,
        donation bookkeeping, pool states, an active shrink plan, the
        extensions' ``reset()``, the warm-start attributes). What the
        runs SHARE stays: the compiled programs, the factorizations
        (functions of (A, P, rho): rebuilt only if a rho updater moved
        rho since the last reset), the kernel plans. A run after
        ``reset_run()`` repeats a fresh engine's iterates bit for bit
        (tests/test_sslp_reference.py). ``serve.manager.install_batch`` calls
        this after installing a tenant's vectors; a driver that runs
        one instance again and again calls it between runs. Span
        ``ph.run.reset``; the seconds land in
        ``phase_timing()["runs"]``."""
        with obs.span("ph.run.reset", cat="ph") as sp:
            self._init_run_state()
            if self._rho_moved:
                # the prox factors were rebuilt at a rho this run does
                # not start from
                self.invalidate_factors()
                self._rho_moved = False
            # active-set compaction state is PER-RUN: the folded
            # constants bake the previous run's rhs/cost values, so
            # the plan (and its separately cached compacted factors)
            # drops here — the next run's fixer re-accumulates and
            # re-compacts against ITS data
            self._shrink = None
            self._shrink_factors.clear()
            if getattr(self, "_shrink_skip_noted", None):
                # the last run's noted skip targets must not mute this
                # run's shrink.compaction_skipped bookings
                self._shrink_skip_noted.clear()
            if self._shrink_status is not None:
                b = self.batch
                self._shrink_status.update(
                    {"fixed": 0, "free": b.K, "compactions": 0,
                     "bucket": 0.0, "n_cols": int(b.n),
                     "m_rows": int(b.m),
                     # full-width estimate again — leaving the last
                     # run's compacted figure would stamp wrong est-HBM
                     # evidence on the next run's bucket-0 iterations
                     "est_hbm_bytes_per_iter": self._shrink_est_hbm(
                         int(b.n), int(b.m))})
            # per-run EXTENSION state: the device fixer's streak
            # counters / latched slot bounds and the rho updaters'
            # prox-center history would otherwise leak the previous
            # run's trajectory into the next (near-threshold streaks
            # fixing after one iteration, bound parks pinning at stale
            # bounds)
            ext = self.extensions
            for e in ([ext] if ext is not None else []) \
                    + list(getattr(ext, "extensions", []) or []):
                r = getattr(e, "reset", None)
                if callable(r):
                    r()
            # warm-start states carry the previous run's iterates and
            # scales, blacklists its pathology — drop them (cold states
            # rebuild through the already-compiled jitted builders)
            for cache in (self._qp_states, self._pool_states,
                          self._pool_dirty, self._chunk_no_retry,
                          self._hospital_no_retry, self._blacklist_calls,
                          self._chunk_donatable, self._chunk_dirty):
                cache.clear()
            for attr in ("_warm_started", "_warm_started_xbar",
                         "trivial_bound", "W_new"):
                if hasattr(self, attr):
                    delattr(self, attr)
        self._run_times["reset_seconds"] += sp.seconds

    @contextlib.contextmanager
    def run_span(self):
        """One whole PH run (span ``ph.run``): ``ph_main`` opens it
        around iter-0, the iterations and the wrap-up; a driver that
        steps ``solve_loop`` itself opens it around ``reset_run()`` and
        its iterations. Counted and timed in ``phase_timing()["runs"]``
        with no telemetry session; a run that raises books nothing."""
        with obs.span("ph.run", cat="ph") as sp:
            yield
        self._run_times["count"] += 1
        self._run_times["seconds"] += sp.seconds

    # ------------- observability plumbing -------------
    def _trace_note(self, etype, msg, **fields):
        """Route a recovery/hospital/standing note through the
        telemetry event stream and the ``mpisppy_tpu.ph`` logger. The
        SCREEN print (historically unconditional — these notes fired
        even with verbose=False) now requires ``verbose`` or an
        explicit ``hospital_trace=True`` opt-in; headless runs read
        the JSONL events instead."""
        obs.event(etype, fields)
        _log.info(msg)
        if self.verbose or bool(self.options.get("hospital_trace",
                                                 False)):
            global_toc(msg)

    def _trace_consumers_active(self):
        """Whether anything would consume a recovery/standing note —
        the gate for host math done only to narrate."""
        return (self.verbose
                or bool(self.options.get("hospital_trace", False))
                or obs.enabled() or _log.isEnabledFor(logging.INFO))

    # ------------- solver plumbing -------------
    def _data_with_prox(self, prox_on: bool) -> QPData:
        if not prox_on:
            return self.qp_data
        d = self.qp_data
        if d.P_diag.ndim == 1:
            # shared-structure batch: the prox diagonal must stay shared for
            # the single-factor path, which it is whenever rho is uniform
            # across scenarios (the default; rho setters are per-variable)
            rho_np = np.asarray(self.rho)   # lint: ok[SYNC001] factor-(re)build path: prox diagonal built host-side once per invalidation, not per solve
            if (rho_np == rho_np[:1]).all():
                P = d.P_diag.at[self.nonant_idx].add(
                    jnp.asarray(rho_np[0], self.dtype))
                return d._replace(P_diag=P)
            # per-scenario rho: fall back to the batched representation
            from ..ops.qp_solver import ScaledView, SplitMatrix
            if isinstance(d.A, (SplitMatrix, ScaledView)):
                raise ValueError(
                    "per-scenario rho needs the batched (S, m, n) "
                    "representation, which the df32 SplitMatrix cannot "
                    "broadcast to — use a uniform rho with "
                    "subproblem_precision='df32'")
            S = self.batch.S
            P = jnp.broadcast_to(d.P_diag, (S,) + d.P_diag.shape) \
                .at[:, self.nonant_idx].add(self.rho)
            A = jnp.broadcast_to(d.A, (S,) + d.A.shape)
            return d._replace(P_diag=P, A=A)
        P = d.P_diag.at[:, self.nonant_idx].add(self.rho)
        return d._replace(P_diag=P)

    def _get_factors(self, prox_on: bool, fixed: bool = False,
                     full: bool = False):
        """Cached per-mode factorization (invalidated on rho change).

        ``full=True`` bypasses an active shrink plan: consumers whose
        operands are built FULL-width against ``self.c`` /
        ``self.batch.n`` (the integer dive, the cross-scenario EF
        bound) must pair them with full factors even while the hot
        loop solves the compacted system — the ``_factors`` cache they
        land in is the full-system cache, untouched by shrink mode.

        ``fixed=True`` builds factors for fully-pinned-nonant solves
        (incumbent evaluation, Benders cut generation): the nonant boxes
        become equalities there, and the ADMM bound-row rho must be
        eq-boosted for those columns or the solve crawls. The boost pattern
        depends only on WHICH columns are pinned, not the pinned values,
        so one factorization serves every candidate x̂."""
        if not fixed and not full and self._shrink is not None:
            # hot-loop modes solve the COMPACTED system while a shrink
            # plan is active (doc/extensions.md §shrinking); fixed-mode
            # solves (incumbent eval, cut generation) keep the full
            # system — they pin every nonant anyway, so the active-set
            # win does not apply and their factor cache stays
            # bucket-stable for the serving layer.
            return self._shrink_get_factors(prox_on)
        key = ("fixed", bool(prox_on)) if fixed else bool(prox_on)
        if key not in self._factors:
            from ..ops.qp_solver import (ScaledView, SplitMatrix,
                                         qp_setup_like)
            d = self._data_with_prox(prox_on)
            d_setup = d
            if fixed:
                # pin the boxes only for the rho-pattern detection; the
                # cached data stays unpinned (the step applies fixed_vals
                # through fixed_mask at solve time)
                idx = self.nonant_idx
                d_setup = d._replace(lb=d.lb.at[:, idx].set(0.0),
                                     ub=d.ub.at[:, idx].set(0.0))
            is_split = isinstance(self.qp_data.A,
                                  (SplitMatrix, ScaledView))
            base = next((f for f, _ in self._factors.values()), None)
            if base is not None and isinstance(base.A_s, SplitMatrix):
                # df32: every mode shares ONE equilibration + scaled
                # split matrix — a per-mode qp_setup would put another
                # (m, n) split pair in HBM per mode (gigabytes at the
                # scale this representation exists for)
                fac = qp_setup_like(base, d_setup)
            elif is_split and self.mesh is None:
                # cross-ENGINE sharing through the batch device cache
                # (single-device engines only — cached arrays carry
                # placement): every cylinder of an in-process wheel
                # holds the same batch, and one scaled split matrix
                # must serve them all. Engines run in concurrent
                # threads, so the build is serialized under the
                # cache's lock (see spbase) — otherwise each thread
                # would put its own multi-GB split in HBM before any
                # cache write landed.
                import threading
                cache = getattr(self.batch, "_dev_cache", None)
                if cache is None:
                    cache = self.batch._dev_cache = {}
                lock = cache.setdefault("_lock", threading.Lock())
                with lock:
                    bkey = ("factors_base", str(self.dtype))
                    base = cache.get(bkey)
                    if base is not None:
                        fac = qp_setup_like(base, d_setup)
                    else:
                        fac = qp_setup(d_setup, q_ref=self.c,
                                       rows_per_call=self._rows_per_call())
                        cache[bkey] = fac
                        # the raw split A and the scaled split cannot
                        # BOTH stay in HBM at the scale df32 exists for
                        # (2.7 GB each on reference UC): from here on,
                        # every consumer reads A through the scaled
                        # view and the raw pair frees once the last
                        # engine's qp_data drops it
                        cache[("A", str(self.dtype), True)] = ScaledView(
                            fac.A_s, fac.D, fac.E)
            else:
                # mesh df32 engines (or non-split) build their own
                fac = qp_setup(d_setup, q_ref=self.c,
                               rows_per_call=self._rows_per_call())
            if is_split and isinstance(fac.A_s, SplitMatrix) \
                    and isinstance(self.qp_data.A, SplitMatrix):
                # swap this engine's raw split A for the scaled view
                # (see the cache note above); d rides along so the
                # solver's data matches
                view = ScaledView(fac.A_s, fac.D, fac.E)
                self.qp_data = self.qp_data._replace(A=view)
                d = d._replace(A=view)
            self._factors[key] = (fac, d)
        return self._factors[key]

    def _rows_per_call(self):
        """Rows ONE device call of the hot loop solves on one device:
        the per-device scenario count, or ``subproblem_chunk`` where
        that microbatches it (solve_loop's own test). What the factors'
        packed matvec form is held against (ops/packed.pack_profitable);
        a streamed source's 2-row setup surrogate says nothing of it."""
        sh = self._shard_ops
        per_device = sh.shard_size if sh is not None else self.batch.S
        chunk = int(self.options.get("subproblem_chunk", 0))
        if not 0 < chunk < per_device:
            return per_device
        return self._local_chunk(chunk) if sh is not None else chunk

    def _kernel_plan(self, key, factors, s_chunk):
        """Cached ops/kernels plan for one mode's factors (resolved
        mode, L⁻¹ profitability verdict, the bulk phase's f32 packed
        operand — doc/kernels.md). Keyed by
        (factor key, rows-per-solve-call): the L⁻¹ trade's
        profitability depends on how many RHS columns each fused
        program back-substitutes. Invalidated with the factor cache —
        a plan holds views of the factors' arrays."""
        pk = (key, int(s_chunk))
        plan = self._kernel_plans.get(pk)
        if plan is None:
            from ..ops import kernels
            tail = self.sub_tail_iter \
                if self.sub_precision in ("mixed", "df32") else 0
            plan = kernels.prepare(
                factors, mode=self.sub_kernel_mode,
                l_inv=self.sub_kernel_l_inv,
                precision=self.sub_precision,
                tail_iter=tail,
                ir_sweeps=self.sub_ir_sweeps, s_chunk=s_chunk)
            self._kernel_plans[pk] = plan
        return plan

    def invalidate_factors(self):
        """Call after changing rho (rho setters / NormRhoUpdater)."""
        self._rho_moved = True
        self._kernel_plans.clear()   # plans hold views of the factors
        # compacted factors carry the prox rho too (ops/shrink); the
        # prox-off entry survives a rho change like the full cache's
        self._shrink_factors.pop(True, None)
        for cache in (self._factors, self._qp_states):
            cache.pop(True, None)
            cache.pop(("fixed", True), None)
            cache.pop(("chunks", True), None)
            cache.pop(("chunks", ("fixed", True)), None)
            # dispatch stores carry the flowed factor + rho_scale of
            # their mode — same lifetime as the chunk states
            cache.pop(("dispatch", True), None)
            cache.pop(("dispatch", ("fixed", True)), None)
        # a new rho deserves fresh recovery chances
        self._chunk_no_retry.clear()
        self._hospital_no_retry.clear()
        self._blacklist_calls.clear()
        # chunk-plumbing caches ride the factor lifetime: rebuilt chunk
        # states start from shared cold buffers again (donation must
        # re-earn eligibility), and the index cache — keyed by
        # (chunk, S) so a mutated batch can never silently reuse stale
        # slices — resets with them
        self._chunk_donatable.clear()
        self._chunk_dirty.clear()
        getattr(self, "_chunk_idx_cache", {}).clear()
        # pool states hold factors-derived L buffers — same lifetime
        self._pool_states.clear()
        self._pool_dirty.clear()

    # ------------- active-set compaction (ops/shrink) -------------
    def _shrink_get_factors(self, prox_on: bool):
        """Cached factorization of the COMPACTED system — one
        re-factorization per (bucket transition, mode), exactly the
        budget the issue allows. Kept in a separate cache from
        ``_factors`` so the serving layer's install-refresh loop (which
        rebuilds FULL data snapshots) never pairs a compacted factor
        with full data."""
        key = bool(prox_on)
        if key not in self._shrink_factors:
            from ..ops.qp_solver import (ScaledView, SplitMatrix,
                                         qp_setup_like)
            plan = self._shrink
            d = plan.data_c
            if prox_on:
                if d.P_diag.ndim == 1:
                    # shared single-factor form: per-slot rho is fine
                    # (vector add), per-SCENARIO rho is not
                    rho_np = np.asarray(self.rho)   # lint: ok[SYNC001] factor-(re)build path: once per compaction x mode, not per solve
                    if not (rho_np == rho_np[:1]).all():
                        raise ValueError(
                            "active-set compaction of a shared-"
                            "structure batch requires rho uniform "
                            "across scenarios (per-slot vector rho is "
                            "supported; per-scenario rho is not)")
                    rho_c = jnp.asarray(rho_np[0], self.dtype)[
                        plan.free_slots_dev]
                    d = d._replace(
                        P_diag=d.P_diag.at[plan.idx_c].add(rho_c))
                else:
                    # batched per-scenario quadratic: rho adds per row
                    d = d._replace(P_diag=d.P_diag.at[:, plan.idx_c].add(
                        self.rho[:, plan.free_slots_dev]))
            if isinstance(d.A, (SplitMatrix, ScaledView)):
                # df32 compacted factors follow the full cache's
                # discipline (_get_factors): modes of ONE transition
                # share one equilibration + scaled compacted split
                # (qp_setup_like), and every consumer reads A through
                # the scaled view so the raw compacted pair frees. The
                # base is pinned on the plan, not just this cache —
                # after the first mode build data_c.A IS the view, so a
                # later mode (or a rho-invalidated rebuild) can no
                # longer run a from-scratch qp_setup
                base = next(
                    (f for f, _ in self._shrink_factors.values()),
                    None) or getattr(plan, "fac_base", None)
                if base is not None and isinstance(base.A_s, SplitMatrix):
                    fac = qp_setup_like(base, d)
                else:
                    fac = qp_setup(d, q_ref=plan.c_c)
                if isinstance(fac.A_s, SplitMatrix):
                    plan.fac_base = fac
                    if isinstance(d.A, SplitMatrix):
                        view = ScaledView(fac.A_s, fac.D, fac.E)
                        d = d._replace(A=view)
                        # later modes and pass-3 consumers read the
                        # plan's data through the same view
                        plan.data_c = plan.data_c._replace(A=view)
            else:
                fac = qp_setup(d, q_ref=plan.c_c)
            self._shrink_factors[key] = (fac, d)
        return self._shrink_factors[key]

    def _shrink_dual_fold(self, shrink, w_on, prox_on):
        """The per-iteration dual-bound constant of the compacted
        system (ops/shrink.dual_fold): base fold + this iteration's
        W / prox-center contributions of the folded slots."""
        from ..ops.shrink import dual_fold
        fsx = shrink.fixed_slots_dev
        ws = None if self._w_scale is None else self._w_scale[:, fsx]
        return dual_fold(shrink.c0_fold, self._fixed_vals[:, fsx],
                         self.W[:, fsx], self.xbar[:, fsx],
                         self.rho[:, fsx], ws, w_on=bool(w_on),
                         prox_on=bool(prox_on))

    def _shrink_est_hbm(self, n, m):
        """Roofline traffic estimate for the CURRENT active-set shapes
        (ops/kernels.est_hbm_bytes_per_iter's tail model) — the number
        the ph.iteration shrink block and the bench ``active=`` stamp
        record, so analyze can show per-iteration bytes tracking the
        active set."""
        from ..ops import kernels
        chunk = int(self.options.get("subproblem_chunk", 0)) \
            or self.batch.S
        return int(kernels.est_hbm_bytes_per_iter(
            n=n, m=m, s_chunk=min(chunk, self.batch.S))["tail"])

    def maybe_compact(self, nfixed=None):
        """Active-set compaction trigger (called by DeviceFixer after
        each fixing pass): when the fixed fraction crosses the next
        ``shrink_buckets`` threshold, gather the unfixed columns (and
        the rows they touch) into a smaller packed system, re-factorize
        once, and solve THAT until the next transition. Returns True
        when a compaction happened. No-op unless ``shrink_compact`` is
        enabled and the engine's structure supports it: shared dense A,
        the df32 split representation (SplitMatrix / ScaledView —
        ops/shrink gathers both f32 planes), and streamed sources
        (one out-of-band full restage feeds build_plan, then the host
        store re-blocks at the compacted width). Packed split matvec
        forms and synthesized sources keep the pin-boxes path."""
        if not bool(self.options.get("shrink_compact")):
            return False
        if nfixed is None:
            # lint: ok[SYNC001] compaction trigger outside the fixer: one (S, K) mask read per call, never in the chunk chain
            nfixed = int(np.asarray(self._fixed_mask).all(axis=0).sum())
        st = self._shrink_status
        if st is not None:
            st["fixed"], st["free"] = int(nfixed), \
                self.batch.K - int(nfixed)
        frac = nfixed / max(self.batch.K, 1)
        crossed = [b for b in self._shrink_buckets if b <= frac]
        target = crossed[-1] if crossed else None
        current = self._shrink.bucket if self._shrink is not None else 0.0
        if target is None or target <= current:
            return False
        from ..ops.qp_solver import ScaledView, SplitMatrix
        A_full = self.qp_data.A
        pat = A_full.A_s if isinstance(A_full, ScaledView) else A_full
        dense_ok = isinstance(A_full, jax.Array) \
            and getattr(A_full, "ndim", 0) in (2, 3)
        # packed split forms carry structure-dependent matvec index
        # planes the column gather cannot re-derive — they skip
        split_ok = isinstance(pat, SplitMatrix) and pat.struct is None
        stream = self._stream_source
        stream_ok = stream is None or stream.kind == "streamed"
        if not self._shrink_allowed or not (dense_ok or split_ok) \
                or not stream_ok:
            # unsupported layout/source: fixing still pays off through
            # the pin boxes. Synthesized sources skip (the generator
            # manufactures FULL-width blocks in-kernel; there is no
            # host store to re-block — AlgoConfig.validate already
            # rejects the CLI combination, this guards programmatic
            # options). Booked once per TARGET bucket (the layout
            # stays unsupported every iteration; a per-call count
            # would tally iterations)
            noted = getattr(self, "_shrink_skip_noted", None)
            if noted is None:
                noted = self._shrink_skip_noted = set()
            if target not in noted:
                noted.add(target)
                obs.counter_add("shrink.compaction_skipped")
            return False
        from ..ops import shrink as shrink_ops
        noted = getattr(self, "_shrink_skip_noted", None)
        if noted is None:
            noted = self._shrink_skip_noted = set()
        if target in noted:
            # a plan for this target already failed (all slots fixed /
            # no rows left): build_plan's host staging must not re-run
            # every miditer — the once-per-transition contract
            return False
        qd, c_full = self.qp_data, self.c
        if stream is not None:
            # ONE out-of-band full restage: build_plan folds the TRUE
            # full-width blocks (the engine's resident qp_data carries
            # 2-row setup surrogates under streaming); its bytes book
            # on stream.compacted_restage_bytes, never the
            # per-iteration bytes_shipped flatness signal
            full = stream.stage_full()
            qd = qd._replace(l=full["l"], u=full["u"],
                             lb=full["lb"], ub=full["ub"])
            c_full = full["c"]
        plan = shrink_ops.build_plan(
            qd, c_full, self.c0, self.nonant_idx,
            self._fixed_mask, self._fixed_vals, target,
            dtype=self.dtype,
            ident={"kernel_mode": self.sub_kernel_mode,
                   "precision": self.sub_precision,
                   "chunk": int(self.options.get("subproblem_chunk",
                                                 0))})
        if plan is None:
            noted.add(target)
            obs.counter_add("shrink.compaction_skipped")
            return False
        if stream is not None:
            # re-block the host store at the compacted width, then swap
            # the plan's per-scenario blocks for 2-row setup surrogates
            # over that store — the hot loop keeps staging per chunk,
            # now at the compacted width (the folded full blocks the
            # plan was built with must NOT stay resident; that is the
            # residency streaming exists to bound)
            stream.install_compacted(plan)
            l2, u2, lb2, ub2, c2 = stream.setup_arrays(
                self.dtype, keep_cols=plan.keep_cols_np)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                repl = lambda a: jax.device_put(a, NamedSharding(
                    self.mesh, PartitionSpec(*([None] * a.ndim))))
                l2, u2, lb2, ub2, c2 = (repl(l2), repl(u2), repl(lb2),
                                        repl(ub2), repl(c2))
            plan.data_c = plan.data_c._replace(l=l2, u=u2,
                                               lb=lb2, ub=ub2)
            plan.c_c = c2
        # capture surviving warm iterates BEFORE the invalidation
        # drops them (cross-bucket warm transplant; pulled back by the
        # first state build of the new bucket)
        self._transplant_capture(plan)
        self._shrink = plan
        self._compact_invalidate()
        obs.counter_add("shrink.compactions")
        obs.gauge_set("shrink.active_cols", plan.n_c)
        obs.gauge_set("shrink.active_rows", plan.m_c)
        if st is not None:
            st["compactions"] += 1
            st["bucket"] = plan.bucket
            st["n_cols"], st["m_rows"] = plan.n_c, plan.m_c
            st["est_hbm_bytes_per_iter"] = self._shrink_est_hbm(
                plan.n_c, plan.m_c)
        obs.event("shrink.compaction", {
            "iter": self._iter, "bucket": plan.bucket,
            "fingerprint": plan.fingerprint,
            "n_cols": plan.n_c, "m_rows": plan.m_c,
            "n_full": plan.n_full, "m_full": plan.m_full,
            "fixed_slots": plan.n_fixed_slots,
            "bucket_cached": plan.meta.get("bucket_cached", False)})
        self._trace_note(
            "shrink.note",
            f"shrink: compacted to bucket {plan.bucket:g} — "
            f"{plan.n_c}/{plan.n_full} cols, {plan.m_c}/{plan.m_full} "
            f"rows ({plan.n_fixed_slots} nonants folded out)",
            bucket=plan.bucket, n_cols=plan.n_c, m_rows=plan.m_c)
        return True

    def _compact_invalidate(self):
        """A bucket transition changes every hot-loop solve shape:
        drop all warm state (compacted iterates of the OLD shape can't
        warm-start the new one — states rebuild cold, and the
        near-converged problem re-converges in a handful of ADMM
        iterations), the compacted factor cache, kernel plans, chunk
        plumbing, and recovery bookkeeping. The FULL-system factor
        cache (``_factors``) survives: a transition changes only the
        compacted representation — (A, P, rho) of the full system are
        untouched, and the full=True / fixed-mode consumers (dive,
        cross-scenario, incumbent eval) would otherwise pay a full
        re-factorization per transition for nothing. The transplant
        snapshot (``_transplant_src``, taken by maybe_compact just
        before this runs) deliberately survives — it IS the warm state
        the next bucket's first state build pulls back."""
        self._shrink_factors.clear()
        self._qp_states.clear()
        self._kernel_plans.clear()
        self._chunk_no_retry.clear()
        self._hospital_no_retry.clear()
        self._blacklist_calls.clear()
        self._chunk_donatable.clear()
        self._chunk_dirty.clear()
        getattr(self, "_chunk_idx_cache", {}).clear()
        self._pool_states.clear()
        self._pool_dirty.clear()

    # ---- cross-bucket warm transplant (ops/shrink) ----
    def _transplant_book_cold(self, reason):
        obs.counter_add("shrink.transplant_cold_fallbacks")
        if self._shrink_status is not None:
            self._shrink_status["transplant_cold"] += 1
        obs.event("shrink.transplant_cold",
                  {"iter": self._iter, "reason": reason})

    def _transplant_fields(self, mode):
        """One mode's warm ADMM iterates as full-(S, ·) device arrays,
        or None when the mode has nothing usable cached. Prefers the
        authoritative per-chunk states (concatenated; sharded chunks
        via the mesh's local concat, host chunks with their tail pads
        trimmed), falls back to a genuine full-width QPState (the
        dispatch store doubles as one). _ChunkStateView alone is never
        read: its precomputed x is the EXPANDED unscaled solution
        while its iterates are compacted — not solver state."""
        S = self.batch.S
        fields = ("x", "yA", "yB", "zA", "zB")
        chunk_states = self._qp_states.get(("chunks", mode))
        if chunk_states:
            if self._shard_ops is not None:
                return {f: self._shard_ops.from_chunks(
                            [getattr(s, f) for s in chunk_states])
                        for f in fields}
            cw = chunk_states[0].x.shape[0]
            trims = [r for _, r in self._chunk_index(cw)]
            return {f: jnp.concatenate(
                        [getattr(s, f)[:r]
                         for s, r in zip(chunk_states, trims)])
                    for f in fields}
        st = self._qp_states.get(mode)
        if isinstance(st, QPState) and st.x.shape[0] == S:
            return {f: getattr(st, f) for f in fields}
        return None

    def _transplant_capture(self, plan_new):
        """Snapshot each hot-loop mode's surviving warm iterates at a
        bucket transition, keyed to the NEW plan's fingerprint — the
        invalidation about to run drops every cached state, and
        without this the near-converged problem restarts cold each
        transition. Stores ONLY the iterate arrays plus the old
        factors' scaling vectors (D/E/Eb/cost_scale) and the old
        plan's geometry — never whole factors, which would pin the old
        compacted split pair in HBM across the transition. Scenarios
        the hospital declared incurable (their cached iterates carry
        stale loose solves) are masked out and restart cold."""
        self._transplant_src = None
        if not bool(self.options.get("shrink_transplant", True)):
            return
        S = self.batch.S
        old = self._shrink
        modes = {}
        for mode in (True, False):
            if mode in self._chunk_dirty:
                # a donating pass died mid-flight: the cached iterates
                # reference deleted buffers
                self._transplant_book_cold("dirty donated pass")
                continue
            fields = self._transplant_fields(mode)
            if fields is None:
                continue       # mode never ran — nothing to carry
            ent = None
            if old is not None:
                ent = self._shrink_factors.get(mode) \
                    or next(iter(self._shrink_factors.values()), None)
            else:
                ent = self._factors.get(mode)
            if ent is None:
                self._transplant_book_cold("no source factors")
                continue
            fac = ent[0]
            ok = np.ones(S, bool)
            for g in self._hospital_no_retry.get(mode, ()):
                if g < S:
                    ok[g] = False
            # the state must certify itself: a converged ADMM state has
            # x ≈ zB (box-split consensus) AND A·x ≈ zA (row-split
            # consensus); a diverged row fails at least one. The
            # state's OWN pri_rel rows cannot be trusted here — the
            # hospital scatters its good residual rows back while the
            # device iterates stay diverged (see _hospitalize), so a
            # hospital-frequent scenario reads converged while carrying
            # garbage. Unscale with the donor factors and gate both
            # consensus gaps (one raw matvec per mode per transition).
            from ..ops.qp_solver import _Ax

            def _b2(v):
                # scaling vectors: shared (·,) or per-scenario (S, ·)
                a = np.asarray(v)   # lint: ok[SYNC001] once-per-transition capture gate, outside the chunk chain (the transition refactorizes anyway)
                return a if a.ndim == 2 else a[None, :]

            x_u = np.asarray(fields["x"]) * _b2(fac.D)      # lint: ok[SYNC001] once-per-transition capture gate
            zB_u = np.asarray(fields["zB"]) / _b2(fac.Eb)   # lint: ok[SYNC001] once-per-transition capture gate
            zA_u = np.asarray(fields["zA"]) / _b2(fac.E)    # lint: ok[SYNC001] once-per-transition capture gate
            ax_u = np.asarray(_Ax(ent[1].A, jnp.asarray(x_u)))  # lint: ok[SYNC001] once-per-transition capture gate (the one raw matvec per mode)
            gap_b = np.abs(x_u - zB_u).max(axis=1)
            gap_a = np.abs(ax_u - zA_u).max(axis=1)
            scale = np.maximum.reduce(
                [np.ones(S), np.abs(x_u).max(axis=1),
                 np.abs(ax_u).max(axis=1)])
            gate = max(100 * _hot_eps(bool(mode), self.sub_eps,  # lint: ok[SYNC001] mode is a host bool (the factor-cache key), not a device value
                                      self.sub_eps_hot), 1e-2)
            gap = np.maximum(gap_b, gap_a)
            ok &= np.isfinite(gap) & (gap / scale <= gate)
            modes[mode] = {
                "st": fields,
                "fac": {"D": fac.D, "E": fac.E, "Eb": fac.Eb,
                        "cs": fac.cost_scale},
                "keep_cols": None if old is None else old.keep_cols_np,
                "keep_rows": None if old is None else old.keep_rows_np,
                "shift": None if old is None else old.rhs_shift,
                "ok": ok}
        if modes:
            self._transplant_src = {
                "fingerprint": plan_new.fingerprint, "modes": modes}

    def _transplant_pull(self, key, factors_new):
        """Rescale the captured warm iterates into the CURRENT plan's
        compacted geometry (ops/shrink._transplant_rescale), or None
        when no applicable snapshot exists. Books
        ``shrink.transplant_cold_fallbacks`` only when a snapshot for
        this plan EXISTS but a guard rejects it — a silent None (no
        snapshot, different bucket, fixed-mode key) is not a fallback,
        it is the ordinary cold build."""
        src = getattr(self, "_transplant_src", None)
        plan = self._shrink
        if src is None or plan is None \
                or src["fingerprint"] != plan.fingerprint \
                or not isinstance(key, bool):
            return None
        mode = key if key in src["modes"] else \
            next(iter(src["modes"]), None)
        ent = src["modes"].get(mode)
        if ent is None:
            return None
        new_keep, new_rows = plan.keep_cols_np, plan.keep_rows_np
        old_keep = ent["keep_cols"]
        if old_keep is None:
            old_keep = np.arange(plan.n_full)
        old_rows = ent["keep_rows"]
        if old_rows is None:
            old_rows = np.arange(plan.m_full)
        st = ent["st"]
        # direction-aware width guard: buckets only ever FIX more
        # slots, so the new kept set must nest inside the old one —
        # anything else (re-admitted slots, a rebuilt batch) is not a
        # gather and restarts cold
        if st["x"].shape[-1] != old_keep.size \
                or st["zA"].shape[-1] != old_rows.size \
                or new_keep.size > old_keep.size \
                or new_rows.size > old_rows.size:
            self._transplant_book_cold("width mismatch")
            return None
        if not (np.isin(new_keep, old_keep).all()
                and np.isin(new_rows, old_rows).all()):
            self._transplant_book_cold("active set not nested")
            return None
        pos_c = jnp.asarray(
            np.searchsorted(old_keep, new_keep).astype(np.int32))
        pos_r = jnp.asarray(
            np.searchsorted(old_rows, new_rows).astype(np.int32))
        shift_old = ent["shift"]
        if shift_old is None:
            # full-width source: the full system has no rhs fold —
            # a (1, m_full) zero row broadcasts over scenarios
            shift_old = jnp.zeros((1, int(plan.m_full)),
                                  plan.rhs_shift.dtype)
        fac_o = ent["fac"]
        cs_ratio = factors_new.cost_scale / fac_o["cs"]
        from ..ops.shrink import _transplant_rescale
        x_n, yA_n, yB_n, zA_n, zB_n = _transplant_rescale(
            st["x"], st["yA"], st["yB"], st["zA"], st["zB"],
            pos_c, pos_r, fac_o["D"], factors_new.D,
            fac_o["E"], factors_new.E, fac_o["Eb"], factors_new.Eb,
            cs_ratio, shift_old, plan.rhs_shift,
            jnp.asarray(ent["ok"]))
        obs.counter_add("shrink.transplants")
        if self._shrink_status is not None:
            self._shrink_status["transplants"] += 1
        obs.event("shrink.transplant", {
            "iter": self._iter, "mode": _mode_str(mode),
            "bucket": plan.bucket, "n_cols": plan.n_c,
            "cold_rows": int((~ent["ok"]).sum())})
        return {"x": x_n, "yA": yA_n, "yB": yB_n,
                "zA": zA_n, "zB": zB_n}

    def _mesh_combine(self, ent, xn, prob, xbar_w, W, rho, wmask):
        """A sharded engine's consensus reduce (``_ph_combine``'s
        contract through ``ShardedScenarioOps.combine``), under its own
        span inside ``ph.reduce`` and booked in ``ent["collective"]``
        beside the call's seconds: one combine and the bytes its psums
        reduce (``combine_collective_bytes``), with or without a
        telemetry session."""
        ops = self._shard_ops
        with obs.span("ph.reduce.combine", cat="ph"):
            out = ops.combine(xn, prob, xbar_w, W, rho, wmask)
        ent["collective"]["combines"] += 1
        ent["collective"]["bytes"] += ops.combine_collective_bytes(
            xn.dtype.itemsize)
        return out

    # ---- three engines in one HBM (doc/cylinders.md §residency) ----
    def residency(self):
        """What this engine keeps on the device that matters at UC
        width, and who else holds it: ``shared`` names the immutable
        operands that are ONE buffer for every engine of the wheel
        (through ``batch._dev_cache``, under its lock: the scaled split
        matrix with its packed form, and the per-scenario data blocks
        ``l u lb ub c``), ``private`` the solve modes whose warm states
        hold an (n, n) factor of their own (a factor follows its
        engine's own rho: sharing one would change results), ``freed``
        the modes this engine has dropped (``_free_mode``)."""
        cache = getattr(self.batch, "_dev_cache", None) or {}
        view = cache.get(("A", str(self.dtype), True))
        shared = []
        if view is not None and getattr(self.qp_data.A, "A_s", None) \
                is view.A_s:
            shared.append("A_s")
        shared += [f for f in ("l", "u", "lb", "ub")
                   if cache.get((f, str(self.dtype)))
                   is getattr(self.qp_data, f)]
        private = sorted(
            {_mode_str(k[1]) for k, v in self._qp_states.items()
             if isinstance(k, tuple) and k and k[0] == "chunks" and v}
            | {_mode_str(k) for k, v in self._qp_states.items()
               if isinstance(v, QPState)}
            | ({"pool"} if self._pool_states else set()))
        return {"shared": shared, "private": private,
                "freed": sorted(self._freed_modes)}

    def _free_mode(self, key):
        """Drop a solve mode's warm states, and with them its (n, n)
        factor (0.68 GB at n = 13,056) and its rows: what an engine of
        a wheel can no longer use (the hub's iter-0 mode once hot
        iterations run). Safe at any time: a later pass of the mode, or
        ``reset_run()``, rebuilds cold states through the compiled
        builders."""
        if self._qp_states.pop(("chunks", key), None) is not None \
                or key in self._qp_states:
            self._freed_modes.add(_mode_str(key))
        self._qp_states.pop(key, None)
        self._qp_states.pop(("dispatch", key), None)
        self._chunk_donatable.discard(key)
        self._chunk_dirty.discard(key)

    def _take_flowed_factor(self, states, on):
        """At the start of a DONATING df32 pass of an engine of a
        wheel: the one (n, n) factor every chunk state of the mode
        shares between passes, taken OUT of ``states`` (they keep a
        placeholder until the pass's unify re-attaches the flow's last
        factor). The fused program never donates its factor (the flow
        shares it), so every chunk solve hands back a fresh one, and
        the inherited factor would stay alive in the not-yet-solved
        chunks' states for the whole pass: two factors an engine, 0.68
        GB each at n = 13,056, where three engines share one HBM (the
        pool's pass is a round of ~50 s). Taken out, it is freed as
        soon as the first chunk's solve has used it. A donating pass
        has marked its states dirty until their successors are stored,
        so a pass that dies mid-flight rebuilds cold and never meets a
        stripped state. Returns the factor for the first chunk's solve;
        None (and nothing changes) outside a wheel or on a pass that
        does not donate."""
        if not on or self._wheel_port is None:
            return None
        L0 = states[0].L
        bare = jnp.zeros((), jnp.float32)
        for ci in range(len(states)):
            states[ci] = states[ci]._replace(L=bare)
        return L0

    def _wheel_turn(self, rows, clock=None):
        """One device solve of ``rows`` scenario rows as ONE turn of the
        wheel's arbiter (doc/cylinders.md): admitted in the wheel's
        fixed order, held until the caller leaves the block, which it
        does once the solve's outputs are ready (``_wheel_ready``).
        The wait for the turn is booked by the arbiter
        (``wheel.queue_wait``), not by ``clock``'s open phase. Outside
        an in-process wheel with spokes this is a no-op."""
        port = self._wheel_port
        if port is None:
            return contextlib.nullcontext()
        return port(rows) if clock is None else clock.outside(port(rows))

    def _wheel_ready(self, out):
        """Inside a turn: wait for the solve just enqueued, so that the
        turn ends when the device is free again (an engine with no port
        never blocks here: its chunk solves stay pipelined)."""
        if self._wheel_port is not None:
            # lint: ok[SYNC001] the wheel's admission grain: one chunk solve in flight (utils/runtime.WheelArbiter)
            jax.block_until_ready(out)

    def _cold_state(self, factors, d, build_log=None):
        """``qp_cold_state`` with the placement every later solve hands
        back (``build_log``: where the build of a per-scenario float64
        inverse is added up, the mode's ``KernelPlan.f64_build``). On a
        mesh the solve programs return their per-row fields row-sharded over "scen"; a cold state's zeros come out of their
        own jit REPLICATED, and a solve program called once with each
        is lowered and compiled twice (at UC width on a four-chip v5e
        host 111 s and 83 s, with ~11 GiB of host memory each: the
        fused program's second compile was a quarter of a cold
        set-up). So the rows are sharded here, a local slice per
        device; the factor, rho and the counters stay replicated, as
        the solves return them. One-device engines and row counts the
        mesh does not divide (the hospital's few rows) pass through
        untouched."""
        st = qp_cold_state(factors, d, build_log)
        ops = self._shard_ops
        if ops is None or st.x.shape[0] % ops.n_devices:
            return st
        from ..parallel.mesh import shard_arrays
        return st._replace(**shard_arrays(
            ops.mesh, {f: getattr(st, f) for f in _ChunkStateView._FIELDS}))

    def _ensure_state(self, prox_on=True, fixed=False):
        """Per-mode solver state (the KKT factor depends on the prox term);
        x/y/z warm-start across modes. Always returns a genuine QPState:
        a chunked solve stores a lazy _ChunkStateView at this key, which
        satisfies the read-only consumers but not the solver's
        ``_replace`` contract — materialize it (fresh factor, the view's
        iterates as warm start) before handing it out."""
        key = ("fixed", bool(prox_on)) if fixed else bool(prox_on)
        self._drop_if_dirty(key)
        st = self._qp_states.get(key)
        if isinstance(st, _ChunkStateView):
            factors, d = self._get_factors(prox_on, fixed)
            cold = self._cold_state(factors, d)
            if st.x.shape[-1] == cold.x.shape[-1] \
                    and st.zA.shape[-1] == cold.zA.shape[-1]:
                st = cold._replace(
                    x=st.x, yA=st.yA, yB=st.yB, zA=st.zA, zB=st.zB)
            else:
                # a shrink-era view's precomputed x is EXPANDED while
                # its iterates are compacted — same-era widths are not
                # transplantable; a cross-BUCKET snapshot may still be
                # (the warm transplant), else start cold
                tp = self._transplant_pull(key, factors)
                if tp is not None \
                        and tp["x"].shape == cold.x.shape \
                        and tp["zA"].shape == cold.zA.shape:
                    st = cold._replace(**tp)
                else:
                    st = cold
            self._qp_states[key] = st
            return st
        if key not in self._qp_states:
            factors, d = self._get_factors(prox_on, fixed)
            st = self._cold_state(
                factors, d, self._kernel_plan(
                    key, factors, self._rows_per_call()).f64_build)
            other = next((v for k, v in self._qp_states.items()
                          if k != key and k not in self._chunk_dirty
                          and isinstance(v, (QPState, _ChunkStateView))),
                         None)
            if other is not None and other.x.shape == st.x.shape \
                    and other.zA.shape == st.zA.shape:
                # transplant the other mode's iterates as a warm start
                # (buffers are never donated — sharing them is safe)
                st = st._replace(x=other.x, yA=other.yA, yB=other.yB,
                                 zA=other.zA, zB=other.zB)
            else:
                # no same-width sibling: a captured cross-bucket
                # snapshot (maybe_compact -> _transplant_capture) warm
                # starts the new compacted geometry instead of cold
                tp = self._transplant_pull(key, factors)
                if tp is not None and tp["x"].shape == st.x.shape \
                        and tp["zA"].shape == st.zA.shape:
                    st = st._replace(**tp)
            self._qp_states[key] = st
        return self._qp_states[key]

    def _drop_if_dirty(self, key):
        """A previous DONATING chunked pass of ``key`` died between
        consuming its warm-start buffers (pass 1) and storing their
        successors (pass 3): every cached state/view of that mode
        references DELETED arrays. Drop them so any consumer — the
        mode's own re-run, another mode's warm-start transplant, a
        view reader — rebuilds cold instead of crashing."""
        if key in self._chunk_dirty:
            self._qp_states.pop(("chunks", key), None)
            self._qp_states.pop(("dispatch", key), None)
            self._qp_states.pop(key, None)
            self._chunk_dirty.discard(key)
            self._chunk_donatable.discard(key)

    # ------------- scenario microbatching -------------
    def _chunk_index(self, chunk):
        """Per-chunk scenario index arrays, every one exactly ``chunk``
        long: a ragged final chunk would force a second XLA compile of
        every solve program for the odd shape (minutes per UC-width
        program: 160 s AOT for the v5e, CHANGES.md PR 24), so the tail
        is padded by REPEATING its
        last scenario — the duplicate rows solve redundantly and their
        outputs are trimmed before the global reduce."""
        S = self.batch.S
        if not hasattr(self, "_chunk_idx_cache"):
            self._chunk_idx_cache = {}
        # keyed by (chunk, S): an entry keyed by chunk alone would
        # silently survive batch mutation and re-target wrong scenarios
        if (chunk, S) not in self._chunk_idx_cache:
            out = []
            for i in range(0, S, chunk):
                idx = np.arange(i, min(i + chunk, S))
                real = idx.size
                if real < chunk:
                    idx = np.concatenate(
                        [idx, np.full(chunk - real, idx[-1])])
                out.append((jnp.asarray(idx), real))
            self._chunk_idx_cache[(chunk, S)] = out
        return self._chunk_idx_cache[(chunk, S)]

    def _chunk_ids(self, chunk):
        """``_chunk_index``'s id arrays stacked (n_chunks, chunk) on the
        device: the staging program's one ids operand (same cache, same
        invalidation)."""
        slices = self._chunk_index(chunk)
        key = ("stacked", chunk, self.batch.S)
        if key not in self._chunk_idx_cache:
            self._chunk_idx_cache[key] = jnp.stack(
                [idx for idx, _ in slices])
        return self._chunk_idx_cache[key]

    def _chunk_row_map(self, slices=None, layout=None):
        """``_row_map`` of a FULL pass's layout, cached beside the
        chunk index it is read from (same invalidation): the chunked
        loop's ``slices`` under their ``layout`` key (host chunks or a
        mesh's strided chunks, and their size), or, with none, the
        un-chunked body's one "chunk" of all S rows."""
        if not hasattr(self, "_chunk_idx_cache"):
            self._chunk_idx_cache = {}
        key = ("rowmap", layout, self.batch.S)
        if key not in self._chunk_idx_cache:
            if slices is None:
                ids, reals = np.arange(self.batch.S)[None, :], [self.batch.S]
            else:
                # lint: ok[SYNC001] layout read once per chunk layout (cached above), never per iteration
                ids = np.stack([np.asarray(idx) for idx, _ in slices])
                reals = [real for _, real in slices]
            self._chunk_idx_cache[key] = _row_map(ids, reals, self._S_orig)
        return self._chunk_idx_cache[key]

    def _book_call_exits(self, ent, solve0, tests, its, res=None,
                         ids=None, live=None):
        """``_book_exits`` of one ``solve_loop`` call into ``ent``
        (``tests``: ``_exit_tests`` of the keyword values the call
        hands ``_solver_call``): the call's solve seconds are what
        ``ent``'s solve accumulator gained since ``solve0``, and the
        per-scenario tally behind
        ``scenarios_over`` / ``top`` is (S,) on the host, held by the
        entry (so it resets with the seconds)."""
        ex = ent["exits"]
        if ex["tally"] is None:
            ex["tally"] = np.zeros(self._S_orig, np.int64)
        if res is not None and ids is None:
            ids, live = self._chunk_row_map()
        return _book_exits(ex, its, tests, ent["acc"]["solve"] - solve0,
                           res, ids, live)

    def _ensure_chunk_states(self, key, factors, data, slices,
                             lc=None, cold_data=None):
        """Per-chunk QPStates (each owns its L / rho_scale trajectory —
        cross-chunk sharing would let one chunk's rho adaptation corrupt
        another's warm start). Authoritative store for chunked mode;
        self._qp_states[key] holds a concatenated read-only view.

        ``lc`` (sharded mode): the local chunk rows — warm-start
        transplants restage through ``to_chunks`` and slice locally
        instead of gathering strided global indices. ``cold_data``:
        one chunk-shaped data block for the cold state's shape (the
        mesh's chunk 0, or a streamed source's first block).

        New modes transplant iterates from any existing mode's
        concatenated view, exactly like _ensure_state: a cold prox-off
        start would cost thousands of ADMM iterations of certified-
        bound tightness every Lagrangian pass."""
        ck = ("chunks", key)
        if ck not in self._qp_states:
            other = next((v for k, v in self._qp_states.items()
                          if k != ck and k not in self._chunk_dirty
                          and isinstance(v, (QPState, _ChunkStateView))),
                         None)
            states = []
            # ONE cold state serves every chunk: qp_cold_state is zero
            # iterates + a factor, data-dependent in SHAPE only (chunk
            # shapes are identical), and immutable buffers make the
            # sharing safe — at df32 scale each per-chunk factor copy
            # would cost ~0.7 GB x chunk count
            if cold_data is not None:
                # the caller staged one chunk-shaped block (a mesh's
                # local slices; a streamed/synthesized source, whose
                # data itself is a 2-row setup surrogate with nothing
                # to slice)
                d0 = cold_data
            else:
                idx0 = slices[0][0]
                d0 = data._replace(l=data.l[idx0], u=data.u[idx0],
                                   lb=data.lb[idx0], ub=data.ub[idx0])
            st0 = self._cold_state(factors, d0)
            oth_ch = None
            transplant = other is not None \
                and other.x.shape[0] == self.batch.S \
                and other.zA.shape[1] == st0.zA.shape[1] \
                and other.x.shape[-1] == st0.x.shape[-1]
            #   (the width check matters under compaction: a shrink
            #   view's precomputed x is EXPANDED to full width while
            #   its solver states are compacted — full iterates must
            #   never transplant into a compacted cold state)
            tp = None
            if not transplant:
                # no same-width sibling mode: try the cross-bucket
                # warm transplant (the snapshot maybe_compact captured
                # before invalidating) — post-transition re-convergence
                # from warm iterates instead of cold zeros
                tp = self._transplant_pull(key, factors)
                if tp is not None and (
                        tp["x"].shape[-1] != st0.x.shape[-1]
                        or tp["zA"].shape[-1] != st0.zA.shape[-1]):
                    tp = None
            if transplant and lc is not None:
                oth_ch = self._shard_ops.to_chunks(
                    {"x": other.x, "yA": other.yA, "yB": other.yB,
                     "zA": other.zA, "zB": other.zB}, lc)
            elif tp is not None and lc is not None:
                oth_ch = self._shard_ops.to_chunks(tp, lc)
            for ci, (idx, _) in enumerate(slices):
                st = st0
                if transplant or tp is not None:
                    if oth_ch is not None:
                        st = st._replace(
                            x=oth_ch["x"][ci], yA=oth_ch["yA"][ci],
                            yB=oth_ch["yB"][ci], zA=oth_ch["zA"][ci],
                            zB=oth_ch["zB"][ci])
                    elif tp is not None:
                        st = st._replace(
                            x=tp["x"][idx], yA=tp["yA"][idx],
                            yB=tp["yB"][idx], zA=tp["zA"][idx],
                            zB=tp["zB"][idx])
                    else:
                        st = st._replace(
                            x=other.x[idx], yA=other.yA[idx],
                            yB=other.yB[idx], zA=other.zA[idx],
                            zB=other.zB[idx])
                states.append(st)
            self._qp_states[ck] = states
        return self._qp_states[ck]

    def _dispatch_store(self, key, factors, data, slices, stream):
        """Full-width per-scenario solver-state store for dispatch-
        masked passes (APH φ-dispatch, doc/aph.md). The positional
        per-chunk states of the full pass can't warm-start a layout
        that re-partitions every iteration, so partial passes keep ONE
        (S, ·) row store: chunk states gather their rows on the way in,
        successors scatter back after pass 3. Seeded from the last
        full pass's chunk states (their SCALED iterates, trimmed of
        chunk pads); cold zeros when none exist (post-compaction /
        post-rho-invalidation — the same cold restart a rebuilt chunk
        state takes). L / rho_scale are shared-mode scalars here
        (chunking requires shared A) and flow like the split loop's."""
        dk = ("dispatch", key)
        st = self._qp_states.get(dk)
        S = self.batch.S
        if isinstance(st, QPState) and st.x.shape[0] == S:
            return st
        chunk_states = self._qp_states.get(("chunks", key))
        if chunk_states:
            cw = chunk_states[0].x.shape[0]
            trims = [r for _, r in self._chunk_index(cw)]

            def catf(f):
                return jnp.concatenate(
                    [getattr(s, f)[:r]
                     for s, r in zip(chunk_states, trims)])

            st = chunk_states[-1]._replace(
                **{f: catf(f) for f in _STORE_ROWS})
        else:
            if stream is not None:
                # one chunk-shaped block for the cold template (direct
                # fetch, once per store rebuild — never steady-state)
                b0 = stream.fetch(0)
                d0 = data._replace(l=b0["l"], u=b0["u"],
                                   lb=b0["lb"], ub=b0["ub"])
            else:
                idx0 = slices[0][0]
                d0 = data._replace(l=data.l[idx0], u=data.u[idx0],
                                   lb=data.lb[idx0], ub=data.ub[idx0])
            st0 = qp_cold_state(factors, d0)

            def zf(a):
                return jnp.zeros((S,) + a.shape[1:], a.dtype)

            st = st0._replace(
                **{f: zf(getattr(st0, f)) for f in _STORE_ROWS})
        self._qp_states[dk] = st
        return st

    def _local_chunk(self, chunk):
        """Per-device chunk rows for the sharded chunked loop:
        ``subproblem_chunk`` bounds the per-device microbatch, and the
        local chunk size is rounded so every chunk is a full local
        slice of every shard (core/spbase pads S from the same shared
        formula, so lc always divides the shard)."""
        from ..parallel.mesh import local_chunk_layout
        return local_chunk_layout(self._shard_ops.shard_size, chunk)[1]

    def _sharded_chunk_slices(self, lc):
        """(global_scenario_ids, rows) per sharded chunk — the gate /
        hospital / trace bookkeeping map for the strided chunk layout
        (chunk ci = local rows [ci*lc, (ci+1)*lc) of EVERY shard).
        Cached beside the host chunk index (same invalidation)."""
        ops = self._shard_ops
        n_chunks, ce = ops.chunk_layout(lc)
        if not hasattr(self, "_chunk_idx_cache"):
            self._chunk_idx_cache = {}
        key = ("sharded", lc, self.batch.S)
        if key not in self._chunk_idx_cache:
            self._chunk_idx_cache[key] = [
                (ops.chunk_global_index(ci, lc), ce)
                for ci in range(n_chunks)]
        return self._chunk_idx_cache[key]

    def _per_scen_operands(self, data, shrink=None, c0fold=None,
                           stream=False):
        """Every per-scenario operand of one chunked pass by name, full
        width over the scenario axis: VECTORS only (never ``data``, the
        factor or a packed matrix), so the dict may cross a jit
        boundary. A resident pipelined pass hands it to ONE staging
        program (``_ph_stage_chunks``; on a mesh
        ``ShardedScenarioOps.map_chunks``); the per-chunk paths take
        chunk ci's rows name by name (host: ``per[name][ids]``;
        sharded: ``to_chunks(per, lc)[name][ci]``).

        With an active shrink plan the assemble-side operands are the
        COMPACTED system (data is already compacted by
        _shrink_get_factors; the (S, K) hub blocks gather to the free
        slots), while the objective-side operands stay FULL width
        (``cF``/``WF``) — pass 3 expands each chunk's solution before
        evaluating them, so objectives remain bit-comparable with the
        uncompacted wheel."""
        if stream:
            # streamed/synthesized source: l/u/lb/ub/c arrive per
            # chunk from the source (with the chunk-row sharding), and
            # the shared P row broadcasts in the objective jit — only
            # the RESIDENT small state restages here
            per_scen = {"c0": self.c0, "W": self.W, "xbar": self.xbar,
                        "rho": self.rho, "fm": self._fixed_mask,
                        "fv": self._fixed_vals}
            if self._w_scale is not None:
                per_scen["ws"] = self._w_scale
            if shrink is not None:
                # compacted streamed pass: assemble-side hub blocks
                # gather to the free slots (the source ships compacted
                # l/u/lb/ub and FULL-width c); pass 3 keeps the full
                # W plus the fold constants for the expanded
                # objectives / compacted dual bound
                fs = shrink.free_slots_dev
                per_scen.update(
                    {"W": self.W[:, fs], "xbar": self.xbar[:, fs],
                     "rho": self.rho[:, fs],
                     "fm": self._fixed_mask[:, fs],
                     "fv": self._fixed_vals[:, fs],
                     "WF": self.W, "c0fold": c0fold,
                     "fvcols": shrink.fixed_colvals})
                if self._w_scale is not None:
                    per_scen["ws"] = self._w_scale[:, fs]
            return per_scen
        per_scen = {"l": data.l, "u": data.u, "lb": data.lb,
                    "ub": data.ub, "c0": self.c0, "P0": self.P_diag}
        if shrink is None:
            per_scen.update(
                {"c": self.c, "W": self.W, "xbar": self.xbar,
                 "rho": self.rho, "fm": self._fixed_mask,
                 "fv": self._fixed_vals})
            if self._w_scale is not None:
                per_scen["ws"] = self._w_scale
        else:
            fs = shrink.free_slots_dev
            per_scen.update(
                {"c": shrink.c_c, "W": self.W[:, fs],
                 "xbar": self.xbar[:, fs], "rho": self.rho[:, fs],
                 "fm": self._fixed_mask[:, fs],
                 "fv": self._fixed_vals[:, fs],
                 "cF": self.c, "WF": self.W,
                 "c0fold": c0fold,
                 "fvcols": shrink.fixed_colvals})
            if self._w_scale is not None:
                per_scen["ws"] = self._w_scale[:, fs]
        return per_scen

    def _solve_loop_chunked(self, chunk, w_on, prox_on, update, fixed,
                            dispatch=None):
        """Host-looped scenario microbatching: S scenarios solved in
        ceil(S/chunk) shared-factor kernel calls, then one global
        membership reduce. This is the single-chip path to the
        1000-scenario north star (ref. paperruns/larger_uc/
        1000scenarios_wind): solver-grade (mixed-precision) solves are
        stable at <=128 scenarios per device call on current TPU
        runtimes, while the cross-scenario reductions are cheap at any
        S. Requires shared structure (one A / P across scenarios — the
        representation that makes single-factor chunking exact).

        PIPELINED DISPATCH (default; ``subproblem_pipeline=0`` opts
        back into the plain sequential loop for debugging): the loop is
        staged so host work and device solves overlap instead of
        strictly alternating —
         - ASSEMBLE: every chunk's (q, bounds) is enqueued up front, so
           per-chunk host assembly cost hides behind device compute
           instead of sitting on the critical path before each solve;
         - SOLVE: on a >1-device mesh every chunk is SHARDED over the
           "scen" axis (chunk ci = local rows [ci*lc, (ci+1)*lc) of
           every device's shard, staged by one jitted local reshape —
           parallel/mesh.ShardedScenarioOps): each microbatch solve is
           ONE SPMD program with all devices solving lc scenarios and
           the in-solve residual/convergence reductions riding psum —
           no per-chunk device_put, no per-device host threads (the
           round-robin spreading this replaces is documented as
           superseded in doc/pipelining.md; anatomy in
           doc/sharding.md). Split (df32) chunks keep the sequential
           factor flow in both layouts. Warm-start states are DONATED
           to the solver after the first pass (see
           qp_solver._qp_solve_jit_donated) so per-segment factor
           copies alias instead of duplicating;
         - GATE: the recovery/hospital decisions read ONE stacked
           residual matrix — a single D2H transfer per PH iteration
           instead of one blocking sync per chunk (or per device).
        Per-phase wall-clock and sync counts land in
        ``phase_timing()`` and, when telemetry is configured (obs),
        as Chrome-trace spans + counters (doc/observability.md)."""
        key = ("fixed", bool(prox_on)) if fixed else bool(prox_on)
        factors, data = self._get_factors(prox_on, fixed)
        if dispatch is None:
            # a full-width chunked pass supersedes this mode's dispatch
            # store (see _dispatch_store: it re-seeds from the pass's
            # full-width view on the next partial pass)
            self._qp_states.pop(("dispatch", key), None)
        if factors.A_s.ndim != 2:
            raise ValueError(
                "subproblem_chunk requires a shared-structure batch "
                "(every scenario must carry the same A and P; "
                "per-scenario matrices need per-scenario factors and "
                "gain nothing from chunking)")
        # active-set compaction (ops/shrink): hot-loop modes solve the
        # compacted system (data/factors above are already compacted);
        # the (S, K) hub blocks gather to the free slots for assembly
        # and pass 3 expands solutions back to full width
        shrink = self._shrink if not fixed else None
        idx_asm = shrink.idx_c if shrink is not None else self.nonant_idx
        c0fold = None if shrink is None else self._shrink_dual_fold(
            shrink, w_on, prox_on)
        stream = self._stream_source
        ops = self._shard_ops
        sharded = ops is not None
        pipeline = bool(int(self.options.get("subproblem_pipeline", 1)))
        # a resident pipelined pass stages EVERY chunk's operands with
        # ONE device program (_ph_stage_chunks / ops.map_chunks). Two
        # inputs keep the per-chunk spelling: a streamed source, whose
        # double buffer bounds how many staged chunks exist, and the
        # sequential opt-out, the tests' reference
        stage_all = pipeline and stream is None
        stage_kw = dict(w_on=bool(w_on), prox_on=bool(prox_on))
        # one shared args dict per call (never mutated): lets trace
        # consumers split phase spans by solve mode, allocated only
        # when telemetry is on
        sp_args = {"mode": _mode_str(key)} if obs.enabled() else None
        restage_s = 0.0
        staged = chs = lc = None
        if sharded:
            lc = self._local_chunk(chunk)
            slices = self._sharded_chunk_slices(lc)
            # the mesh's restaging IS assembly: a ph.assemble span of
            # its own (the pass's clock starts below, after the state
            # and plan look-ups), booked with the pass's assemble seconds
            with obs.span("ph.assemble", cat="ph", args=sp_args) as sp:
                per = self._per_scen_operands(data, shrink, c0fold,
                                              stream is not None)
                if stage_all:
                    staged = ops.map_chunks(
                        ("ph.stage", *stage_kw.values()),
                        partial(_stage_chunk, **stage_kw), per, lc,
                        idx_asm)
                else:
                    chs = ops.to_chunks(per, lc)
            restage_s = sp.seconds
        else:
            if dispatch is None:
                slices = self._chunk_index(chunk)
            else:
                # dispatch-masked pass (APH φ-dispatch, doc/aph.md):
                # microbatch ONLY the dispatched ids — ceil(scnt/chunk)
                # device calls instead of ceil(S/chunk). Chunks keep
                # the full ``chunk`` width (same solve program as the
                # full pass — zero new solve compiles); the tail pads
                # by repeating the last id, exactly the _chunk_index
                # convention, so duplicate scatter rows carry identical
                # values. Scatter-back programs compile per chunk
                # COUNT — the bucket registry proves compiles track
                # bucket transitions, not iterations.
                from ..ops import dispatch as dispatch_ops
                # lint: ok[SYNC001] host id list (np.flatnonzero of the already-read gate row), not a device value
                didx = np.asarray(dispatch, dtype=np.int64).ravel()
                scnt = int(didx.size)
                if scnt == 0:
                    raise ValueError("dispatch id list is empty")
                n_dchunks = -(-scnt // chunk)
                pad_n = n_dchunks * chunk - scnt
                ids_pad = np.concatenate(
                    [didx, np.full(pad_n, didx[-1])]) if pad_n else didx
                # the pass's ONE id upload: the operand of the staging
                # program, of the store's gather and of the placement
                ids_stack = jnp.asarray(ids_pad.reshape(n_dchunks, chunk))
                # a staged pass reads its rows by chunk number and keeps
                # the host's ids; the per-chunk paths (a streamed
                # source, the sequential opt-out) index by device ids
                slices = [(ids_pad[i * chunk:(i + 1) * chunk] if stage_all
                           else ids_stack[i],
                           min(chunk, scnt - i * chunk))
                          for i in range(n_dchunks)]
                new_bucket = dispatch_ops.register_bucket({
                    "n_chunks": n_dchunks, "chunk": chunk,
                    "S": self.batch.S, "mode": _mode_str(key),
                    "shrink": None if shrink is None else shrink.bucket,
                    "stream": stream is not None})
                skipped = max(self._S_orig - scnt, 0)
                obs.counter_add("dispatch.solved_scenarios", scnt)
                obs.counter_add("dispatch.skipped_scenarios", skipped)
            per = self._per_scen_operands(data, shrink, c0fold,
                                          stream is not None)

        def rows(name, ci):
            """Chunk ci's rows of per-scenario operand ``name``: a
            tuple read on a staged pass, one eager index launch on the
            per-chunk paths."""
            if staged is not None:
                return staged[ci][name]
            return chs[name][ci] if sharded else per[name][slices[ci][0]]

        def chunk_data(ci):
            # chunk ci's data block (before the assembly pins lb / ub
            # on the per-chunk paths, after it on a staged pass)
            return data._replace(l=rows("l", ci), u=rows("u", ci),
                                 lb=rows("lb", ci), ub=rows("ub", ci))

        cold_d = None
        if stream is not None:
            # bind the source to THIS layout: chunk ci's global
            # scenario rows in chunk-row order — exactly the gate/
            # hospital slice maps. The id conversion is gated on an
            # actual layout change (once per (chunk, S), never
            # steady-state — the per-call spelling would be a small
            # D2H per iteration).
            if dispatch is not None:
                # dispatch-driven staging: bind to THIS iteration's id
                # set so the source stages ONLY the dispatched chunks —
                # the composition ROADMAP item 3 names. The sequence
                # number makes every partial pass a fresh layout (the
                # id set changes with φ); the per-pass pipeline rebuild
                # is host thread churn, amortized by the chunks NOT
                # staged.
                self._dispatch_bind_seq = \
                    getattr(self, "_dispatch_bind_seq", 0) + 1
                lkey = ("dispatch", chunk, self.batch.S,
                        self._dispatch_bind_seq)
                if shrink is not None:
                    lkey = lkey + ("compact", shrink.fingerprint)
                stream.bind(lkey, [ids_pad[i * chunk:(i + 1) * chunk]
                                   for i in range(n_dchunks)],
                            compacted=shrink is not None)
            else:
                lkey = (("sharded", lc, self.batch.S) if sharded
                        else ("host", chunk, self.batch.S))
                if shrink is not None:
                    # the store WIDTH is part of the layout: a bucket
                    # transition (new fingerprint) must re-bind even
                    # when the chunk geometry is unchanged, and a
                    # fixed-mode full-width pass must never share a
                    # compacted bind
                    lkey = lkey + ("compact", shrink.fingerprint)
                if stream.bound_key != lkey:
                    # lint: ok[SYNC001] layout staging once per chunk-layout change (guarded by bound_key above), never per iteration
                    arrs = [np.asarray(idx) for idx, _ in slices]
                    stream.bind(lkey, arrs,
                                compacted=shrink is not None)
        self._drop_if_dirty(key)
        if dispatch is not None:
            # full-width per-scenario warm store: per-chunk positional
            # states can't serve a layout that re-partitions every
            # iteration, so dispatch passes gather their chunk states
            # from one (S, ·) row store and scatter successors back
            states = None
            dstore = self._dispatch_store(key, factors, data, slices,
                                          stream)
        else:
            dstore = None
            if stream is not None \
                    and ("chunks", key) not in self._qp_states:
                # cold chunk states need one chunk-shaped data block; a
                # direct fetch outside the pipeline's in-order pass
                # (once per mode rebuild, never steady-state)
                b0 = stream.fetch(0)
                cold_d = data._replace(l=b0["l"], u=b0["u"],
                                       lb=b0["lb"], ub=b0["ub"])
            fresh_states = ("chunks", key) not in self._qp_states
            if fresh_states and sharded and cold_d is None:
                # the mesh's cold states take their shape from chunk
                # 0's rows of the restaged store
                cold_d = chunk_data(0)
            states = self._ensure_chunk_states(key, factors, data, slices,
                                               lc=lc, cold_data=cold_d)
            if fresh_states:
                # rebuilt chunk states share cold-state buffers —
                # donation must wait for the first completed pass to
                # privatize them
                self._chunk_donatable.discard(key)
        if dispatch is not None:
            from ..ops.dispatch import gather_chunks
            # the warm states' way in is assembly, as the mesh's
            # restage is: a span of its own, booked with the pass's
            # assemble seconds below. ONE program gathers every chunk's
            # rows of every row field.
            with obs.span("ph.dispatch.gather", cat="ph",
                          args=sp_args) as sp:
                states = [dstore._replace(**dict(zip(_STORE_ROWS, rows_c)))
                          for rows_c in gather_chunks(
                              tuple(getattr(dstore, f)
                                    for f in _STORE_ROWS), ids_stack)]
            restage_s = sp.seconds
        polish_chunk = int(self.options.get("subproblem_polish_chunk", 0))
        from ..ops.qp_solver import SplitMatrix
        split_mode = isinstance(factors.A_s, SplitMatrix)
        # kernel plan for THIS mode's factors at this call's PER-DEVICE
        # batch rows: fused plans route each chunk solve through one
        # device program; recovery and the hospital below always clear
        # it (they ARE the full-precision segmented fallback —
        # doc/kernels.md). Sharded solves hand lc, not lc*n_devices:
        # the L⁻¹ build replicates on every device while the applies
        # are sharded, so per-device break-even is what the
        # profitability check must see (l_inv_profitable).
        rows_per_call = lc if sharded else chunk
        plan = self._kernel_plan(key, factors, rows_per_call)
        kw = dict(prox_on=bool(prox_on), precision=self.sub_precision,
                  sub_max_iter=self.sub_max_iter, sub_eps=self.sub_eps,
                  sub_eps_hot=self.sub_eps_hot,
                  sub_eps_dua_hot=self.sub_eps_dua_hot,
                  tail_iter=self.sub_tail_iter,
                  stall_rel=self.sub_stall_rel, segment=self.sub_segment,
                  polish_hot=self.sub_polish_hot,
                  polish_chunk=polish_chunk,
                  segment_lo=self.sub_segment_lo,
                  ir_sweeps=self.sub_ir_sweeps, kernel=plan)
        # dispatch passes never donate: every gathered chunk state
        # aliases the dispatch store's single flowed factor, so the
        # first donated solve would delete the buffer chunk 2 needs
        donate = pipeline and key in self._chunk_donatable \
            and dispatch is None
        if donate:
            self._chunk_dirty.add(key)   # cleared after pass 3 stores
            obs.counter_add("qp.donated_passes")
        ent = self._phase_times.setdefault(key, _new_phase_entry())
        ent["calls"] += 1
        ent["devices"] = ops.n_devices if sharded else 1
        ent["mode"] = "sharded" if sharded else "host"
        ent["kernel"] = plan.descriptor()
        ent["linv_build"] = plan.linv_build
        ent["f64_build"] = plan.f64_build
        ent["shape"] = self._solve_shape(factors, plan, rows_per_call)
        if dispatch is not None:
            dent = ent["dispatch"]
            dent["passes"] += 1
            dent["chunks"] += n_dchunks
            dent["solved"] += scnt
            dent["skipped"] += skipped
            dent["bucket_compiles"] += int(new_bucket)
            dent["gather_seconds"] += restage_s
            dent["gather_programs"] += 1
        gate_syncs = 0
        solve0 = ent["acc"]["solve"]
        # device programs the assemble phase launches for pass 1: ONE
        # on a staged pass (the mesh launched it above), one
        # _ph_assemble per chunk on the per-chunk paths
        asm_programs = int(staged is not None)
        ent["acc"]["assemble"] += restage_s
        clock = _PhaseClock(ent["acc"], sp_args)

        # record layout (indices 0-3 are the _hospitalize contract):
        #  [st, x, yA, yB, d_c, q_c, factors]
        # sharded chunks are mesh-placed end to end (solve outputs ARE
        # reduction inputs — no home/loc distinction survives the
        # spread path's retirement).
        def _hub_rows(ci):
            # _ph_assemble's operands after c, from the resident
            # (S, K) hub state
            return (rows("W", ci), rows("xbar", ci), rows("rho", ci),
                    idx_asm, rows("fm", ci), rows("fv", ci),
                    rows("ws", ci) if "ws" in per else None)

        def _assemble(ci):
            """The sequential opt-out's per-chunk assembly (sharded:
            local slices of the pre-chunked store; host: gathers by the
            chunk's ids), through _ph_assemble."""
            d_c = chunk_data(ci)
            q_c, bl_c, bu_c = _ph_assemble(
                d_c, rows("c", ci), *_hub_rows(ci), **stage_kw)
            return d_c._replace(lb=bl_c, ub=bu_c), q_c

        def _stream_assemble(ci, direct=False):
            """Streamed twin of _assemble: the five vector fields come
            from the source (prefetched in-order; ``direct`` bypasses
            the pipeline for the exceptional retry path), the resident
            (S, K) state slices exactly as the resident path. Returns
            (d_c, q_c, c_c) — the c chunk rides along because pass 3's
            objectives need it and the records deliberately do NOT
            keep data blocks alive across the iteration."""
            blk = stream.fetch(ci) if direct else stream.chunk(ci)
            d_c = data._replace(l=blk["l"], u=blk["u"],
                                lb=blk["lb"], ub=blk["ub"])
            # under an active shrink plan the source stages compacted
            # l/u/lb/ub but keeps c FULL width (install_compacted):
            # assembly gathers the kept columns — a pure gather, so
            # the compacted q is bit-equal to the resident plan.c_c
            # spelling — while the returned full c serves pass 3's
            # expanded objectives
            c_blk = blk["c"]
            c_asm = c_blk[:, shrink.keep_cols] if shrink is not None \
                else c_blk
            q_c, bl_c, bu_c = _ph_assemble(
                d_c, c_asm, *_hub_rows(ci), **stage_kw)
            return d_c._replace(lb=bl_c, ub=bu_c), q_c, c_blk

        # ASSEMBLE — pipelined: every chunk's operands are staged now
        # by ONE device program (the mesh ran its own above, inside the
        # restage span), so the first chunk solve is enqueued one launch
        # after the pass begins and the host never again stops to
        # assemble between chunks. Streamed sources rewind their
        # prefetch pipeline first (the SOLVE pass) and their assembly
        # stays in the solve loop below — the double buffer bounds how
        # many staged chunks exist, so staging all of them up front
        # would defeat the residency bound streaming exists for.
        if stream is not None:
            stream.begin_pass()
        elif stage_all and not sharded:
            # the ids are an operand: cached on the device for the
            # full pass; a dispatch pass, whose set changes every
            # iteration, made its one upload above
            if dispatch is None:
                ids_stack = self._chunk_ids(chunk)
            staged = _ph_stage_chunks(per, idx_asm, ids_stack, **stage_kw)
            asm_programs += 1
        clock.lap("solve")

        # pass 1 — SOLVE. (Segmented solves sync on their own iteration
        # counters internally; the three-pass split buys a SINGLE
        # recovery decision point over all chunks and keeps objectives
        # computed strictly on accepted solutions.)
        solved_chunks = [None] * len(slices)
        prev_st = None
        wraps0 = _linv_wraps(plan, states[0])
        flow0 = self._take_flowed_factor(states, split_mode and donate)
        for ci in range(len(slices)):
            if stream is not None:
                # streamed staging: the prefetch thread has chunk ci
                # (or is shipping it) — assembly cost books under
                # "assemble" exactly like the sequential opt-out so
                # the phase anatomy stays honest
                clock.lap("assemble")
                d_c, q_c, _ = _stream_assemble(ci)
                asm_programs += 1
                clock.lap("solve")
            elif staged is not None:
                d_c, q_c = chunk_data(ci), rows("q", ci)
            else:
                # sequential opt-out: assembly stays interleaved on
                # the critical path, but its wall-clock books under
                # "assemble" (its own span between two "solve" spans)
                # so the seq-vs-pipelined anatomy the instrumentation
                # exists for compares honestly
                clock.lap("assemble")
                d_c, q_c = _assemble(ci)
                asm_programs += 1
                clock.lap("solve")
            st_in = states[ci]
            if split_mode and prev_st is not None:
                # df32: chunks FLOW one (rho_scale, factor) pair
                # through the sequential loop (the in-jit adaptation
                # keeps its responsiveness, each chunk inheriting
                # the previous chunk's adapted stepsize) instead of
                # holding a private ~0.7 GB factor per chunk —
                # per-chunk copies would multiply HBM by chunk
                # count x modes at exactly the scale the split
                # representation exists for. rho is a stepsize:
                # iterates warm-start across scale changes.
                st_in = st_in._replace(L=prev_st.L,
                                       rho_scale=prev_st.rho_scale)
            elif flow0 is not None:
                st_in, flow0 = st_in._replace(L=flow0), None
            # sharded: ONE SPMD chunk solve over all devices (lc
            # scenarios each, psum-reduced termination tests inside
            # the jit); host-chunked: the single-device program
            ck_args = None if sp_args is None else {
                "chunk": ci, "mode": sp_args["mode"],
                "devices": ent["devices"]}
            with self._wheel_turn(slices[ci][1], clock), \
                    obs.span("ph.solve.chunk", cat="ph", args=ck_args):
                st, x, yA, yB = _solver_call(factors, d_c, q_c, st_in,
                                             donate=donate, **kw)
                self._wheel_ready(st.pri_rel)
            prev_st = st
            if split_mode:
                # record a STRIPPED state: keeping each chunk's L
                # alive in solved_chunks until pass 3 would pin
                # every refactorized ~0.7 GB copy simultaneously
                # (the unify below re-attaches the flowed factor)
                st = st._replace(L=jnp.zeros((), jnp.float32))
            # streamed mode drops the data/assembly blocks from the
            # record the moment the solve is enqueued: keeping every
            # chunk's (d_c, q_c) alive through the iteration would
            # re-materialize a full-batch footprint — the exact
            # residency streaming exists to bound. Passes 2/3 restage
            # on demand (retries directly, objectives via a second
            # in-order pipeline pass).
            solved_chunks[ci] = [st, x, yA, yB,
                                 None if stream is not None else d_c,
                                 None if stream is not None else q_c,
                                 factors]
        if plan.mode == "fused":
            # phase honesty: fused programs never block mid-solve (no
            # per-segment iteration readbacks), so without this the
            # device wait would book under "gate" (the first D2H) and
            # the solve/occupancy anatomy would read near-zero. Every
            # chunk is already enqueued — blocking here costs no
            # cross-chunk pipelining and adds no transfer; the gate
            # still pays its one D2H below.
            # lint: ok[SYNC001] phase honesty for fused plans: every chunk already enqueued, the wait adds no serialization (see comment above)
            jax.block_until_ready([rec[0].pri_rel
                                   for rec in solved_chunks])
        # the pass-1 solves' ADMM iterations, beside their seconds:
        # booked post-block (a scalar copy per chunk, not a stall)
        # rather than inside kernel_solve, where the read would
        # serialize chunk k's solve with chunk k+1's dispatch. Retries
        # keep their own counter (ph.chunk_retries).
        # (the chain flows ONE factor: only its first state can arrive
        # without the explicit inverse)
        its, _ = _book_admm_iters(
            ent["admm"], [rec[0] for rec in solved_chunks],
            plan.mode == "fused", wraps0, ir_sweeps=self.sub_ir_sweeps)
        ent["assemble_programs"] += asm_programs
        obs.counter_add("ph.assemble_programs", asm_programs)
        clock.lap("gate")
        # pass 2 — bounded recovery: a chunk whose warm-started rho
        # trajectory went pathological (per-chunk shared rho adapts on
        # chunk statistics) can exhaust its budget far from
        # feasibility. ONE gate point reads every chunk's residual;
        # flagged chunks retry once from a reset rho/factor. The NaN
        # blowup case must flag too, and a chunk whose reset retry
        # didn't help is blacklisted — a genuinely hard chunk must not
        # double every future iteration's cost.
        thr = max(100 * _hot_eps(bool(prox_on), self.sub_eps,
                                 self.sub_eps_hot), 1e-2)
        # FUSED GATE: all recovery/hospital/standing decisions below
        # read this host copy of every chunk's pri_rel. Pipelined mode
        # stacks on device and pays ONE D2H for the whole iteration;
        # the opt-out keeps the historical one-blocking-sync-per-chunk
        # reads. Retries update their row from values they already
        # synced, so the matrix stays current through passes 2/2b.
        # The same read carries the other residual rows the exit
        # booking classifies (EXIT_ROWS, pri_rel first: 4 fields x 8 B
        # a row), so how the pass-1 solves ended costs no sync.
        if pipeline:
            # np.array (not asarray): retry/hospital row writebacks need
            # a writable host matrix, and jax exports read-only views
            # lint: ok[SYNC001] THE stacked-residual gate: ONE D2H per iteration for the whole chunk chain (ph.gate_syncs)
            res_host = np.array(stacked_residuals(
                [rec[0] for rec in solved_chunks], EXIT_ROWS)).reshape(
                    len(EXIT_ROWS), len(solved_chunks), -1)
            gate_syncs += 1
        else:
            # sequential opt-out: the documented one-blocking-read-per-
            # chunk path (gate_syncs books each)
            res_host = np.stack([
                np.stack(jax.device_get([getattr(rec[0], f)
                                         for f in EXIT_ROWS]))
                for rec in solved_chunks], axis=1)
            gate_syncs += len(solved_chunks)
        pri_host = res_host[0]
        if obs.enabled():
            obs.counter_add("xfer.d2h_bytes", res_host.nbytes)
        # every chunk row's global scenario id, and whether it counts
        # (chunk pads and zero-probability mesh pads do not): a
        # dispatch pass names the scenarios it solved
        if dispatch is None:
            row_ids, row_live = self._chunk_row_map(
                slices, ("sharded", lc) if sharded else ("host", chunk))
        else:
            row_ids, row_live = _row_map(
                ids_pad.reshape(n_dchunks, chunk),
                [real for _, real in slices], self._S_orig)
        self._book_call_exits(ent, solve0, _exit_tests(**kw), its,
                              res_host, row_ids, row_live)
        # blacklist RE-ADMISSION (VERDICT r3 #6): PH moves q every
        # iteration, so a row declared incurable under one (W, x̄) may be
        # easy under a later one; permanent blacklists would freeze its
        # stale ~1e-2-residual solution into x̄/W for the rest of the
        # run. Every ``readmit`` solves of this mode, both blacklists
        # get cleared and every standing casualty earns a fresh
        # recovery/hospital attempt. (Rho changes still clear them
        # immediately via invalidate_factors.)
        readmit = int(self.options.get("subproblem_blacklist_readmit", 16))
        calls = self._blacklist_calls[key] = \
            self._blacklist_calls.get(key, 0) + 1
        if readmit and calls % readmit == 0 and (
                self._chunk_no_retry.get(key)
                or self._hospital_no_retry.get(key)):
            nb = len(self._chunk_no_retry.get(key, ())) \
                + len(self._hospital_no_retry.get(key, ()))
            self._chunk_no_retry.pop(key, None)
            self._hospital_no_retry.pop(key, None)
            obs.counter_add("ph.blacklist_readmitted", nb)
            self._trace_note(
                "ph.blacklist_readmit",
                f"blacklist: re-admitting {nb} entr"
                f"{'y' if nb == 1 else 'ies'} for recovery "
                f"(every {readmit} solves)", count=nb, every=readmit)
        no_retry = self._chunk_no_retry.setdefault(key, set())
        for ci, rec in enumerate(solved_chunks):
            m = float(pri_host[ci].max())   # lint: ok[SYNC001] host numpy, synced once at the gate read above
            is_nan = not np.isfinite(m)
            # the blacklist stops repeated retries of a genuinely hard
            # chunk, but NaN iterates MUST always be replaced — storing
            # them would poison every future warm start
            if (m <= thr) or (ci in no_retry and not is_nan):
                continue
            fac_c = rec[6]
            if stream is not None:
                # the record deliberately dropped the data blocks —
                # restage this chunk directly (exceptional path; the
                # in-order pipeline is between passes)
                d_r, q_r, _ = _stream_assemble(ci, direct=True)
            else:
                d_r, q_r = rec[4], rec[5]
            if is_nan:
                # NaN blowup: the iterates themselves are poison — a
                # rho reset would re-iterate NaNs; restart cold
                st_r = self._cold_state(fac_c, d_r)
            else:
                # plateaued far out: keep the iterates, reset the
                # stepsize trajectory
                st_r = qp_reset_rho(fac_c, rec[0])
            # MIXED configs retry in single-precision-free native mode
            # (engine dtype is f64 there — 'mixed' requires it): the
            # mixed retry's f32 bulk phase re-drives the kept iterates
            # straight back to the plateau being recovered from
            # (measured on TPU). Budget never shrinks below the
            # original solve's. Native configs keep their precision
            # (there is no higher tier to escalate to) and just get
            # the bigger budget.
            # budget >= the original solve's TOTAL (bulk + tail) work.
            # kernel=None: recovery ALWAYS takes the segmented path in
            # native precision — it doubles as the fused path's
            # full-precision fallback (doc/kernels.md)
            kw_r = dict(kw, precision="native", kernel=None,
                        sub_max_iter=max(kw["sub_max_iter"]
                                         + 4 * kw["tail_iter"], 1500))
            with self._wheel_turn(slices[ci][1], clock):
                st2, x2, yA2, yB2 = _solver_call(fac_c, d_r, q_r,
                                                 st_r, **kw_r)
                self._wheel_ready(st2.pri_rel)
            pri2 = np.asarray(st2.pri_rel)   # lint: ok[SYNC001] exceptional-path retry sync, booked as its own gate_sync
            gate_syncs += 1
            if obs.enabled():
                obs.counter_add("xfer.d2h_bytes", pri2.nbytes)
            m2 = float(pri2.max())   # lint: ok[SYNC001] host numpy from the retry read
            obs.counter_add("ph.chunk_retries")
            obs.event("ph.chunk_retry",
                      {"chunk": ci, "nan": is_nan, "pri_rel_before": m,
                       "pri_rel_after": m2})
            if split_mode:
                # retry factors are transient too (see the pass-1 strip)
                st2 = st2._replace(L=jnp.zeros((), jnp.float32))
                st_r = st_r._replace(L=jnp.zeros((), jnp.float32))
            if np.isfinite(m2) and (is_nan or m2 < m):
                rec[:4] = [st2, x2, yA2, yB2]
                pri_host[ci] = pri2
            elif is_nan:
                # both attempts NaN: keep the CLEAN cold state so the
                # next iteration starts from finite values (zero duals
                # still certify a valid, if loose, bound)
                rec[:4] = [st_r, st_r.x, st_r.yA, st_r.yB]
                pri_host[ci] = np.inf   # cold-state residuals
            if not (m2 <= thr):
                no_retry.add(ci)
        # pass 2b — scenario HOSPITAL: scenarios still far out after the
        # chunk-level retry get a per-scenario (non-shared) solve. The
        # shared kernel's Ruiz/cost scaling and rho patterns are
        # computed against the REFERENCE objective c, while PH solves
        # the assembled q = c + (W − ρx̄) — for outlier scenarios that
        # compromise can stall the ADMM at 1e-1-level residuals
        # regardless of budget (measured: a scenario stuck at 7e-2
        # through every shared-mode retry converges to 4e-16 in
        # non-shared mode, where qp_setup scales against ITS OWN q).
        # Per-scenario (n, n) factorizations are expensive, so this is
        # capped and only ever runs on the few flagged scenarios.
        from ..ops.qp_solver import ScaledView
        if bool(self.options.get("subproblem_hospital", True)) \
                and not isinstance(data.A, (SplitMatrix, ScaledView)):
            # COMPACTED passes run the hospital too (the ROADMAP item 5
            # remainder, landed here): under an active shrink plan
            # ``data`` is already the compacted system and _hospitalize
            # assembles the rescue against the COMPACTED operands
            # (shrink.c_c, free-slot W/x̄/ρ, idx_c) — the treated rows
            # scatter back into the compacted-width records pass 3
            # expands. Chunk retries + blacklist re-admission above run
            # on the compacted system unchanged, as before.
            # The hospital builds per-scenario (cap, m, n) batched
            # factors — structurally impossible at the scale df32
            # exists for (one (n, n) f64 host inversion there costs
            # minutes); those configs rely on chunk retries + blacklist
            # re-admission instead (the isinstance guard).
            treated = self._hospitalize(key, slices, solved_chunks, data,
                                        thr, bool(w_on), bool(prox_on),
                                        kw, pri_host=pri_host,
                                        stream=stream, shrink=shrink)
            gate_syncs += treated
        # standing-casualty observability (VERDICT r3 #6): rows STILL
        # above the gate after recovery + hospital enter x̄/W with their
        # loose solutions this iteration — that must be visible in the
        # trace, not only the hospital's treatment log. pri_host was
        # kept current through passes 2/2b, so this is one more mask
        # over the exit booking's row map, counted with it; the note
        # is narrated only when something consumes it (screen, logger,
        # or the telemetry event stream).
        standing = ~(pri_host <= thr) & row_live
        n_standing = int(standing.sum())
        ent["exits"]["rows_over_gate"] += n_standing
        if n_standing and self._trace_consumers_active():
            worst = np.argmax(np.where(standing, pri_host, -np.inf))
            g_w, pr_w = int(row_ids.flat[worst]), float(pri_host.flat[worst])   # lint: ok[SYNC001] host numpy of the gate read
            when = (f"re-admission in {readmit - calls % readmit} "
                    "solves" if readmit else "re-admission disabled")
            obs.counter_add("ph.standing_rows", n_standing)
            self._trace_note(
                "ph.standing",
                f"standing: {n_standing} scenario row(s) above "
                f"pri_rel gate {thr:.0e} enter xbar/W loose "
                f"(worst s{g_w}:{pr_w:.0e}; {when})",
                rows=n_standing, gate=thr, worst_scenario=g_w,
                worst_pri_rel=pr_w)
        ent["gate_syncs"] += gate_syncs
        obs.counter_add("ph.gate_syncs", gate_syncs)
        clock.lap("reduce")
        # pass 3 — per-chunk objectives on the accepted solutions.
        # Streamed sources restage each chunk through a SECOND in-order
        # pipeline pass (the records dropped the data blocks — see the
        # pass-1 comment): the reassembled (d, q) are bit-identical to
        # pass 1's (W/x̄/ρ/fixed masks only move after this pass), so
        # the objectives and certified dual bound match the resident
        # spelling exactly while per-iteration residency stays bounded
        # by the pipeline depth.
        if stream is not None:
            stream.begin_pass()
        parts = {k: [] for k in ("x", "yA", "yB", "xn", "base", "solved",
                                 "dual")}
        for ci, (idx_c, real) in enumerate(slices):
            st, x, yA, yB = solved_chunks[ci][:4]
            d_h, q_h = solved_chunks[ci][4], solved_chunks[ci][5]
            states[ci] = st
            if shrink is not None:
                # expand the compacted solution to full width (fixed
                # columns take their folded values) and evaluate the
                # objectives against the FULL cost structures; the
                # dual bound stays on the compacted system + fold
                from ..ops.shrink import expand_solution
                if stream is not None:
                    # restage this chunk (the second in-order pipeline
                    # pass begun above): the records dropped the data
                    # blocks, and the reassembled compacted (d, q) are
                    # bit-identical to pass 1's for the dual bound;
                    # the full-width c chunk rides along for the
                    # expanded objectives, and the RAW shared P row
                    # broadcasts (the objective must not carry the
                    # prox rho)
                    d_h, q_h, cF_c = _stream_assemble(ci)
                    P0_c = jnp.broadcast_to(self.qp_data.P_diag,
                                            cF_c.shape)
                else:
                    cF_c, P0_c = rows("cF", ci), rows("P0", ci)
                fvc, WF_c = rows("fvcols", ci), rows("WF", ci)
                c0_c, c0f_c = rows("c0", ci), rows("c0fold", ci)
                x = expand_solution(x, fvc, shrink.keep_cols,
                                    shrink.fixed_cols, cF_c[0])
                xn, base, solved = _shrink_objs(
                    x, cF_c, c0_c, P0_c, WF_c, self.nonant_idx,
                    w_on=bool(w_on))
                dual = _shrink_dual(d_h, q_h, c0f_c, yA, yB,
                                    solved_chunks[ci][1])
            else:
                if stream is not None:
                    d_h, q_h, c_c = _stream_assemble(ci)
                    # the RAW shared P row broadcasts per chunk (the
                    # objective must not carry the prox rho that
                    # _data_with_prox added to ``data``'s diagonal)
                    P0_c = jnp.broadcast_to(self.qp_data.P_diag,
                                            c_c.shape)
                else:
                    # a staged pass had these in hand since pass 1
                    c_c, P0_c = rows("c", ci), rows("P0", ci)
                c0_c, W_c = rows("c0", ci), rows("W", ci)
                xn, base, solved, dual = _ph_chunk_objs(
                    x, yA, yB, d_h, q_h, c_c, c0_c, P0_c,
                    self.nonant_idx, W_c, w_on=bool(w_on))
            # a dispatch pass keeps the pad rows: the placement writes
            # the PADDED width (duplicate ids carry identical values, so
            # which of them lands does not matter); trimming would make
            # its shape vary per scnt instead of per chunk-count bucket
            for k, v in (("x", x), ("yA", yA), ("yB", yB), ("xn", xn),
                         ("base", base), ("solved", solved),
                         ("dual", dual)):
                parts[k].append(v if dispatch is not None else v[:real])
        if split_mode and prev_st is not None:
            # UNIFY after the pass: every chunk state adopts the flow's
            # final (rho_scale, factor) so exactly ONE (n, n) factor
            # persists between passes (pass 1 strips each record's L
            # immediately, so at most two factors are ever alive — the
            # inherited one and, briefly, a refactorized successor)
            for ci in range(len(states)):
                states[ci] = states[ci]._replace(
                    L=prev_st.L, rho_scale=prev_st.rho_scale)
        # from here the chunk states are solve outputs with privately
        # owned buffers — the NEXT pass of this mode may donate them,
        # and this pass's donation window is closed
        self._chunk_dirty.discard(key)
        if dispatch is not None:
            # scatter-back: the dispatched rows' results land in the
            # full-width arrays; every other row — solution, duals,
            # warm state, objectives — carries forward untouched (the
            # staleness contract, doc/aph.md). Store rows take the
            # SCALED post-solve states (warm-start semantics); the
            # engine-facing x/yA/yB take the unscaled solutions.
            # ONE program places all fifteen fields.
            from ..ops.dispatch import place_chunks
            with obs.span("ph.dispatch.scatter", cat="ph",
                          args=sp_args) as sp:
                placed = place_chunks(
                    tuple(getattr(dstore, f) for f in _STORE_ROWS)
                    + (self.x, self.yA, self.yB, self._last_base_obj,
                       self._last_solved_obj, self._last_dual_obj),
                    ids_stack,
                    tuple(tuple(getattr(st, f) for st in states)
                          for f in _STORE_ROWS)
                    + tuple(tuple(parts[k]) for k in (
                        "x", "yA", "yB", "base", "solved", "dual")))
                last = states[-1]
                n_rows = len(_STORE_ROWS)
                new_store = dstore._replace(
                    L=last.L, rho_scale=last.rho_scale, iters=last.iters,
                    **dict(zip(_STORE_ROWS, placed[:n_rows])))
                self._qp_states[("dispatch", key)] = new_store
                # the full-width store doubles as this mode's QPState
                # for the read-only consumers (residual_summary,
                # feasibility checks, warm-start transplants)
                self._qp_states[key] = new_store
                (self.x, self.yA, self.yB, self._last_base_obj,
                 self._last_solved_obj, self._last_dual_obj) = \
                    placed[n_rows:]
            ent["dispatch"]["scatter_programs"] += 1
            ent["dispatch"]["scatter_seconds"] += sp.seconds
            clock.lap()
            self._ext("post_solve")
            return self._last_solved_obj
        self._chunk_donatable.add(key)
        # reassembly: sharded chunks concatenate LOCALLY per device
        # (each device's chunk rows are exactly its contiguous shard —
        # one jitted shard_map, natural global order, no collectives);
        # host chunks concatenate plainly
        cat_fn = ops.from_chunks if sharded else jnp.concatenate
        cat = {k: cat_fn(v) for k, v in parts.items()}
        # lazily concatenated read-only view for the state consumers
        # (assert_feasible_iter0, incumbent feasibility, bench prints);
        # per-chunk states stay authoritative for warm starts
        self._qp_states[key] = _ChunkStateView(
            states, [real for _, real in slices],
            precomputed={"x": cat["x"], "yA": cat["yA"],
                         "yB": cat["yB"]},
            concat_fn=ops.from_chunks if sharded else None)
        self.x, self.yA, self.yB = cat["x"], cat["yA"], cat["yB"]
        if update:
            wmask = None if self._w_scale is None else self._w_scale > 0
            if sharded:
                # Compute_Xbar / Update_W / convergence as segment-sum
                # + psum over the named axis (doc/sharding.md)
                xbar_new, xsqbar_new, W_new, conv = self._mesh_combine(
                    ent, cat["xn"], self.prob, self.xbar_weights, self.W,
                    self.rho, wmask)
            else:
                xbar_new, xsqbar_new, W_new, conv = _ph_combine(
                    cat["xn"], self.prob, self.xbar_weights,
                    tuple(self.memberships), self.W, self.rho, wmask,
                    slot_slices=self.slot_bounds)
            self.xbar, self.xsqbar = xbar_new, xsqbar_new
            self.W_new = W_new
            # lint: ok[SYNC001] THE per-iteration convergence scalar readback — the one designed sync (doc/pipelining.md)
            self.conv = float(conv)
            obs.gauge_set("ph.conv", self.conv)
        self._last_base_obj = cat["base"]
        self._last_solved_obj = cat["solved"]
        self._last_dual_obj = cat["dual"]
        clock.lap()
        self._ext("post_solve")
        return cat["solved"]

    def reset_phase_timing(self):
        """Zero the per-phase wall-clock accumulators and the ADMM
        iteration counts booked beside them (bench timing windows).
        Telemetry COUNTERS (obs: ph.gate_syncs and friends)
        are process-cumulative and deliberately survive this reset —
        invariant tests read them as pure before/after deltas."""
        self._phase_times.clear()
        self._run_times.update(count=0, seconds=0.0, reset_seconds=0.0)

    def phase_timing(self, key=True):
        """Per-phase wall-clock anatomy of the solve loop for one
        mode key (chunked or fused — the fused path books assemble/
        solve/reduce with gate pinned at 0): mean seconds per
        solve_loop call in each pipeline
        phase (assemble / solve / gate / reduce), the device-busy
        occupancy estimate solve/(total) — the solve phase is the only
        one that blocks on device compute, so everything else is host
        orchestration the pipeline exists to shrink — and the gate's
        D2H sync count per call (the O(chunks) -> O(1) acceptance
        evidence). Returns None when the key never ran."""
        ent = self._phase_times.get(key)
        if not ent or not ent["calls"]:
            return None
        n = ent["calls"]
        per_call = {p: ent["acc"][p] / n for p in
                    ("assemble", "solve", "gate", "reduce")}
        total = sum(per_call.values())
        kernel = ent.get("kernel")
        if kernel is not None and self._wheel_port is not None:
            kernel = dict(kernel, residency=self.residency())
        return {
            "calls": n,
            "seconds_per_call": per_call,
            "occupancy": (per_call["solve"] / total) if total > 0 else 0.0,
            "gate_d2h_syncs_per_call": ent["gate_syncs"] / n,
            # device programs the assemble phase launched per call: 1
            # where ONE program stages every chunk (the resident
            # pipelined pass) or the batch is not chunked, the number
            # of chunks where each is assembled by its own
            # _ph_assemble (a streamed source, subproblem_pipeline=0)
            "assemble_programs_per_call": ent["assemble_programs"] / n,
            "devices": ent["devices"],
            # "sharded": scenario-axis SPMD over the mesh;
            # "host": single-device dispatch (doc/sharding.md)
            "mode": ent.get("mode", "host"),
            # resolved kernel decisions of the last call ({mode,
            # backend, l_inv, block_dtype} — ops/kernels.KernelPlan
            # .descriptor(), doc/kernels.md); None on engines predating
            # a kernel-plan build
            # (an engine of an in-process wheel adds what it shares
            # with the other cylinders' engines and what is its own:
            # ``residency()``)
            "kernel": kernel,
            # the ADMM work of the SAME solve passes the solve seconds
            # cover (pass-1 solves; reset with them): f32 bulk and
            # refinement-tail iterations per solve_loop call, summed
            # over its chunk solves. A solve with no low-precision
            # phase books every iteration as tail. ``refactors``: the
            # same solves' in-loop rho refactorizations, i.e. how often
            # the factor was prepared anew (qp_solver.PreparedFactor).
            "admm_iters_per_call": {k: v / n
                                    for k, v in ent["admm"].items()},
            # how those same solves ENDED, totals since the reset
            # (``_exits_view``)
            "exits": _exits_view(ent["exits"]),
            # a sharded engine's consensus reduces per call and the
            # bytes their psums move between the chips (_mesh_combine);
            # zeros on one device
            "collective": {k: v / n
                           for k, v in ent["collective"].items()},
            # this mode's dispatch-masked passes since the last reset
            # (totals: a mode's calls mix full and partial passes):
            # how many, their chunk solves, the scenarios they solved
            # and skipped, the host seconds of the warm states' way in
            # (``ph.dispatch.gather``, part of the assemble seconds)
            # and of the scatter-back (``ph.dispatch.scatter``, part of
            # the reduce seconds), the device programs launched at
            # those two sites (one each a pass), and the bucket
            # registry's first sightings
            "dispatch": dict(ent["dispatch"]),
            # what one solve call of the last pass streams: keyword
            # for keyword the facts a bytes-per-iteration model prices
            # (ops/kernels.est_hbm_bytes_per_iter)
            "solve_shape": ent.get("shape"),
            # the eager explicit-inverse builds of this mode's plan
            # (span ``qp.l_inv_build``): {builds, seconds, n, panels},
            # totals kept by the PLAN, so a cold state's build during
            # set-up is still told after ``reset_phase_timing``; empty
            # where none ran (ops/kernels.KernelPlan.linv_build)
            "linv_build": dict(ent.get("linv_build") or {}),
            # the eager builds of this mode's per-scenario float64
            # inverse (span ``qp.f64_refactor_build``: its cold state):
            # {builds, seconds, rows, n}, kept by the plan like
            # ``linv_build``; empty where the factor is none
            # (ops/kernels.KernelPlan.f64_build)
            "f64_refactor_build": dict(ent.get("f64_build") or {}),
            # whole PH runs of the ENGINE (every mode's; ``run_span``)
            # since the last reset: how many, their seconds, and the
            # seconds of their ``reset_run()``s (totals, not per call)
            "runs": dict(self._run_times),
        }

    def _solve_shape(self, factors, plan, rows_per_call):
        A_s = factors.A_s
        m, n = (int(v) for v in A_s.shape[-2:])
        pk = None
        if getattr(A_s, "pk_hi", None) is not None:
            from ..ops.packed import pk_nbytes
            pk = pk_nbytes(A_s.pk_hi) + pk_nbytes(A_s.pk_lo)
        return {"n": n, "m": m, "s_chunk": int(rows_per_call),
                "ir_sweeps": int(self.sub_ir_sweeps),
                "pk_pass_bytes": pk,
                "block_dtype": plan.descriptor()["block_dtype"]}

    def _phase_totals(self):
        """Accumulated per-phase wall-clock summed over every solve
        mode — the per-iteration convergence record diffs two of these
        to attribute one iteration's budget (free host math: four dict
        reads per mode)."""
        tot = {"assemble": 0.0, "solve": 0.0, "gate": 0.0, "reduce": 0.0}
        for ent in self._phase_times.values():
            for k, v in ent["acc"].items():
                tot[k] += v
        return tot

    def phase_booked(self, key=True):
        """The raw running totals behind ``phase_timing(key)`` (zeros
        where the key never ran): ``solve_loop`` calls, the four
        phases' seconds, ADMM iterations (bulk + tail), in-program
        refactorizations and solves that ran their whole tail budget.
        Two of these differ by what the mode booked in between
        (serve/manager: one wheel's share of a leased engine)."""
        ent = self._phase_times.get(key) or _new_phase_entry()
        admm = ent["admm"]
        return {"calls": ent["calls"], **ent["acc"],
                "admm_iters": admm["bulk"] + admm["tail"],
                "refactors": admm["refactors"],
                "capped": ent["exits"]["tail_capped"]}

    def residual_summary(self, key=True):
        """Host summary of the last solve's relative residuals for one
        mode key (None when that mode never ran). Reading the state
        syncs a small (S,) vector — callers gate on ``obs.enabled()``;
        by record-emission time the iteration already synced ``conv``,
        so this adds a transfer, not a pipeline stall."""
        st = self._qp_states.get(key)
        if st is None:
            return None
        # mesh pads (zero-probability copies) are excluded: a pad row's
        # residual is redundant with its source scenario's
        pri = np.asarray(st.pri_rel)[:self._S_orig]
        dua = np.asarray(st.dua_rel)[:self._S_orig]
        return {"pri_rel_max": float(pri.max()),
                "pri_rel_mean": float(pri.mean()),
                "dua_rel_max": float(dua.max()),
                "dua_rel_mean": float(dua.mean())}

    def _forensic_sample(self, it):
        """One wheel-forensics sample (ops/forensics.py): the jitted
        attribution reduction over the current (S, K) hub state, its
        packed result fetched at the already-synced gate (the
        ``residual_summary`` license — ``ph.gate_syncs`` stays O(1)),
        unpacked and handed to the diagnosis engine. Returns the
        sample dict, or None when the state is not ready."""
        if self.x is None or self.conv is None:
            return None
        from ..obs import diagnose as _obs_diagnose
        from ..ops import forensics as _forensics
        xn = self.nonants_of(self.x)
        S, K = xn.shape
        st = self._forensic_state
        if st is None or st.prev_w.shape != (S, K):
            # first sample, or a shrink compaction changed the slot
            # width: restart the carry (validity gates re-arm)
            st = _forensics.init_state(S, K, dtype=xn.dtype)
        kk = min(_forensics.TOPK, K)
        ks = min(_forensics.TOPK, int(self._S_orig))
        st, packed = _forensics.forensic_reduce(
            st, xn, self.xbar, self.W, self.prob, self.rho,
            kk=kk, ks=ks)
        self._forensic_state = st
        fx = _forensics.unpack(packed, kk, ks)
        fx["it"] = int(it)
        fx["n_scens"] = int(self._S_orig)
        fx["n_slots"] = int(K)
        shrink = None
        if self._shrink_status is not None:
            shrink = dict(self._shrink_status)
            buckets = getattr(self, "_shrink_buckets", None)
            if buckets:
                shrink["first_bucket"] = float(buckets[0])
        _obs_diagnose.note_sample(fx, shrink=shrink)
        # rebind, don't mutate: a reader on another thread or in a
        # signal frame must see a whole record
        self._forensic_last = fx
        return fx

    # counters whose per-iteration deltas enter the ph.iteration record
    # (the recovery machinery volume THIS iteration, plus compile
    # activity — a nonzero jax.compiles delta mid-run is a retrace)
    _ITER_DELTA_COUNTERS = ("ph.gate_syncs", "ph.chunk_retries",
                            "ph.hospital_treated", "ph.standing_rows",
                            "ph.blacklist_readmitted", "qp.donated_passes",
                            "qp.solve_segments", "jax.compiles",
                            # sharded engines: the steady-state contract
                            # is collective bytes > 0 and device_put
                            # bytes == 0 (so device_put only appears in
                            # a record when something went wrong)
                            "xfer.collective_bytes",
                            "xfer.device_put_bytes",
                            # kernel-layer activity (ops/kernels):
                            # fused ADMM iterations this iteration, plus
                            # the (rare) eager L⁻¹ builds — the analyze
                            # fused-vs-segmented verdict row reads these
                            # APH φ-dispatch (ops/dispatch, doc/aph.md):
                            # one gate sync per iteration, solved vs
                            # skipped scenario counts, and bucket
                            # compile-vs-hit activity — the analyze aph
                            # section and its compare verdict read these
                            "aph.gate_syncs",
                            "dispatch.solved_scenarios",
                            "dispatch.skipped_scenarios",
                            "dispatch.bucket.compile",
                            "dispatch.bucket.cache_hit",
                            "kernel.fused_iters",
                            "kernel.l_inv_factorizations",
                            # scenario streaming (mpisppy_tpu/stream):
                            # chunks/bytes staged this iteration —
                            # analyze's streaming section asserts the
                            # steady-state flatness off these deltas
                            "stream.chunks_shipped",
                            "stream.bytes_shipped",
                            "stream.synth_chunks",
                            "stream.prefetch_stalls",
                            "stream.direct_fetches",
                            # shrink x stream composition: transitions
                            # re-block the host store and restage once
                            # out-of-band — analyze's flatness verdict
                            # excludes these bytes from bytes_shipped
                            "stream.compacted_transitions",
                            "stream.compacted_restage_bytes",
                            # progressive shrinking (ops/shrink): newly
                            # fixed slots and bucket transitions THIS
                            # iteration — analyze's shrinking section
                            # reads these off the record stream
                            "shrink.fixed_new",
                            "shrink.compactions",
                            # cross-bucket warm transplant: warm-state
                            # pulls vs guarded cold restarts at each
                            # transition — the analyze re-convergence
                            # row and its --compare REGRESSION read
                            # these
                            "shrink.transplants",
                            "shrink.transplant_cold_fallbacks")

    def iteration_record(self, it, seconds, phase_before, counters_before):
        """The structured per-iteration convergence record (the
        device-resident analog of the reference's Diagnoser extension):
        conv, residual summary, best bounds + gap as currently known,
        this iteration's phase wall-clocks and recovery/compile counter
        deltas. Emitted as the ``ph.iteration`` event by drivers; only
        assembled when telemetry is enabled."""
        fin = obs.finite_or_none
        rec = {"iter": it, "conv": fin(self.conv), "seconds": seconds,
               "best_outer": fin(self.best_bound)}
        if self._shard_ops is not None:
            # the sharding anatomy analyze's sharding section renders
            # (collective bytes arrive via counter_deltas below)
            rec["sharding"] = {
                "mode": "sharded",
                "n_devices": self._shard_ops.n_devices,
                "shard_scenarios": self._shard_ops.shard_size}
        if self.spcomm is not None:
            outer = fin(getattr(self.spcomm, "BestOuterBound", None))
            inner = fin(getattr(self.spcomm, "BestInnerBound", None))
            rec["best_outer"] = outer if outer is not None \
                else rec["best_outer"]
            rec["best_inner"] = inner
            if outer is not None and inner is not None and inner != 0:
                rec["gap_rel"] = (inner - outer) / abs(inner)
        res = self.residual_summary(True)
        if res is not None:
            rec.update(res)
        if self._shrink_status is not None:
            # the active-set trajectory (doc/extensions.md §shrinking):
            # plain host-dict copy, updated by the device fixer and
            # maybe_compact — analyze's shrinking section plots
            # fixed-fraction, bucket, and est-HBM against s/iter
            rec["shrink"] = dict(self._shrink_status)
        if self._stream_source is not None:
            # scenario-source anatomy (doc/streaming.md): cumulative
            # staging totals as plain host ints — per-iteration deltas
            # ride counter_deltas below
            rec["stream"] = self._stream_source.status()
        aph = getattr(self, "_aph_status", None)
        if aph:
            # APH dispatch anatomy (doc/aph.md): this iteration's
            # dispatched fraction, φ stats from the packed gate, and
            # which solve path carried it — analyze's aph section plots
            # the trajectory and the skipped-solve savings
            rec["aph"] = dict(aph)
        now = self._phase_totals()
        rec["phase_seconds"] = {k: now[k] - phase_before.get(k, 0.0)
                                for k in now}
        last = (self._phase_times.get(True) or {}).get("exits", {}).get(
            "last")
        if last is not None:
            # how the hot mode's last call's chunk solves ended (the
            # record ``phase_timing()["exits"]["per_call"]`` keeps)
            rec["exits"] = {"tail_iters": last[0], "rows_over": last[1]}
        ctr = obs.counters_snapshot()
        rec["counter_deltas"] = {
            k: ctr.get(k, 0) - counters_before.get(k, 0)
            for k in self._ITER_DELTA_COUNTERS
            if ctr.get(k, 0) != counters_before.get(k, 0)}
        if self._forensics_every > 0 \
                and it % self._forensics_every == 0:
            # wheel forensics (ops/forensics.py, doc/forensics.md):
            # per-slot/per-scenario convergence attribution, sampled
            # on the interval — the record carries the sample and the
            # diagnosis engine (obs/diagnose.py) re-runs its verdicts
            fx = self._forensic_sample(it)
            if fx is not None:
                rec["forensics"] = fx
        return rec

    def _hospitalize(self, key, slices, solved_chunks, data, thr, w_on,
                     prox_on, kw, pri_host=None, stream=None,
                     shrink=None):
        """Per-scenario rescue solves for chunked-mode stragglers (see
        the pass-2b comment in _solve_loop_chunked). Selected scenarios
        are re-assembled and solved NON-shared (own Ruiz/cost scaling
        against their own assembled q, own adaptive rho, own (n, n)
        factor) from cold, and their rows scattered back into the
        accepted chunk results and warm-start states. The selection is
        padded to ``subproblem_hospital_max`` so the non-shared
        programs compile once. The default cap is SMALL (4): the
        batched (cap, n, n) f64 factorization is a single long device
        execution whose cost grows with the cap (the value was tuned on
        a machine since retired; unverified on the attached v5e);
        scenarios beyond the cap stay flagged and are picked up
        (worst-first) on subsequent iterations.

        ``pri_host`` ((n_chunks, chunk) host residual matrix from the
        fused gate): selection reads it instead of one D2H per chunk,
        and cured rows are written back so the standing-casualty trace
        stays current. Returns the number of host transfers performed
        (0 or 1) for the caller's sync accounting."""
        cap = int(self.options.get("subproblem_hospital_max", 4))
        # scenarios the hospital already failed to improve: skip them
        # forever (same recurring-cost bound as pass 2's no_retry — a
        # cold hospital solve per PH iteration for an incurable row
        # would be pure waste)
        failed = self._hospital_no_retry.setdefault(key, set())
        picks = []                      # (chunk, row, global scenario)
        for ci, (idx_c, real) in enumerate(slices):
            pr = (np.asarray(solved_chunks[ci][0].pri_rel)
                  if pri_host is None else pri_host[ci])[:real]
            for r in np.flatnonzero(~(pr <= thr)):
                g = int(np.asarray(idx_c)[r])
                # keyed by GLOBAL scenario id: chunk-local coordinates
                # would re-target other scenarios if the chunk size
                # ever changes mid-run. Zero-probability mesh pad rows
                # never earn a rescue solve — they are copies of a real
                # scenario and carry no objective weight.
                if g not in failed and g < self._S_orig:
                    picks.append((ci, int(r), g, float(pr[r])))
        if not picks:
            return 0
        picks.sort(key=lambda t: -t[3])     # worst first under the cap
        picks = picks[:cap]
        sel = np.array([g for _, _, g, _ in picks])
        pad = cap - sel.size
        sel_p = np.concatenate([sel, np.full(pad, sel[0])]) if pad else sel
        k = sel_p.size
        # the compacted width under an active shrink plan (data IS the
        # compacted system there — the ROADMAP item 5 remainder's
        # compacted hospital spelling), the full width otherwise
        n = int(data.lb.shape[-1])
        A_b = jnp.broadcast_to(data.A, (k,) + data.A.shape) \
            if data.A.ndim == 2 else data.A[sel_p]
        P_b = jnp.broadcast_to(data.P_diag, (k, n)) \
            if data.P_diag.ndim == 1 else data.P_diag[sel_p]
        if stream is not None:
            # streamed source: the engine never shipped full-width
            # vectors — stage exactly the flagged rows (host gather or
            # in-kernel synthesis; an exceptional-path transfer booked
            # like every other stream fetch)
            rb = stream.rows(sel_p)
            d_h = QPData(P_b, A_b, rb["l"], rb["u"], rb["lb"], rb["ub"])
            c_sel = rb["c"]
        else:
            d_h = QPData(P_b, A_b, data.l[sel_p], data.u[sel_p],
                         data.lb[sel_p], data.ub[sel_p])
            c_sel = None
        if shrink is not None:
            # compacted assembly: free-slot gathers of the hub state +
            # the compacted cost block, pinned by the compacted nonant
            # index — mirrors _solve_loop_chunked's compacted
            # operands, so the rescue solves THE SAME system the chunk
            # solves do and its rows scatter back width-consistent
            fs = shrink.free_slots_dev
            if stream is not None:
                # rb["c"] above is FULL width (the compacted store
                # keeps c full; plan.c_c is a 2-row setup surrogate
                # under streaming) — gather the kept columns
                c_sel = c_sel[:, shrink.keep_cols]
            else:
                c_sel = shrink.c_c[sel_p]
            W_s, xb_s, rho_s = (self.W[sel_p][:, fs],
                                self.xbar[sel_p][:, fs],
                                self.rho[sel_p][:, fs])
            fm_s, fv_s = (self._fixed_mask[sel_p][:, fs],
                          self._fixed_vals[sel_p][:, fs])
            ws = None if self._w_scale is None \
                else self._w_scale[sel_p][:, fs]
            idx_h = shrink.idx_c
        else:
            if c_sel is None:
                c_sel = self.c[sel_p]
            W_s, xb_s, rho_s = (self.W[sel_p], self.xbar[sel_p],
                                self.rho[sel_p])
            fm_s, fv_s = (self._fixed_mask[sel_p],
                          self._fixed_vals[sel_p])
            ws = None if self._w_scale is None else self._w_scale[sel_p]
            idx_h = self.nonant_idx
        q_h, bl_h, bu_h = _ph_assemble(
            d_h, c_sel, W_s, xb_s, rho_s, idx_h, fm_s, fv_s, ws,
            w_on=w_on, prox_on=prox_on)
        d_h = d_h._replace(lb=bl_h, ub=bu_h)
        fac_h = qp_setup(d_h, q_ref=q_h)
        st_h = qp_cold_state(fac_h, d_h)
        # pass 1's kwargs with precision/budget escalated and LONG
        # segments: the batch is tiny (cap rows), and the inherited
        # short segment would trigger a host
        # rho-refactorization every ~150 iterations on untrusted-f64
        # backends (measured: ~20 host inversions per rescue, tens of
        # seconds per PH iteration for one sick scenario)
        st_h, x_h, yA_h, yB_h = _solver_call(
            fac_h, d_h, q_h, st_h,
            **dict(kw, precision="native", kernel=None,
                   sub_max_iter=max(6000, kw["sub_max_iter"]),
                   segment=1500))
        pr_h = np.asarray(st_h.pri_rel)
        obs.counter_add("ph.hospital_treated", len(picks))
        worst = " ".join(
            f"s{g}:{pr_old:.0e}->{pr_h[j]:.0e}"
            for j, (_, _, g, pr_old) in enumerate(picks))
        self._trace_note(
            "ph.hospital",
            f"hospital: treated {len(picks)} scenario(s) [{worst}]",
            treated=len(picks),
            scenarios=[{"scenario": g, "pri_rel_before": pr_old,
                        "pri_rel_after": float(pr_h[j])}
                       for j, (_, _, g, pr_old) in enumerate(picks)])
        for j, (ci, r, g, pr_old) in enumerate(picks):
            if not (pr_h[j] <= thr):
                # one shot per scenario: an improved-but-uncured row
                # still gets its better solution scattered below, but a
                # cold hospital solve every future iteration for a row
                # that never reaches the gate is pure waste
                failed.add(g)
            if not (pr_h[j] < pr_old):
                continue
            rec = solved_chunks[ci]
            st = rec[0]
            # scatter the UNSCALED solution rows + residual rows only.
            # The hospital's internal iterates live in ITS OWN Ruiz/cost
            # scaling — transplanting them into the chunk state (a
            # different scaling) would corrupt the warm start. The
            # rescued scenario keeps its old chunk-state iterates; if it
            # stalls again next iteration the hospital re-fires
            # (bounded: once per iteration, capped batch, failed rows
            # never re-admitted).
            res_rows = (st_h.pri_res[j], st_h.dua_res[j],
                        st_h.pri_rel[j], st_h.dua_rel[j])
            rec[0] = st._replace(
                pri_res=st.pri_res.at[r].set(res_rows[0]),
                dua_res=st.dua_res.at[r].set(res_rows[1]),
                pri_rel=st.pri_rel.at[r].set(res_rows[2]),
                dua_rel=st.dua_rel.at[r].set(res_rows[3]))
            rec[1] = rec[1].at[r].set(x_h[j])
            rec[2] = rec[2].at[r].set(yA_h[j])
            rec[3] = rec[3].at[r].set(yB_h[j])
            if pri_host is not None:
                pri_host[ci][r] = pr_h[j]
        return 1

    def _dive_in_chunks(self, factors, d, q, c0, st, imask, **kw):
        """core.mip.dive_integers with scenario microbatching. Dives
        have NO cross-scenario coupling (each scenario pins its own
        columns), so chunking is exact; without it a 1024-scenario dive
        would launch full-batch f64-involving device calls — the
        unstable regime subproblem_chunk exists to avoid."""
        from .mip import dive_integers

        chunk = int(self.options.get("subproblem_chunk", 0))
        S = self.batch.S
        if not (chunk and chunk < S):
            return dive_integers(factors, d, q, c0, st, imask, **kw)
        if factors.A_s.ndim != 2:
            raise ValueError("subproblem_chunk requires a shared-"
                             "structure batch (see _solve_loop_chunked)")
        n = d.lb.shape[-1]
        imask_b = jnp.broadcast_to(jnp.asarray(imask, bool), (S, n))
        q_b = jnp.broadcast_to(jnp.asarray(q), (S, n))
        c0_b = jnp.broadcast_to(jnp.asarray(c0), (S,))
        xs, objs, feas = [], [], []
        for idx_c, real in self._chunk_index(chunk):
            d_c = d._replace(l=d.l[idx_c], u=d.u[idx_c],
                             lb=d.lb[idx_c], ub=d.ub[idx_c])
            st_c = st._replace(
                x=st.x[idx_c], yA=st.yA[idx_c], yB=st.yB[idx_c],
                zA=st.zA[idx_c], zB=st.zB[idx_c],
                pri_res=st.pri_res[idx_c], dua_res=st.dua_res[idx_c],
                pri_rel=st.pri_rel[idx_c], dua_rel=st.dua_rel[idx_c])
            x, o, f, _ = dive_integers(factors, d_c, q_b[idx_c],
                                       c0_b[idx_c], st_c,
                                       imask_b[idx_c], **kw)
            xs.append(x[:real])
            objs.append(o[:real])
            feas.append(f[:real])
        return (jnp.concatenate(xs), jnp.concatenate(objs),
                jnp.concatenate(feas), st)

    # ------------- the fused PH step -------------
    def solve_loop(self, w_on=True, prox_on=True, update=True, fixed=False,
                   dispatch=None):
        """One batched solve pass in the given mode; mirrors solve_loop
        (ref. phbase.py:999) + Compute_Xbar + Update_W fused. Returns the
        per-scenario *solved* objective (including the W term when w_on,
        which is what Ebound of a Lagrangian pass needs). ``fixed=True``
        selects the eq-boosted factorization for fully-pinned solves.
        With ``subproblem_chunk`` set below S, the solve microbatches
        over scenario chunks (see _solve_loop_chunked).

        ``dispatch`` (host int array of ascending scenario ids, APH's
        φ-dispatch — doc/aph.md): solve ONLY those scenarios. The
        dispatched ids microbatch into full-size chunks and scatter
        back; every other scenario's solution, duals, warm state, and
        objectives carry forward unchanged. Host-chunked loop only,
        and the pass must not run the W/x̄ update (the caller owns the
        reduction semantics over a partial solve)."""
        t0 = _time.perf_counter()
        obs.counter_add("ph.solve_loop_calls")
        chunk = int(self.options.get("subproblem_chunk", 0))
        # sharded engines read ``subproblem_chunk`` as the PER-DEVICE
        # microbatch bound (the device-call stability limit is per
        # device): a shard that already fits one chunk runs the fused
        # SPMD step; larger shards run the sharded chunked loop
        sh = self._shard_ops
        chunked = chunk > 0 and (chunk < sh.shard_size if sh is not None
                                 else chunk < self.batch.S)
        if self._stream_source is not None and not chunked:
            raise ValueError(
                "scenario streaming serves the CHUNKED hot loop only: "
                "subproblem_chunk must be positive and below the "
                f"(per-device) scenario count (got chunk={chunk}, "
                f"S={self.batch.S}) — see doc/streaming.md")
        if dispatch is not None:
            if not chunked or sh is not None:
                raise ValueError(
                    "dispatch-masked solves require the HOST-chunked "
                    "loop (subproblem_chunk below S on a single "
                    "device); sharded/fused engines use masked "
                    "acceptance instead — see doc/aph.md")
            if update:
                raise ValueError(
                    "dispatch-masked solves cannot run the W/xbar "
                    "update: the reduction would mix fresh and stale "
                    "rows silently (APH owns its own reduce)")
        if chunked:
            out = self._solve_loop_chunked(chunk, w_on, prox_on, update,
                                           fixed, dispatch=dispatch)
            if self._timing:
                # lint: ok[SYNC001] opt-in timing sync (report_timing), off by default
                jax.block_until_ready(self.x)
                self._solve_times.setdefault(
                    (bool(w_on), bool(prox_on), bool(fixed)), []).append(
                    _time.perf_counter() - t0)
            return out
        qp_state = self._ensure_state(prox_on, fixed)
        factors, data = self._get_factors(prox_on, fixed)
        # the fused path books the same per-phase anatomy as the
        # chunked loop (gate stays 0 — there is no recovery gate here),
        # so phase_timing()/telemetry spans exist for EVERY engine, not
        # only chunked ones. The clock starts after the factor fetch: a
        # first-call factorization is setup, not iteration anatomy.
        skey = ("fixed", bool(prox_on)) if fixed else bool(prox_on)
        # a full-width pass supersedes this mode's dispatch store (its
        # rows would go stale the moment the fused solve lands)
        self._qp_states.pop(("dispatch", skey), None)
        ent = self._phase_times.setdefault(skey, _new_phase_entry())
        ent["calls"] += 1
        ent["assemble_programs"] += 1   # the one un-chunked assembly
        obs.counter_add("ph.assemble_programs")
        ent["devices"] = sh.n_devices if sh is not None else 1
        ent["mode"] = "sharded" if sh is not None else "host"
        # per-device rows (see _solve_loop_chunked: the profitability
        # check amortizes the replicated L⁻¹ build against the LOCAL
        # shard's applies)
        rows_per_call = self._rows_per_call()
        plan = self._kernel_plan(skey, factors, rows_per_call)
        ent["kernel"] = plan.descriptor()
        ent["linv_build"] = plan.linv_build
        ent["f64_build"] = plan.f64_build
        ent["shape"] = self._solve_shape(factors, plan, rows_per_call)
        sp_args = {"mode": _mode_str(skey)} if obs.enabled() else None
        clock = _PhaseClock(ent["acc"], sp_args)
        # the ONE set of keyword values both spellings of the body hand
        # ``_solver_call``, and what its loops' exits are booked against
        kw = dict(prox_on=bool(prox_on), precision=self.sub_precision,
                  sub_max_iter=self.sub_max_iter, sub_eps=self.sub_eps,
                  sub_eps_hot=self.sub_eps_hot,
                  sub_eps_dua_hot=self.sub_eps_dua_hot,
                  tail_iter=self.sub_tail_iter,
                  stall_rel=self.sub_stall_rel, segment=self.sub_segment,
                  polish_hot=self.sub_polish_hot,
                  polish_chunk=int(self.options.get(
                      "subproblem_polish_chunk", 0)),
                  segment_lo=self.sub_segment_lo,
                  ir_sweeps=self.sub_ir_sweeps, kernel=plan)
        book_exits = partial(self._book_call_exits, ent,
                             ent["acc"]["solve"], _exit_tests(**kw))

        combine_fn = partial(self._mesh_combine, ent) \
            if sh is not None else None

        shrink = self._shrink if not fixed else None
        if shrink is not None:
            # compacted fused step (ops/shrink): assemble on the
            # gathered free-slot blocks, solve the compacted system,
            # expand, then reduce on the FULL blocks — the reduce math
            # (and therefore W/xbar/conv) is the uncompacted path's
            from ..ops.shrink import expand_solution
            fs = shrink.free_slots_dev
            ws = None if self._w_scale is None else self._w_scale[:, fs]
            q_c, bl_c, bu_c = _ph_assemble(
                data, shrink.c_c, self.W[:, fs], self.xbar[:, fs],
                self.rho[:, fs], shrink.idx_c,
                self._fixed_mask[:, fs], self._fixed_vals[:, fs], ws,
                w_on=bool(w_on), prox_on=bool(prox_on))
            d_c = data._replace(lb=bl_c, ub=bu_c)
            clock.lap("solve")
            wraps = _linv_wraps(plan, qp_state)
            with self._wheel_turn(self._S_orig, clock):
                qp_state, x_c, yA, yB = _solver_call(
                    factors, d_c, q_c, qp_state, **kw)
                self._wheel_ready(qp_state.pri_rel)
            fused = plan.mode == "fused"
            if fused:
                # phase honesty (see _ph_step): the fused wait must
                # land inside the solve lap
                packed = [packed_exit(qp_state)]
                # lint: ok[SYNC001] phase honesty for fused plans, same site contract as _ph_step
                jax.block_until_ready(packed)
            its, res = _book_admm_iters(ent["admm"], [qp_state], fused,
                                        wraps, packed if fused else None,
                                        self.sub_ir_sweeps)
            clock.lap("reduce")
            x = expand_solution(x_c, shrink.fixed_colvals,
                                shrink.keep_cols, shrink.fixed_cols,
                                self.c[0])
            xn, base_obj, solved_obj = _shrink_objs(
                x, self.c, self.c0, self.P_diag, self.W,
                self.nonant_idx, w_on=bool(w_on))
            dual_obj = _shrink_dual(
                d_c, q_c, self._shrink_dual_fold(shrink, w_on, prox_on),
                yA, yB, x_c)
            wmask = None if self._w_scale is None else self._w_scale > 0
            if combine_fn is None:
                xbar_new, xsqbar_new, W_new, conv = _ph_combine(
                    xn, self.prob, self.xbar_weights,
                    tuple(self.memberships), self.W, self.rho, wmask,
                    slot_slices=self.slot_bounds)
            else:
                xbar_new, xsqbar_new, W_new, conv = combine_fn(
                    xn, self.prob, self.xbar_weights, self.W, self.rho,
                    wmask)
            book_exits(its, res)    # beside the device's reduce
            clock.lap()
            self._qp_states[skey] = qp_state
            self.x, self.yA, self.yB = x, yA, yB
            if update:
                self.xbar, self.xsqbar = xbar_new, xsqbar_new
                self.W_new = W_new
                # lint: ok[SYNC001] THE per-iteration convergence scalar readback — the one designed sync (doc/pipelining.md)
                self.conv = float(conv)
                obs.gauge_set("ph.conv", self.conv)
            self._last_base_obj = base_obj
            self._last_solved_obj = solved_obj
            self._last_dual_obj = dual_obj
            if self._timing:
                # lint: ok[SYNC001] opt-in timing sync (report_timing), off by default
                jax.block_until_ready(x)
                self._solve_times.setdefault(
                    (bool(w_on), bool(prox_on), bool(fixed)), []).append(
                    _time.perf_counter() - t0)
            self._ext("post_solve")
            return solved_obj

        (qp_state, x, yA, yB, xn, xbar_new, xsqbar_new, W_new, conv,
         base_obj, solved_obj, dual_obj) = _ph_step(
            qp_state, factors, data, self.c, self.c0, self.P_diag,
            self.prob, self.xbar_weights, tuple(self.memberships),
            self.nonant_idx, self.W, self.xbar, self.rho,
            self._fixed_mask, self._fixed_vals, self._w_scale,
            w_on=bool(w_on), slot_slices=self.slot_bounds, lap=clock.lap,
            combine_fn=combine_fn, admm=ent["admm"], exits=book_exits,
            # an un-chunked engine's whole batch is its one chunk solve
            turn=None if self._wheel_port is None
            else partial(self._wheel_turn, self._S_orig, clock), **kw)
        clock.lap()
        self._qp_states[skey] = qp_state
        self.x, self.yA, self.yB = x, yA, yB
        if update:
            self.xbar, self.xsqbar = xbar_new, xsqbar_new
            self.W_new = W_new
            # lint: ok[SYNC001] THE per-iteration convergence scalar readback — the one designed sync (doc/pipelining.md)
            self.conv = float(conv)
            obs.gauge_set("ph.conv", self.conv)
        self._last_base_obj = base_obj
        self._last_solved_obj = solved_obj
        self._last_dual_obj = dual_obj
        if self._timing:
            # the sync exists only to time honestly; without the option it
            # is skipped so host work keeps overlapping device compute
            # lint: ok[SYNC001] opt-in timing sync (report_timing), off by default
            jax.block_until_ready(x)
            self._solve_times.setdefault(
                (bool(w_on), bool(prox_on), bool(fixed)), []).append(
                _time.perf_counter() - t0)
        self._ext("post_solve")  # after-each-solve hook (ref. phbase.py:955)
        return solved_obj

    def report_timing(self):
        """Solve-time splits min/mean/max per mode (ref. spbase.py:261-269
        display_timing; the reference gathers instance-creation /
        set-objective / solve times to rank 0 — here the modes play the
        role of the phases). Returns {mode: (count, min, mean, max)}."""
        out = {}
        for key, ts in sorted(self._solve_times.items()):
            w_on, prox_on, fixed = key
            name = f"w={int(w_on)} prox={int(prox_on)}" \
                + (" fixed" if fixed else "")
            out[name] = (len(ts), min(ts), sum(ts) / len(ts), max(ts))
        if self.verbose:
            for name, (n, lo, mean, hi) in out.items():
                global_toc(f"solve_loop[{name}]: n={n} "
                           f"min/mean/max = {lo:.3f}/{mean:.3f}/{hi:.3f} s")
        return out

    def iter0_feasible_mask(self, tol=None):
        """(ok_per_scenario, tol): the ONE iter-0 feasibility predicate —
        a scenario passes on EITHER the absolute or the relative primal
        residual, threshold scaling with the solve tolerance. Shared by
        assert_feasible_iter0 and the sharded APH's collective gate."""
        if tol is None:
            tol = float(self.options.get("iter0_feas_tol",
                                         max(1e-3, 100 * self.sub_eps)))
        st = self._qp_states[False]
        # mesh pad rows are trimmed: they duplicate a real scenario and
        # must neither mask nor fabricate an infeasibility
        ok = (np.asarray(st.pri_res)[:self._S_orig] <= tol) \
            | (np.asarray(st.pri_rel)[:self._S_orig] <= tol)
        return ok, tol

    def assert_feasible_iter0(self, tol=None):
        """Abort when any scenario's iter-0 subproblem came out infeasible
        — the analog of the reference quitting when a scenario is
        infeasible or probabilities are off at iter 0
        (ref. phbase.py:1415-1427 _update_E1 / feas_prob abort). Gated by
        the ``iter0_infeasibility_abort`` option (default on). Like every
        other feasibility predicate here, a scenario passes on EITHER the
        absolute or the relative primal residual; the threshold scales
        with the configured solve tolerance (a converged feasible solve
        sits at ~sub_eps, an infeasible one orders of magnitude above)."""
        if not self.options.get("iter0_infeasibility_abort", True):
            return
        ok, tol = self.iter0_feasible_mask(tol)
        if not np.all(ok):
            bad = np.flatnonzero(~ok)
            names = [self.batch.tree.scen_names[i] for i in bad[:5]]
            raise RuntimeError(
                f"iter0: {bad.size} scenario subproblem(s) infeasible "
                f"(pri_rel > {tol:g}), e.g. {names} — aborting like the "
                "reference's iter-0 infeasibility quit "
                "(ref. phbase.py:1415-1427)")

    # ------------- reference-named primitives -------------
    def Compute_Xbar(self):
        xn = self.nonants_of(self.x)
        self.xbar = self.compute_xbar(xn)
        self.xsqbar = self.compute_xbar(xn * xn)

    def Update_W(self):
        xn = self.nonants_of(self.x)
        W = self.W + self.rho * (xn - self.xbar)
        if self._w_scale is not None:
            W = jnp.where(self._w_scale > 0, W, 0.0)
        self.W = W

    def Ebound(self):
        """Expected certified subproblem lower bound (ref. phbase.py:314
        Ebound). Built from the ADMM dual vectors, NOT the primal
        objectives — an inexact primal solve over-estimates the minimum and
        would produce an invalid outer bound. Meaningful for prox-off
        solves (trivial bound, Lagrangian spokes)."""
        return float(self.Eobjective(self._last_dual_obj))

    def update_best_bound(self, bound):
        """Monotone best-outer-bound bookkeeping: accept an incremental
        improvement from ANY source — the engine's own Ebound, a
        device-dual bounder spoke, or the exact host oracle harvested
        through the hub — and ignore everything else. Returns True when
        the best bound moved. This is the engine-side half of the
        hub/spoke incremental-bound contract (the hub's
        OuterBoundUpdate is the wheel-side half)."""
        if bound is None:
            return False
        b = float(bound)
        if np.isfinite(b) and b > self.best_bound:
            self.best_bound = b
            return True
        return False

    def Eobjective_value(self):
        return float(self.Eobjective(self._last_base_obj))

    def W_disabled_Ebound(self):
        return float(self.Eobjective(self._last_base_obj))

    # ------------- fixing (ref. phbase.py:413, xhat_tryer.py:126) -------------
    def fix_nonants(self, values, mask=None):
        """Pin nonant slots to `values` ((S,K) or (K,)); mask selects slots."""
        t = self.dtype
        vals = jnp.broadcast_to(jnp.asarray(values, t), (self.batch.S, self.batch.K))
        self._fixed_vals = vals
        self._fixed_mask = (jnp.ones_like(vals, bool) if mask is None
                            else jnp.broadcast_to(jnp.asarray(mask, bool), vals.shape))

    def unfix_nonants(self):
        self._fixed_mask = jnp.zeros((self.batch.S, self.batch.K), bool)

    # ------------- incumbent evaluation (ref. utils/xhat_tryer.py:126-182) -------------
    @property
    def nonant_integer_mask(self):
        """(K,) bool: which nonant slots are integer variables."""
        return np.asarray(self.batch.integer)[np.asarray(self.batch.nonant_idx)]

    def round_nonants(self, vals):
        """Round integer nonant slots to the nearest integer (the incumbent
        heuristics' stand-in for MIP feasibility of first-stage vars)."""
        vals = np.asarray(vals, dtype=np.float64)
        mask = self.nonant_integer_mask
        return np.where(mask, np.round(vals), vals)

    def calculate_incumbent(self, xhat_vals, feas_tol=None, pin_mask=None):
        """Fix nonants at `xhat_vals` ((K,) or (S,K)), solve with W/prox off,
        and return the expected objective, or None if any scenario's
        subproblem is infeasible at that x̂ (ref. xhat_tryer.py:159-182
        calculate_incumbent, xhatbase.py:129-134 infeasibility => no bound).
        Feasibility = primal residual of the batched solve below tolerance,
        absolute or relative to problem scale (the solver terminates on the
        relative criterion, so large-coefficient models can't hit a tight
        absolute residual).

        ``pin_mask`` ((K,) bool, default all): pin only those nonant
        slots. For models whose nonant blocks contain DERIVED variables
        (UC: the startup indicators are determined by the commitment
        through st_t >= u_t − u_{t−1} and positive startup costs), the
        derived slots are left to the solve — they come out identical
        across scenarios (a deterministic function of the pinned
        block), so the incumbent stays nonanticipative and the bound
        valid, while pinning them independently would fight the
        coupling rows.
        """
        if feas_tol is None:
            feas_tol = float(self.options.get("xhat_feas_tol", 1e-4))
        # snapshot engine state: this can run mid-iteration (XhatClosest
        # miditer, spokes sharing an engine) and must not clobber the
        # subproblem solutions the hub ships / convergers read, nor wipe a
        # Fixer's pinned slots
        saved = (self._fixed_mask, self._fixed_vals, self.x,
                 getattr(self, "yA", None), getattr(self, "yB", None),
                 getattr(self, "_last_base_obj", None),
                 getattr(self, "_last_solved_obj", None),
                 getattr(self, "_last_dual_obj", None))
        self.fix_nonants(self.round_nonants(xhat_vals), mask=pin_mask)
        try:
            # integer columns OUTSIDE the nonant set (second-stage
            # integers) need a dive to integral values — the reference
            # gets this for free from its MIP subproblem solver
            # (ref. xhatbase.py:117 solves fixed-nonant MIPs)
            n = self.batch.n
            nonant_cols = np.zeros(n, bool)
            nonant_cols[np.asarray(self.batch.nonant_idx)] = True
            rec_ints = np.asarray(self.batch.integer) & ~nonant_cols
            if rec_ints.any() and self.options.get("xhat_dive_integers",
                                                   True):
                if self._stream_source is not None:
                    raise RuntimeError(
                        "recourse-integer dives read the full-width "
                        "cost/bound blocks, which a streamed/"
                        "synthesized scenario source never ships "
                        "(doc/streaming.md v1 scope)")
                factors, d0 = self._get_factors(False, fixed=True)
                idx = self.nonant_idx
                lb = d0.lb.at[:, idx].set(
                    jnp.where(self._fixed_mask, self._fixed_vals,
                              d0.lb[:, idx]))
                ub = d0.ub.at[:, idx].set(
                    jnp.where(self._fixed_mask, self._fixed_vals,
                              d0.ub[:, idx]))
                d = d0._replace(lb=lb, ub=ub)
                st = self._ensure_state(False, fixed=True)
                x, obj, feasible, _ = self._dive_in_chunks(
                    factors, d, self.c, self.c0, st, rec_ints,
                    max_iter=self.sub_max_iter, eps=self.sub_eps,
                    feas_tol=feas_tol,
                    polish_chunk=int(self.options.get(
                        "subproblem_polish_chunk", 0)))
                if not bool(jnp.all(feasible)):
                    return None
                self._incumbent_rows = obj
                return float(self.Eobjective(obj))
            self.solve_loop(w_on=False, prox_on=False, update=False,
                            fixed=True)
            st = self._qp_states[("fixed", False)]
            pri = np.asarray(st.pri_res)
            rel = np.asarray(st.pri_rel)
            if not np.all((pri <= feas_tol) | (rel <= feas_tol)):
                # an infeasible candidate leaves a DIVERGED state
                # behind (blown rho_scale, ~1e9 duals measured on
                # farmer): warm-starting the NEXT candidate from it can
                # "converge" by the corrupt scale's relative criteria
                # to a wrong objective. Drop it so the next evaluation
                # restarts clean (ISSUE 9: surfaced by the pool
                # equivalence tests; the candidate streams of every x̂
                # spoke hit the same sequence). Chunked engines keep
                # the authoritative warm starts under the "chunks" key
                # — both must go, or the next chunked solve warm-starts
                # from the same diverged states.
                self._qp_states.pop(("fixed", False), None)
                self._qp_states.pop(("chunks", ("fixed", False)), None)
                return None
            # the per-scenario values behind the expectation returned
            # (the x̂ spokes keep the published candidate's)
            self._incumbent_rows = self._last_base_obj
            return self.Eobjective_value()
        finally:
            (self._fixed_mask, self._fixed_vals, self.x, self.yA, self.yB,
             self._last_base_obj, self._last_solved_obj,
             self._last_dual_obj) = saved

    def dive_nonant_candidates(self, X=None, feas_tol=None, max_iter=None,
                               dive_slots=None):
        """Per-scenario INTEGER-FEASIBLE nonant schedules via the batched
        dive — incumbent candidates for the x̂ spokes on integer models.

        Rounding a fractional LP nonant block (the reference-shaped
        candidate source) routinely breaks covering rows with no slack
        (UC reserve: rounded-down commitments force VOLL shedding);
        the reference never sees this because its subproblem solves are
        MIPs whose first stages are already integral
        (ref. xhatshufflelooper_bounder.py:108 uses solved scenario
        values). The TPU analog: dive every scenario's subproblem to
        integer feasibility on the NONANT integer mask, prox-regularized
        toward ``X`` (the hub's consensus) when given — strongly convex
        inner solves, candidates that track the hub's trajectory.

        ``dive_slots`` ((K,) bool, default all): restrict the dive to
        those nonant slots' integer columns — the candidate side of
        calculate_incumbent's ``pin_mask`` (DERIVED nonants like UC's
        startup indicators must not be dived independently of the
        commitments that determine them; diving both fights the
        coupling rows and returns nothing feasible).

        Returns (cands (S, K), feasible (S,) bool)."""
        if self._stream_source is not None:
            raise RuntimeError(
                "dive_nonant_candidates reads the full-width scenario blocks, which a "
                "streamed/synthesized scenario source never ships "
                "(doc/streaming.md v1 scope)")
        if feas_tol is None:
            # the df32 kernel's residual floor under heavily pinned
            # bounds sits near 1e-3 — a gate AT the floor rejects every
            # candidate; consumers that need certainty re-evaluate the
            # winners exactly (xhat_exact_eval / host oracle)
            feas_tol = 5e-3 if self.sub_precision == "df32" else 1e-3
        n = self.batch.n
        idx_np = np.asarray(self.batch.nonant_idx)
        imask = np.zeros(n, bool)
        imask[idx_np] = np.asarray(self.batch.integer)[idx_np]
        if dive_slots is not None:
            keep = np.zeros(n, bool)
            keep[idx_np[np.asarray(dive_slots, bool)]] = True
            imask &= keep
        if not imask.any():
            xn = self._hub_nonants() if X is None else jnp.asarray(X)
            return np.asarray(xn), np.ones(self.batch.S, bool)
        prox_on = X is not None
        # full=True: the dive's q/imask are built full-width against
        # self.c — while a shrink plan is active the hot-loop factors
        # are compacted and would mismatch (see _get_factors)
        factors, d = self._get_factors(prox_on, full=True)
        if prox_on:
            q = self.c.at[:, self.nonant_idx].add(
                -self.rho * jnp.asarray(X, self.dtype))
        else:
            q = self.c
        if self._shrink is None:
            st = self._ensure_state(prox_on)
        else:
            # the cached hot-loop state is compacted — dive from a
            # full-width cold state instead of clobbering it
            st = self._cold_state(factors, d)
        # aggressiveness knobs for reference-scale dives (VERDICT r4
        # #5): pin_frac=2 pins half the remaining columns per round
        # (~11 rounds on 4320 commitments vs ~60 at the default 8);
        # xhat_dive_rounds hard-caps the round count. More aggression
        # = fewer solves but more single-pin retries/dead scenarios —
        # the exact evaluator stays the feasibility gate either way.
        kw = {}
        pf = self.options.get("xhat_dive_pin_frac")
        if pf is not None:
            kw["pin_frac"] = int(pf)
        mr = self.options.get("xhat_dive_rounds")
        if mr is not None:
            kw["max_rounds"] = int(mr)
        x, _, feasible, _ = self._dive_in_chunks(
            factors, d, q, self.c0, st, jnp.asarray(imask),
            max_iter=int(max_iter or min(self.sub_max_iter, 1500)),
            eps=max(self.sub_eps, 1e-6), feas_tol=feas_tol,
            polish_chunk=int(self.options.get("subproblem_polish_chunk",
                                              0)), **kw)
        return np.asarray(x)[:, idx_np], np.asarray(feasible)

    def _hub_nonants(self):
        """(S, K) latest subproblem nonant values for cylinder traffic
        (ref. phbase.py:562-617 nonant flat caches)."""
        return self.nonants_of(self.x)

    # ------------- batched incumbent-pool evaluation -------------
    def _pool_chunk_index(self, P, chunk):
        """(scenario_idx, candidate_idx, real) per pool chunk: pool
        solves linearize the (candidate, scenario) grid as rows
        r = p*S + s and microbatch them exactly like the PH hot loop
        (``subproblem_chunk`` bounds the rows per solve call; the tail
        chunk pads by repeating its last row so every call compiles
        once). Cached beside the PH chunk index (same invalidation)."""
        S = self.batch.S
        rows = P * S
        if not hasattr(self, "_chunk_idx_cache"):
            self._chunk_idx_cache = {}
        key = ("pool", P, chunk, S)
        if key not in self._chunk_idx_cache:
            out = []
            for i in range(0, rows, chunk):
                r = np.arange(i, min(i + chunk, rows))
                real = r.size
                if real < chunk:
                    r = np.concatenate([r, np.full(chunk - real, r[-1])])
                out.append((jnp.asarray(r % S), jnp.asarray(r // S), real))
            self._chunk_idx_cache[key] = out
        return self._chunk_idx_cache[key]

    def evaluate_incumbent_pool(self, pool, pin_mask=None, feas_tol=None):
        """Batched fix-and-dive evaluation of a (P, K) candidate pool
        (ops/incumbent, doc/incumbents.md): every candidate's pinned
        nonant slots are fixed (l = u = x̂ bound tightening) across ALL
        scenarios, the continuous recourse re-solves through the
        standard donated warm-start kernel path
        (``subproblem_kernel_mode`` honored — the pool rows are
        literally more chunks of the pipelined dispatch), and the
        feasibility screen + Eobjective land in ONE stacked D2H verdict
        per call (``incumbent.gate_syncs`` stays O(1) per round on any
        mesh). Returns host ``(objs (P,), feasible (P,) bool)`` with
        infeasible candidates' objectives at +inf.

        The vmapped-over-the-pool-axis semantics are exactly P
        sequential ``calculate_incumbent`` calls (the equivalence is
        pinned by tests/test_incumbent.py); the batched spelling costs
        one warm-started chunk pass instead of P full solve_loop
        passes. Falls back to that sequential path for the shapes the
        chunked solver cannot batch (per-scenario A) or that need the
        per-candidate recourse-integer dive."""
        if self._stream_source is not None:
            raise RuntimeError(
                "evaluate_incumbent_pool reads the full-width scenario blocks, which a "
                "streamed/synthesized scenario source never ships "
                "(doc/streaming.md v1 scope)")
        if feas_tol is None:
            feas_tol = float(self.options.get("xhat_feas_tol", 1e-4))
        pool = jnp.asarray(pool, self.dtype)
        P, S = int(pool.shape[0]), self.batch.S
        n = self.batch.n
        idx_np = np.asarray(self.batch.nonant_idx)
        nonant_cols = np.zeros(n, bool)
        nonant_cols[idx_np] = True
        rec_ints = np.asarray(self.batch.integer, bool) & ~nonant_cols
        factors, d0 = self._get_factors(False, fixed=True)
        if (rec_ints.any() and self.options.get("xhat_dive_integers",
                                                True)) \
                or factors.A_s.ndim != 2:
            # integer RECOURSE columns need the per-candidate dive, and
            # per-scenario matrices carry per-scenario factors the
            # pool's shared-factor chunking cannot batch — evaluate
            # sequentially through the reference path instead
            objs = np.full(P, np.inf)
            feas = np.zeros(P, bool)
            for p in range(P):
                v = self.calculate_incumbent(np.asarray(pool[p]),
                                             feas_tol=feas_tol,
                                             pin_mask=pin_mask)
                if v is not None:
                    objs[p] = v
                    feas[p] = True
            obs.counter_add("incumbent.gate_syncs", P)
            return objs, feas
        from ..ops.incumbent import pool_verdict
        from ..ops.qp_solver import SplitMatrix, qp_objective
        K = self.batch.K
        pin = np.ones(K, bool) if pin_mask is None \
            else np.asarray(pin_mask, bool)
        # integral snap on the integer slots the candidate pins —
        # build_pool rows are already integral; snapping here keeps the
        # calculate_incumbent round_nonants contract for raw callers
        imask = jnp.asarray(self.nonant_integer_mask)
        vals = jnp.where(imask, jnp.round(pool), pool)
        pmb = jnp.asarray(pin)
        rows = P * S
        copt = int(self.options.get("subproblem_chunk", 0))
        chunk = copt if (copt and copt < rows) else rows
        slices = self._pool_chunk_index(P, chunk)
        plan = self._kernel_plan(("fixed", False), factors, chunk)
        polish_chunk = int(self.options.get("subproblem_polish_chunk", 0))
        kw = dict(prox_on=False, precision=self.sub_precision,
                  sub_max_iter=self.sub_max_iter, sub_eps=self.sub_eps,
                  sub_eps_hot=self.sub_eps_hot,
                  sub_eps_dua_hot=self.sub_eps_dua_hot,
                  tail_iter=self.sub_tail_iter,
                  stall_rel=self.sub_stall_rel, segment=self.sub_segment,
                  polish_hot=self.sub_polish_hot,
                  polish_chunk=polish_chunk,
                  segment_lo=self.sub_segment_lo,
                  ir_sweeps=self.sub_ir_sweeps, kernel=plan,
                  # FIXED stepsize: shared-mode rho adaptation is a
                  # geometric mean over the batch rows, and a pool
                  # always contains infeasible members whose diverging
                  # ratios contaminate the shared scalar (measured 13%
                  # objective inflation on the feasible UC candidate) —
                  # the eq-boosted fixed-mode rho pattern carries the
                  # pinned solves fine at scale 1
                  adaptive_rho=False)
        ck = (P, chunk)
        if ck in self._pool_dirty:
            # a previous donating pass died mid-flight: its cached
            # states reference deleted buffers — rebuild cold
            self._pool_states.pop(ck, None)
            self._pool_dirty.discard(ck)
        states = self._pool_states.get(ck)
        fresh = states is None
        if fresh:
            # ONE cold state serves every chunk (identical shapes,
            # immutable buffers — see _ensure_chunk_states); donation
            # waits for the first completed pass to privatize them
            sidx0, pidx0, _ = slices[0]
            lb0, ub0, l0, u0, _, _ = _pool_assemble(
                d0.lb, d0.ub, d0.l, d0.u, self.c, self.c0, vals, pmb,
                self.nonant_idx, sidx0, pidx0)
            st0 = qp_cold_state(factors, d0._replace(lb=lb0, ub=ub0,
                                                     l=l0, u=u0))
            states = [st0] * len(slices)
            self._pool_states[ck] = states
        donate = (not fresh) \
            and bool(int(self.options.get("subproblem_pipeline", 1)))
        if donate:
            self._pool_dirty.add(ck)
            obs.counter_add("qp.donated_passes")
        split_mode = isinstance(factors.A_s, SplitMatrix)
        prev_st = None
        flow0 = self._take_flowed_factor(states, split_mode and donate)
        outs = []
        for ci, (sidx, pidx, _) in enumerate(slices):
            lb_c, ub_c, l_c, u_c, q_c, c0_c = _pool_assemble(
                d0.lb, d0.ub, d0.l, d0.u, self.c, self.c0, vals, pmb,
                self.nonant_idx, sidx, pidx)
            d_c = d0._replace(lb=lb_c, ub=ub_c, l=l_c, u=u_c)
            st_in = states[ci]
            if split_mode and prev_st is not None:
                # df32 chunks FLOW one (rho_scale, factor) pair — the
                # chunked hot loop's HBM discipline (one ~GB factor
                # alive, not one per chunk)
                st_in = st_in._replace(L=prev_st.L,
                                       rho_scale=prev_st.rho_scale)
            elif flow0 is not None:
                st_in, flow0 = st_in._replace(L=flow0), None
            with self._wheel_turn(slices[ci][2]):
                st, x, _, _ = _solver_call(factors, d_c, q_c, st_in,
                                           donate=donate, **kw)
                self._wheel_ready(st.pri_rel)
            prev_st = st
            if split_mode:
                st = st._replace(L=jnp.zeros((), jnp.float32))
            states[ci] = st
            outs.append((qp_objective(d_c, q_c, c0_c, x),
                         st.pri_res, st.pri_rel))
        if split_mode and prev_st is not None:
            for ci in range(len(states)):
                states[ci] = states[ci]._replace(
                    L=prev_st.L, rho_scale=prev_st.rho_scale)
        # donation window closed: states are solve outputs with
        # privately owned buffers — the next round may donate them
        self._pool_dirty.discard(ck)
        obj_rows = jnp.concatenate([o for o, _, _ in outs])[:rows]
        # the screen's per-row objectives, row r = p * S + s, beside
        # the verdict (the pool spoke keeps a round's in ``last_screen``)
        self._pool_obj_rows = obj_rows
        pri_res = jnp.concatenate([r for _, r, _ in outs])[:rows]
        pri_rel = jnp.concatenate([r for _, _, r in outs])[:rows]
        live = jnp.asarray(np.arange(S) < self._S_orig)
        v = np.asarray(pool_verdict(obj_rows, pri_res, pri_rel, self.prob,
                                    live, feas_tol, P=P, S=S))
        # THE one stacked D2H of the round (the chunked loop's fused-
        # gate discipline — doc/pipelining.md)
        obs.counter_add("incumbent.gate_syncs")
        # the screen's chunk solves book like every other solve path's
        # (``phase_timing(("pool", False))``: ADMM counts and how each
        # solve ended), behind the verdict: scalar copies, no new wait
        ent = self._phase_times.setdefault(("pool", False),
                                           _new_phase_entry())
        ent["calls"] += 1
        ent["kernel"] = plan.descriptor()
        ent["linv_build"] = plan.linv_build
        ent["f64_build"] = plan.f64_build
        its, _ = _book_admm_iters(ent["admm"], states,
                                  plan.mode == "fused")
        _book_exits(ent["exits"], its, _exit_tests(**kw), 0.0)
        if obs.enabled():
            obs.counter_add("xfer.d2h_bytes", v.nbytes)
        feas = v[1] > 0.5
        # cold-reset the infeasible candidates' rows before the
        # states are reused as next round's warm starts (see
        # _pool_rows_zeroed); tail-chunk pad rows duplicate the
        # LAST candidate's rows, so they inherit ITS verdict — a
        # blanket keep would preserve diverged pad iterates when
        # that candidate is infeasible. Run EVERY round (an
        # all-feasible round keeps every row): the program is then
        # compiled by the first round, not by the first infeasible
        # verdict minutes into a run
        keep = np.repeat(feas, S)
        keep = np.concatenate(
            [keep, np.full(len(slices) * chunk - rows, feas[-1])])
        for ci in range(len(states)):
            kc = jnp.asarray(keep[ci * chunk:(ci + 1) * chunk])
            st = states[ci]
            x_z, yA_z, yB_z, zA_z, zB_z = _pool_rows_zeroed(
                st.x, st.yA, st.yB, st.zA, st.zB, kc)
            states[ci] = st._replace(x=x_z, yA=yA_z, yB=yB_z,
                                     zA=zA_z, zB=zB_z)
        objs = np.where(feas, v[0], np.inf)
        return objs, feas

    # ------------- extension hooks (ref. extensions/extension.py:14) -------------
    def _ext(self, hook):
        if self.extensions is not None:
            getattr(self.extensions, hook)(self)


class PH(PHBase):
    """Synchronous PH driver (ref. mpisppy/opt/ph.py:26 ph_main)."""

    def ph_main(self, finalize=True):
        with self.run_span():
            return self._ph_main(finalize)

    def _ph_main(self, finalize):
        self._ext("pre_iter0")
        # Iter 0: no W, no prox (ref. phbase.py:1364 Iter0). A warm start
        # (WXBarReader / load_state, or a checkpoint-bundle resume —
        # ckpt.manager.resume_hub installs through the same
        # install_state_arrays body) keeps the loaded W and solves with it
        # on — the dual bound of that pass is a valid Lagrangian bound since
        # PH-generated W satisfies sum_s p_s W_s = 0 per node. An xbar-only
        # warm start keeps the loaded prox center: iter 0 must not
        # overwrite it (solve still runs for x/W/bounds).
        warm = getattr(self, "_warm_started", False)
        # only an ACTUAL xbar load suppresses the iter-0 xbar update — a
        # W-only warm start must still compute xbar from the solutions or
        # iter 1 would prox toward the zeros initialization
        warm_xbar = getattr(self, "_warm_started_xbar", False)
        self.solve_loop(w_on=warm, prox_on=False, update=not warm_xbar)
        self.assert_feasible_iter0()
        if not warm:
            self.Update_W()  # W was zero, so W = rho(x - xbar)
        self.trivial_bound = self.Ebound()  # certified wait-and-see bound
        self.update_best_bound(self.trivial_bound)
        self._iter = 0
        obs.event("ph.iter0", {"trivial_bound": self.trivial_bound})
        self._ext("post_iter0")
        if self.converger_cls is not None:
            self.converger = self.converger_cls(self)
        global_toc(f"PH iter 0: trivial bound = {self.trivial_bound:.4f}",
                   self.verbose)
        if self.spcomm is not None:
            # iter-0 sync: push the first W / nonants and collect any
            # bounds the host-oracle spokes produced while the device
            # ran iter 0. The reference's hub first syncs inside
            # iterk_loop (ref. phbase.py:1522), an artifact of its
            # solver-bound startup; with asynchronous host bound spokes
            # a whole wheel can be within tolerance before iter 1.
            self.spcomm.sync()
            self.update_best_bound(
                getattr(self.spcomm, "BestOuterBound", None))
            if self.spcomm.is_converged():
                global_toc("PH iter 0: hub termination", self.verbose)
                if finalize:
                    return self.post_loops()
                return self.conv

        # Iter k loop (ref. phbase.py:1472 iterk_loop)
        pt0 = ctr0 = None
        for it in range(1, self.max_iterations + 1):
            self._iter = it
            rec_on = obs.enabled()
            if rec_on and ctr0 is None:
                # snapshots for the per-iteration convergence record:
                # phase wall-clock totals and the recovery/compile
                # counters, diffed after the solve. Only the FIRST
                # window opens here — later windows open at the
                # previous record's close below, so counters booked by
                # miditer extensions (device fixing, a compaction
                # transition's restage) land in the next iteration's
                # deltas instead of a bookkeeping gap between the
                # record and the next top-of-loop snapshot.
                pt0 = self._phase_totals()
                ctr0 = obs.counters_snapshot()
            sp_args = {"iter": it} if rec_on else None
            with obs.span("ph.iteration", cat="ph", args=sp_args) as sp_it:
                self.solve_loop(w_on=True, prox_on=True)
                self.W = self.W_new
            if it == 1 and self._wheel_port is not None:
                # three engines share this HBM: the iter-0 mode's warm
                # states and factor have served (the hot mode took its
                # warm start from them above)
                self._free_mode(False)
            if rec_on:
                obs.histogram_observe("ph.iteration_seconds",
                                      sp_it.seconds)
                obs.event("ph.iteration", self.iteration_record(
                    it, sp_it.seconds, pt0, ctr0))
                pt0 = self._phase_totals()
                ctr0 = obs.counters_snapshot()
                # device memory watermark gauges (guarded no-op on
                # backends without allocator stats, e.g. CPU)
                _obs_resource.sample_memory()
            self._ext("miditer")
            if self.spcomm is not None:
                self.spcomm.sync()
                # incremental best-bound bookkeeping: spoke bounds
                # (device-dual or exact-oracle) flow back to the engine
                self.update_best_bound(
                    getattr(self.spcomm, "BestOuterBound", None))
                if self.spcomm.is_converged():
                    global_toc(f"PH iter {it}: hub termination", self.verbose)
                    break
            if self.converger is not None and self.converger.is_converged():
                global_toc(f"PH iter {it}: converger termination", self.verbose)
                break
            if self.conv is not None and self.conv < self.convthresh:
                global_toc(f"PH iter {it}: conv={self.conv:.3e} < thresh",
                           self.verbose)
                break
            self._ext("enditer")
            if self.verbose and (it % 10 == 0 or it == 1):
                global_toc(f"PH iter {it}: conv={self.conv:.6e} "
                           f"Eobj={self.Eobjective_value():.4f}")
        if finalize:
            return self.post_loops()
        return self.conv

    def post_loops(self):
        """ref. phbase.py:1568: final Eobjective and extension wrap-up."""
        self._ext("post_everything")
        return self.conv, self.Eobjective_value(), self.trivial_bound
