"""FWPH: Frank–Wolfe Progressive Hedging (Boland et al. 2018).

The reference (ref. mpisppy/fwph/fwph.py:52-1043) pairs each scenario MIP with
a companion QP over the convex hull of discovered MIP solutions, runs a
Simplicial Decomposition Method inner loop (solve QP → set W → solve MIP →
add column → Γ check, ref. fwph.py:210-303 SDM), swaps the nonant pointers
so PH's x̄/W updates read the *QP* solutions (ref. fwph.py:989-1018
_swap_nonant_vars), and publishes a Lagrangian dual bound from the inner
linearized solves (ref. fwph.py:526 _compute_dual_bound). Two-stage only,
like the reference (ref. fwph.py:439-442).

TPU redesign (doc/fwph.md):
- the column pool is a statically shaped rolling buffer (S, C, n): slots
  start as copies of the iter-0 solution and are overwritten round-robin —
  the padded-max-columns answer to Pyomo's dynamically growing `a` vars.
  Beside it live the two things the weight QP reads of it, the nonant
  block (S, C, K) and the base costs c·column (S, C): a pass writes ONE
  slot of each in place (``_column_step``), nothing is gathered or
  multiplied over the whole pool;
- the weight QP batches over scenarios via ops/simplex_qp (accelerated
  projected gradient over the simplex);
- the linearized ("MIP") subproblem is one batched ADMM solve with the
  KKT factor shared with plain PH (prox-off mode), warm-started across
  iterations;
- the dual bound is taken at the *first* SDM pass of each outer iteration,
  where E[w] = 0 holds exactly (W from the PH update plus ρ(x_t − x̄) with
  x̄ = E[x_t]), so the published bound is a certified Lagrangian bound
  built from the ADMM dual vectors. The engine measures that manifold
  itself, in its own arithmetic, before the bound is published: a bound
  off it is dropped and counted;
- a pass reads ONE row back: Γ, the scale of its stop test, the bound and
  the manifold's error (``_column_step``'s four scalars).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import global_toc, obs
from ..ops.simplex_qp import simplex_qp_solve
from .ph import PHBase, _ph_combine
from .spbase import compute_xbar

# a Lagrangian bound is an outer bound only where Σ_s p_s w_s = 0 slot
# by slot: |node mean of w_t| over max|w_t| must sit under this (the
# engine's rounding reads 1e-14 in float64; an f32 engine gets its own
# epsilon's room)
MANIFOLD_TOL = 1e-9


def _new_fw_times():
    """``FWPH.phase_timing()["fwph"]``: totals since the last reset.
    ``iterations`` (outer), ``passes`` (SDM), ``passes_ended_by_gamma``
    (passes whose Γ test ended their SDM loop), ``host_reads`` (device
    values read back: one row a pass, conv once an iteration), the host
    seconds of the spans ``fwph.linearized`` / ``fwph.column`` (the
    launch and the wait for the in-place slot write) /
    ``fwph.bound_gamma`` (the pass's one read) / ``fwph.simplex_qp``
    (launch and wait) / ``fwph.update``, the first-pass bounds that
    were published and dropped (off the Σ p w = 0 manifold), the
    columns written, how often the round-robin slot came back to 0,
    and the QP's FISTA trips."""
    return {"iterations": 0, "passes": 0, "passes_ended_by_gamma": 0,
            "host_reads": 0, "linearized_seconds": 0.0,
            "column_seconds": 0.0, "qp_seconds": 0.0,
            "bound_gamma_seconds": 0.0, "update_seconds": 0.0,
            "bounds_published": 0, "bounds_dropped": 0,
            "columns_written": 0, "pool_wraps": 0, "qp_iters": 0}


@partial(jax.jit, static_argnames=("n_slots",))
def _pool_init(x0, c, idx, *, n_slots):
    """The pool, its nonant block and its base costs, every slot a copy
    of ``x0``: three buffers of their own (a broadcast view could not
    be written in place)."""
    rep = lambda v: jnp.repeat(v[:, None], n_slots, axis=1)
    return rep(x0), rep(x0[:, idx]), rep(jnp.sum(c * x0, axis=-1))


# the two big buffers are donated: XLA updates them where they lie. The
# (S, C) base costs are not: a pass's Γ is made from the base costs of
# the pool BEFORE its column, and who holds that array keeps it
@partial(jax.jit, donate_argnums=(0, 1), static_argnames=("slot_slices",))
def _column_step(columns, G, base, a, xn_t, w_t, x_star, dual_obj, c, c0,
                 prob, xbar_w, memberships, idx, slot, *, slot_slices):
    """Everything of an SDM pass between the linearized solve and the
    QP, as ONE program: the two linearizations and Γ (ref. fwph.py:
    254-271), the expected certified bound, how far w_t lies off the
    Σ p w = 0 manifold, and the new column written into its slot.
    Returns the pool's three buffers and the pass's ONE row for the
    host: [Γ, E[lin_t], bound, manifold error]."""
    lin_t = (jnp.sum(base * a, axis=-1) + c0
             + jnp.sum(w_t * xn_t, axis=-1))
    lin_star = (jnp.sum(c * x_star, axis=-1) + c0
                + jnp.sum(w_t * x_star[:, idx], axis=-1))
    off = jnp.max(jnp.abs(compute_xbar(memberships, slot_slices, xbar_w,
                                       w_t))) \
        / jnp.maximum(jnp.max(jnp.abs(w_t)), jnp.finfo(w_t.dtype).tiny)
    row = jnp.stack([jnp.dot(prob, lin_t - lin_star), jnp.dot(prob, lin_t),
                     jnp.dot(prob, dual_obj), off])
    at = lambda pool, new: jax.lax.dynamic_update_slice_in_dim(
        pool, new[:, None], slot, axis=1)
    return (at(columns, x_star), at(G, x_star[:, idx]),
            at(base, jnp.sum(c * x_star, axis=-1)), row)


class FWPH(PHBase):
    def __init__(self, batch, options=None, rho_setter=None, extensions=None,
                 converger=None, dtype=None, mesh=None):
        super().__init__(batch, options, rho_setter, extensions, converger,
                         dtype, mesh)
        if batch.tree.num_stages != 2:
            raise ValueError("FWPH is two-stage only (ref. fwph.py:439-442)")
        opts = self.options
        self.FW_iter_limit = int(opts.get("FW_iter_limit", 3))
        self.FW_conv_thresh = float(opts.get("FW_conv_thresh", 1e-4))
        self.max_columns = int(opts.get("fwph_max_columns", 16))
        self.qp_iters = int(opts.get("fwph_qp_iters", 400))
        self._manifold_tol = max(
            MANIFOLD_TOL, 1e3 * float(jnp.finfo(self.dtype).eps))
        self._local_bound = None
        self._col_ptr = 0
        self._fw_times = _new_fw_times()

    # ---- column pool ----
    def _init_columns(self, x0):
        self.columns, self._G, self._base = _pool_init(
            x0, self.c, self.nonant_idx, n_slots=self.max_columns)
        self._col_ptr = 0

    def _next_slot(self):
        """The slot the next column lands in: round-robin (the rolling
        pad for Pyomo's growing column set, ref. fwph.py:305-352
        _add_QP_column), booked."""
        slot = self._col_ptr % self.max_columns
        self._col_ptr += 1
        times = self._fw_times
        times["columns_written"] += 1
        times["pool_wraps"] += int(slot == 0 and self._col_ptr > 1)
        return jnp.asarray(slot, jnp.int32)

    # ---- the SDM inner loop (ref. fwph.py:210-303) ----
    def SDM(self, first_pass_bound=True):
        """One simplicial-decomposition pass. Ordering matters for bound
        validity: w is set from the *incumbent* QP iterate x_t — whose
        scenario mean IS x̄ at the first pass (x̄ was computed from it at
        the end of the previous outer iteration) — so E[w] = 0 there and
        the first linearized solve yields a certified Lagrangian bound
        (the reference computes its dual bound at the same point,
        ref. fwph.py:526 _compute_dual_bound)."""
        times = self._fw_times
        if self._a is None:
            self._a = jnp.full((self.batch.S, self.max_columns),
                               1.0 / self.max_columns, self.dtype)
        gamma = jnp.inf
        for k in range(self.FW_iter_limit):
            self._sdm_k = k
            with obs.span("fwph.sdm.pass", cat="fwph"):
                gamma, stop = self._sdm_pass(k == 0 and first_pass_bound)
            if stop:
                times["passes_ended_by_gamma"] += 1
                break
        return self._xn_t, gamma

    def _sdm_pass(self, publish):
        times = self._fw_times
        times["passes"] += 1
        self._w_t = w_t = self.W + self.rho * (self._xn_t - self.xbar)
        # linearized subproblem: min (c + scatter(w_t))'x over the
        # original feasible set — shares PH's prox-off KKT factor. The
        # chunked loop reads W once, as its staging program's operand,
        # so the swap costs a rebinding and nothing on the device
        saved_W = self.W
        self.W = w_t
        try:
            with obs.span("fwph.linearized", cat="fwph") as sp:
                self.solve_loop(w_on=True, prox_on=False, update=False)
        finally:
            self.W = saved_W
        times["linearized_seconds"] += sp.seconds
        with obs.span("fwph.column", cat="fwph") as sp:
            # Γ: linearization gap of the QP iterate vs the new vertex
            self.columns, self._G, self._base, row = _column_step(
                self.columns, self._G, self._base, self._a, self._xn_t,
                w_t, self.x, self._last_dual_obj, self.c, self.c0,
                self.prob, self.xbar_weights, tuple(self.memberships),
                self.nonant_idx, self._next_slot(),
                slot_slices=self.slot_bounds)
            # phase honesty: the slot write's device seconds land in
            # its own span, not in the read below
            jax.block_until_ready(row)
        times["column_seconds"] += sp.seconds
        with obs.span("fwph.bound_gamma", cat="fwph") as sp:
            # THE pass's one read: Γ, its test's scale, the bound and
            # the manifold's error are four scalars of the same pass
            gamma, e_lin, bound, off = np.asarray(row).tolist()
        self._sdm_row = {"gamma": gamma, "E_lin_t": e_lin, "bound": bound,
                         "manifold_err": off}
        times["bound_gamma_seconds"] += sp.seconds
        times["host_reads"] += 1
        if publish:
            self._pass_bound = bound    # this first pass's own, kept or not
            if off <= self._manifold_tol:
                prev = (self._local_bound if self._local_bound is not None
                        else -np.inf)
                self._local_bound = max(prev, bound)
                times["bounds_published"] += 1
            else:
                times["bounds_dropped"] += 1
                obs.counter_add("fwph.bounds_dropped")
        with obs.span("fwph.simplex_qp", cat="fwph") as sp:
            self._a, self._xn_t = simplex_qp_solve(
                self._G, self._base, self.W, self.rho, self.xbar, self._a,
                iters=self.qp_iters)
            # phase honesty again: the next pass's w_t needs xn_t, so
            # the wait is one the host would have at its next step
            jax.block_until_ready(self._xn_t)
        times["qp_seconds"] += sp.seconds
        times["qp_iters"] += self.qp_iters
        return gamma, abs(gamma) < self.FW_conv_thresh * max(1.0,
                                                             abs(e_lin))

    # ---- driver (ref. fwph.py:142-208 fwph_main) ----
    def iter0(self):
        """Plain solves seed the pool and x̄ (ref. fwph.py:156-168).
        Warm-start semantics match PH.ph_main: a loaded W solves with W
        on, a loaded xbar survives iter 0 unoverwritten."""
        warm = getattr(self, "_warm_started", False)
        warm_xbar = getattr(self, "_warm_started_xbar", False)
        self.solve_loop(w_on=warm, prox_on=False, update=not warm_xbar)
        self._init_columns(self.x)
        self._xn_t = self.nonants_of(self.x)   # E[xn_t] = x̄ holds at start
        self._a = self._w_t = None     # the first pass makes both
        if not warm:
            self.Update_W()   # W=0 before, so W = rho(x - xbar)
        self.trivial_bound = self.Ebound()
        self._local_bound = self.trivial_bound
        self._iter = 0

    def iterate(self, it):
        """One outer iteration, the engine's own step (``fwph_main``
        calls it for it = 1, 2, ... after ``iter0``; a driver or a test
        that steps the engine itself does the same): the SDM passes,
        then the PH updates from the QP solutions (the reference's
        _swap_nonant_vars pointer trick, ref. fwph.py:989). Returns
        False when a termination test ended the run."""
        self._iter = it
        times = self._fw_times
        with obs.span("fwph.iteration", cat="fwph"):
            xn_t, gamma = self.SDM()
            with obs.span("fwph.update", cat="fwph") as sp:
                wmask = None if self._w_scale is None else self._w_scale > 0
                self.xbar, self.xsqbar, self.W, conv = _ph_combine(
                    xn_t, self.prob, self.xbar_weights,
                    tuple(self.memberships), self.W, self.rho, wmask,
                    slot_slices=self.slot_bounds)
                self.conv = float(conv)     # the iteration's one read
            times["update_seconds"] += sp.seconds
        times["host_reads"] += 1
        times["iterations"] += 1
        if self.spcomm is not None:
            self.spcomm.sync()
            if self.spcomm.is_converged():
                return False
        if self.conv < self.convthresh:
            global_toc(f"FWPH iter {it}: conv={self.conv:.3e} < thresh",
                       self.verbose)
            return False
        if self.verbose and it % 10 == 0:
            global_toc(f"FWPH iter {it}: conv={self.conv:.4e} "
                       f"bound={self._local_bound:.4f} Γ={gamma:.3e}")
        return True

    def fwph_main(self, finalize=True):
        self.iter0()
        for it in range(1, self.max_iterations + 1):
            if not self.iterate(it):
                break
        if finalize:
            return self.conv, self._local_bound, self.trivial_bound
        return self.conv

    # ---- the FWPH seconds beside the solve loop's (no session) ----
    def reset_phase_timing(self):
        super().reset_phase_timing()
        self._fw_times = _new_fw_times()

    def phase_timing(self, key=False):
        """``PHBase.phase_timing`` of the linearized (prox-off) solves
        plus ``"fwph"``: the engine's own totals (``_new_fw_times``)."""
        out = super().phase_timing(key)
        if out is not None:
            out["fwph"] = dict(self._fw_times)
        return out

    def _hub_nonants(self):
        xn_t = getattr(self, "_xn_t", None)
        if xn_t is None:
            return super()._hub_nonants()
        return xn_t   # simplex_qp_solve already returns a @ columns[nonants]
