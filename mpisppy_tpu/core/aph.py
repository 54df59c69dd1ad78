"""APH: Asynchronous Projective Hedging (Algorithm 2 of the APH paper).

The reference (ref. mpisppy/opt/aph.py:54-921) runs APH as a two-thread
asynchronous runtime: a listener thread doing periodic Allreduces of
(x̄, x̄², ȳ) + (τ, φ, norms) concatenations, a side-gig computing the
projective quantities when enough ranks have fresh data, and a worker doing
phi-based partial dispatch of subproblem solves.

The math per iteration (notation as in the reference):
  y_s   = W_s + ρ(x_s − z_s)             (dual estimate, dispatched scens
                                          only; y ≡ 0 at iter 1)
  x̄,x̄²,ȳ = prob-weighted per-node means ("FirstReduce", aph.py:393-407)
  u_s   = x_s − x̄;  v = ȳ               (side gig, aph.py:269-291)
  τ     = Σ_s p_s (‖u_s‖² + ‖ȳ‖²/γ)     (aph.py:313-316)
  φ     = Σ_s p_s ⟨z_s − x_s, W_s − y_s⟩ (compute_phis_summand, aph.py:190-201)
  θ     = ν φ/τ  if τ>0 and φ>0 else 0   (Update_theta_zw, aph.py:451-462)
  W_s  += θ u_s;   z_s += θ ȳ/γ          (z := x̄ at iter 1) (aph.py:474-486)
  conv  = ‖u‖_p/‖W‖_p + ‖v‖_p/‖z‖_p      (Compute_Convergence, aph.py:497-523)
  dispatch: the ⌈frac·S⌉ most-negative post-step φ_s, tie-broken by least
  recently dispatched (APH_solve_loop, aph.py:552-669); subproblem objective
  is f_s(x) + W·x + (ρ/2)‖x − z‖² — prox against z, not x̄ (aph.py:866-883).

TPU redesign:
- The listener/side-gig machinery exists because MPI reductions are
  expensive and ranks drift; on a TPU mesh the reductions are the same
  membership matmuls as PH (psum under sharding) inside one fused jitted
  update, so "enough fresh ranks" (async_frac_needed) is always 100% and
  the async staleness model is carried entirely by **partial dispatch**:
  non-dispatched scenarios keep stale x (and lagged W/z when use_lag), which
  is exactly the reference's worker-view of a straggler rank.
- The reference's OTHER listener purpose — wall-clock overlap of
  reduction communication with solves (ref. listener_util.py:277-327) —
  is carried by the execution model rather than a thread: under
  sharding the collectives live INSIDE the jitted step, where XLA's
  scheduler overlaps them with compute (the classic latency-hiding the
  listener hand-rolled over MPI), and host-side control (dispatch
  selection, window sync) runs while the device executes the
  asynchronously dispatched solve. A Python listener thread would add
  GIL contention to hide latency the compiler already hides; the one
  genuinely host-synchronous point — phi-based dispatch needs last
  iteration's phis on host — is inherent to data-dependent dispatch,
  exactly as the reference blocks on its SecondReduce before
  dispatching (ref. aph.py:552-669).
- Dispatch selection runs ON DEVICE (ops/dispatch.dispatch_select): the
  negative-φ top-k and the least-recently-dispatched fill are one jitted
  rank sort over the (S,) φ vector, and the whole iteration's host
  traffic is ONE stacked D2H gate — [τ, φ, θ, conv, φ-stats] ++ mask —
  booked as ``aph.gate_syncs`` (O(1) per iteration by counter test).
- On the host-chunked hot loop, partial dispatch solves ONLY the
  dispatched scenarios: solve_loop(dispatch=ids) microbatches the
  dispatched id list into full-size chunks (ceil(scnt/chunk) device
  calls instead of ceil(S/chunk)) and scatters results back, so
  dispatch_frac=0.2 is a ~5x solve-FLOP cut, not a same-shape masked
  launch (doc/aph.md). Fused (per-scenario A) and sharded engines keep
  the masked-accept spelling: the batch solves as one SIMD program and
  non-dispatched scenarios' solutions are simply not accepted.
- The subproblem shares PH's cached prox-on KKT factorization: the prox
  center enters only the linear term q = c + scatter(W − ρz).
- Active-set compaction (ops/shrink) composes: it compacts the VARIABLE
  axis while dispatch selects on the SCENARIO axis, so φ scoring stays
  full-width math while the dispatched solves run the compacted system.

Options (reference names accepted): APHnu, APHgamma, dispatch_frac,
aph_use_lag; async_frac_needed / async_sleep_secs are accepted and ignored
(no listener thread exists to tune).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import global_toc, obs
from ..ops.dispatch import GATE_HEAD, dispatch_gate, scalar_gate
from .ph import PHBase
from .spbase import compute_xbar


def aph_theta_step(u, ybar, W, z, xbar, tau, phi, nu, gamma, iter1: bool):
    """The θ-step given GLOBAL (τ, φ): θ = νφ/τ when a separating
    hyperplane was found (τ, φ > 0), W += θu, z += θȳ/γ (z := x̄ at
    iter 1) (ref. aph.py:451-486 Update_theta_zw). The ONE definition
    shared by the fused single-chip update below and the sharded
    multi-process engine (core/aph_shard.py), which feeds it
    Synchronizer-reduced scalars instead of local reductions."""
    theta = jnp.where((tau > 0) & (phi > 0),
                      nu * phi / jnp.maximum(tau, 1e-30), 0.0)
    W_new = W + theta * u
    z_new = xbar if iter1 else z + theta * ybar / gamma
    return W_new, z_new, theta


def aph_conv_metric(pusq, pvsq, pwsq, pzsq):
    """‖u‖_p/‖W‖_p + ‖v‖_p/‖z‖_p from the four reduced square norms
    (ref. aph.py:497-523 Compute_Convergence); inf until W and z carry
    mass. Shared by both engines (see aph_theta_step)."""
    return jnp.where(
        (pwsq > 0) & (pzsq > 0),
        jnp.sqrt(pusq) / jnp.sqrt(jnp.maximum(pwsq, 1e-30))
        + jnp.sqrt(pvsq) / jnp.sqrt(jnp.maximum(pzsq, 1e-30)),
        jnp.inf)


@partial(jax.jit, static_argnames=("iter1",))
def _aph_update(xn, W, y, z, rho, prob, xbar, ybar, nu, gamma, iter1: bool):
    """The fused projective-hedging update: side-gig quantities + θ-step
    + convergence + post-step φ in one XLA program (collectives under
    sharding). xbar/ybar are the FirstReduce results (membership matmuls).
    """
    u = xn - xbar                                     # (S, K)
    pusq = jnp.dot(prob, jnp.sum(u * u, axis=1))
    pvsq = jnp.dot(prob, jnp.sum(ybar * ybar, axis=1))
    tau = pusq + pvsq / gamma
    phi = jnp.dot(prob, jnp.sum((z - xn) * (W - y), axis=1))
    W_new, z_new, theta = aph_theta_step(u, ybar, W, z, xbar, tau, phi,
                                         nu, gamma, iter1)
    pwsq = jnp.dot(prob, jnp.sum(W_new * W_new, axis=1))
    pzsq = jnp.dot(prob, jnp.sum(z_new * z_new, axis=1))
    conv = aph_conv_metric(pusq, pvsq, pwsq, pzsq)
    # post-step per-scenario phis drive dispatch (ref. aph.py:755 phisum)
    phis = prob * jnp.sum((z_new - xn) * (W_new - y), axis=1)
    return W_new, z_new, tau, phi, theta, conv, phis, pusq, pvsq, pwsq, pzsq


@partial(jax.jit, static_argnames=("gate", "xbar_fn", "slot_slices", "iter1",
                                   "full", "scnt", "S_real"))
def _aph_step(x, W, z, y, lag, rho, prob, nidx, weights, tree, mask, stamps,
              it, nu, gamma, *, gate, xbar_fn, slot_slices, iter1: bool,
              full: bool, scnt: int, S_real: int):
    """ONE program for everything an iteration does on the device before
    its solve (42 eager launches at UC width and two uploads before
    this, with an idle device behind each): the nonant gather, Update_y
    on the previously dispatched set (``mask``; y ≡ 0 at iter 1), the
    three FirstReduce means, ``_aph_update`` and the stacked gate row.
    One data flow from (x, W, z, y, last pass's mask, the stamps) to
    (W, z, x̄, x̄², ȳ, y, φ_s, the gate row) and to the twins the NEXT
    call takes back: this pass's mask and its stamps.

    ``lag``: None, or the lagged (W, z) the y-update reads under
    ``aph_use_lag``. ``it``: a traced scalar, so no compile per
    iteration (as ν and γ are, which ``_aph_update`` took so).
    ``gate``: this module's ``scalar_gate`` when ``full`` (every real
    row dispatches), else its ``dispatch_gate``, looked up by the
    CALLER at every call: a static operand, so that a gate put in their
    place (benchmarks/tests does) retraces. ``xbar_fn``: None for the
    dense membership means (``tree`` = the memberships), else the
    mesh's collective (``tree`` = its node indices;
    parallel/mesh.ShardedScenarioOps.xbar_traced)."""
    xn = x[..., nidx]
    if not iter1:
        # Update_y (ref. aph.py:157-186)
        W_y, z_y = (W, z) if lag is None else lag
        y = jnp.where(mask[:, None], W_y + rho * (xn - z_y), y)

    def mean(v):
        if xbar_fn is None:
            return compute_xbar(tree, slot_slices, weights, v)
        return xbar_fn(v, weights, *tree)

    xbar, xsqbar, ybar = mean(xn), mean(xn * xn), mean(y)
    W, z, tau, phi, theta, conv, phis = _aph_update(
        xn, W, y, z, rho, prob, xbar, ybar, nu, gamma, iter1=iter1)[:7]
    if full:
        row = gate(tau, phi, theta, conv, phis, S_real=S_real)
        mask = jnp.arange(phis.shape[0]) < S_real
    else:
        row = gate(tau, phi, theta, conv, phis, stamps, scnt=scnt,
                   S_real=S_real)
        mask = row[GATE_HEAD:] != 0
    stamps = jnp.where(mask, jnp.asarray(it, stamps.dtype), stamps)
    return W, z, xbar, xsqbar, ybar, y, phis, row, mask, stamps


def _new_aph_times():
    """``APH.phase_timing()["aph"]``: the iterations since the last
    reset, the host seconds of their two spans, their gate reads, the
    device programs their ``aph.project`` spans launched (``_aph_step``:
    one an iteration) and how often the step's device twins had to be
    seeded from the host (``APH._device_twins``)."""
    return {"iterations": 0, "project_seconds": 0.0, "gate_seconds": 0.0,
            "gate_syncs": 0, "project_programs": 0, "twin_seeds": 0}


class APH(PHBase):
    """Asynchronous Projective Hedging engine (ref. mpisppy/opt/aph.py:54).

    The reference's ``y`` (dual estimate) is named ``y_aph`` here because
    PHBase.yA/yB already carry the QP duals of the last solve.
    """

    def __init__(self, batch, options=None, **kw):
        super().__init__(batch, options, **kw)
        # active-set compaction (ops/shrink) composes with dispatch:
        # compaction packs the VARIABLE axis while φ/dispatch select on
        # the SCENARIO axis, and φ stays full-width math regardless of
        # the solve representation — so the PR 13 guard is lifted and
        # _shrink_allowed keeps PHBase's default
        o = self.options
        self.nu = float(o.get("APHnu", 1.0))
        self.gamma = float(o.get("APHgamma", 1.0))
        self.dispatch_frac = float(o.get("dispatch_frac", 1.0))
        self.use_lag = bool(o.get("aph_use_lag", False))
        S, K = self.batch.S, self.batch.K
        t = self.dtype
        self.z = jnp.zeros((S, K), t)
        self.y_aph = jnp.zeros((S, K), t)
        self.ybar = jnp.zeros((S, K), t)
        # phis lives on DEVICE between iterations (dispatch selection
        # reads it there); tests and APHShard may assign host arrays —
        # every consumer goes through jnp/np.asarray
        self.phis = np.zeros(S)
        self._last_dispatch = np.zeros(S, np.int64)
        self._dispatched = np.ones(S, bool)   # iter 0 solves everyone
        # (mask, stamps, their device twins): the two host arrays above
        # as the step program last left them (see _device_twins)
        self._twins = None
        self.theta = 0.0
        self.tau = self.phi = 0.0
        self._phi_stats = None   # gate φ-histogram row (analyze/aph)
        self._aph_status = None  # per-iteration record block (rec["aph"])
        self._aph_times = _new_aph_times()

    # ---- dispatch selection (ref. aph.py:592-640 _dispatch_list) ----
    def _dispatch_mask(self, it, frac):
        """HOST REFERENCE implementation of the dispatch selection —
        the semantic contract ops/dispatch.dispatch_select reproduces
        bit-for-bit on device (parity-tested in test_dispatch.py). The
        hot loop reads the mask from the stacked gate; this spelling
        serves APHShard's per-rank local pools and the tests.

        Zero-probability mesh pad rows (core/spbase padding for
        uneven shards) are excluded from both the dispatch budget and
        the candidate pools: their phis are identically zero and the
        least-recently-dispatched fill would otherwise burn real
        dispatch slots re-solving dummy copies."""
        S = self.batch.S
        S_real = self._S_orig
        scnt = max(1, int(np.ceil(S_real * frac)))
        mask = np.zeros(S, bool)
        if scnt >= S_real:
            mask[:S_real] = True
            return mask
        # lint: ok[SYNC001] host reference path (APHShard/tests): the hot loop reads the mask from the packed gate instead
        phis = np.asarray(self.phis)[:S_real]
        neg = np.flatnonzero(phis < 0)
        # stable sorts throughout: index order is the pinned tie-break
        # (the device spelling's two-pass radix depends on it)
        take = neg[np.argsort(phis[neg], kind="stable")][:scnt]
        mask[take] = True
        short = scnt - take.size
        if short > 0:
            # least-recently-dispatched fill, index as the tie-break
            rest = np.flatnonzero(~mask[:S_real])
            oldest = rest[np.argsort(self._last_dispatch[rest],
                                     kind="stable")][:short]
            mask[oldest] = True
        return mask

    def _dispatch_capable(self):
        """True when partial dispatch can SKIP solves (the host-chunked
        loop microbatches an arbitrary id list): shared-structure batch,
        chunked, single device. Sharded and fused (per-scenario A)
        engines keep masked acceptance — every scenario solves in the
        one SIMD program and non-dispatched results are dropped."""
        chunk = int(self.options.get("subproblem_chunk", 0))
        return (self._shard_ops is None and 0 < chunk < self.batch.S
                and getattr(self.qp_data.A, "ndim", 0) == 2)

    # ---- the solve with prox against z (ref. aph.py:866-883) ----
    def _aph_solve(self, mask, didx=None):
        """Batched solve of min f_s + W·x + (ρ/2)‖x−z‖² for the
        dispatched scenarios (the TPU carrier of asynchrony). With
        ``didx`` (host id array, ascending) the host-chunked loop
        solves ONLY those scenarios and scatters their rows back —
        undispatched state never enters a device call. Without it
        (fused / sharded / full dispatch) every scenario solves and
        non-dispatched results are simply not accepted."""
        W_solve = self._W_lag if self.use_lag else self.W
        z_solve = self._z_lag if self.use_lag else self.z
        saved_xbar, saved_W = self.xbar, self.W
        x_old = self.x
        yA_old, yB_old = getattr(self, "yA", None), getattr(self, "yB", None)
        self.xbar, self.W = z_solve, W_solve   # prox center := z
        try:
            self.solve_loop(w_on=True, prox_on=True, update=False,
                            dispatch=didx)
        finally:
            self.xbar, self.W = saved_xbar, saved_W
        if didx is None or self.use_lag:
            # the step program's own mask where this pass dispatches it
            tw = self._twins
            m = (tw[2] if tw is not None and tw[0] is mask
                 else jnp.asarray(mask))[:, None]
        if didx is None:
            # masked acceptance: all S solved, dispatched rows accepted
            obs.counter_add("dispatch.solved_scenarios", self._S_orig)
            self.x = jnp.where(m, self.x, x_old)
            # dual merge only at matching widths: a compaction bucket
            # transition changes the QP dual width mid-wheel (the
            # transition pass dispatches everyone — APH_main), so the
            # fresh duals stand whenever the old width died with it
            if yA_old is not None and yA_old.shape == self.yA.shape \
                    and yB_old.shape == self.yB.shape:
                self.yA = jnp.where(m, self.yA, yA_old)
                self.yB = jnp.where(m, self.yB, yB_old)
        # else: the dispatch-masked chunked loop already scattered only
        # the dispatched rows into x/yA/yB (and booked the counters)
        if self.use_lag:
            # lag: dispatched scenarios pick up current (W, z) for their
            # NEXT solve (ref. aph.py:671-683 _update_foropt)
            self._W_lag = jnp.where(m, self.W, self._W_lag)
            self._z_lag = jnp.where(m, self.z, self._z_lag)
        self._last_dispatch[mask] = self._iter
        self._dispatched = mask

    def _device_twins(self):
        """Last pass's mask and the stamps ON THE DEVICE, for the step
        program: what the previous step returned, as long as the host's
        ``_dispatched`` / ``_last_dispatch`` still hold the values that
        flowed beside them (``_twins`` keeps those). Anything else (a
        fresh engine, ``install_aph_state``, a caller's assignment or
        in-place edit, a pass whose mask the host overrode) seeds them
        anew: two small uploads, booked as ``twin_seeds``."""
        tw = self._twins
        if tw is not None and np.array_equal(tw[0], self._dispatched) \
                and np.array_equal(tw[1], self._last_dispatch):
            return tw[2], tw[3]
        self._aph_times["twin_seeds"] += 1
        return (jnp.asarray(self._dispatched, bool),
                jnp.asarray(self._last_dispatch))

    def iterate(self, it, spcomm=None):
        """One APH iteration, the engine's own step (ref.
        aph.py:704-815 APH_iterk): projective step (span
        ``aph.project``), the stacked gate (``aph.gate``), termination
        tests, dispatch and solve. Returns False when a termination
        test ended the run. ``APH_main`` calls it for it = 1, 2, ...
        after iter-0 and ``Update_W``; a driver that steps the engine
        itself does the same."""
        self._iter = it
        nu, gamma = self.nu, self.gamma
        S, S_real = self.batch.S, self._S_orig
        times = self._aph_times
        # dispatch & solve (frac forced to 1 at iter 1 "to get a decent w
        # for everyone", ref. aph.py:783-786). Selection runs on device
        # and rides the SAME packed gate as the projective scalars: the
        # iteration's entire host traffic is one row.
        frac = 1.0 if it == 1 else self.dispatch_frac
        scnt = max(1, int(np.ceil(S_real * frac)))
        full = scnt >= S_real
        with obs.span("aph.project", cat="aph") as sp:
            if self.use_lag and it == 1:
                self._W_lag, self._z_lag = self.W, self.z
            ops = self._shard_ops
            if ops is None:
                xbar_fn, tree = None, tuple(self.memberships)
            else:
                xbar_fn, tree = ops.xbar_traced(self.xbar_weights.ndim,
                                                self.dtype, calls=3)
            mask_dev, stamps_dev = self._device_twins()
            # Update_y + FirstReduce + projective step + gate, fused;
            # phis stays on device, the gate ships its stats
            (self.W, self.z, self.xbar, self.xsqbar, self.ybar,
             self.y_aph, self.phis, gate, mask_dev, stamps_dev) = _aph_step(
                self.x, self.W, self.z, self.y_aph,
                (self._W_lag, self._z_lag) if self.use_lag else None,
                self.rho, self.prob, self.nonant_idx, self.xbar_weights,
                tree, mask_dev, stamps_dev, it, nu, gamma,
                gate=scalar_gate if full else dispatch_gate,
                xbar_fn=xbar_fn, slot_slices=self.slot_bounds,
                iter1=(it == 1), full=full, scnt=scnt, S_real=S_real)
            times["project_programs"] += 1
        times["project_seconds"] += sp.seconds
        with obs.span("aph.gate", cat="aph") as sp:
            # lint: ok[SYNC001] THE stacked APH gate: one D2H per iteration carries scalars + phi stats + dispatch mask (aph.gate_syncs)
            g = np.asarray(gate)
        times["gate_seconds"] += sp.seconds
        times["gate_syncs"] += 1
        times["iterations"] += 1
        obs.counter_add("aph.gate_syncs")
        (self.tau, self.phi, self.theta, self.conv,
         phi_min, phi_max, phi_neg) = g[:GATE_HEAD].tolist()
        self._phi_stats = {"phi_min": phi_min, "phi_max": phi_max,
                           "phi_neg": int(phi_neg)}
        if full:
            mask = np.zeros(S, bool)
            mask[:S_real] = True
        else:
            mask = g[GATE_HEAD:] != 0

        if self.verbose and (it % 10 == 0 or it == 1):
            global_toc(f"APH iter {it}: conv={self.conv:.6e} "
                       f"tau={self.tau:.3e} phi={self.phi:.3e} "
                       f"theta={self.theta:.3e}")
        if spcomm is not None:
            spcomm.sync()
            if spcomm.is_converged():
                global_toc(f"APH iter {it}: hub termination", self.verbose)
                return False
        if self.converger is not None and self.converger.is_converged():
            global_toc(f"APH iter {it}: converger termination", self.verbose)
            return False
        if self.conv is not None and self.conv < self.convthresh:
            global_toc(f"APH iter {it}: conv={self.conv:.3e} < thresh",
                       self.verbose)
            return False
        self._ext("miditer")
        cur_bucket = self._shrink.bucket \
            if self._shrink is not None else None
        if not full \
                and cur_bucket != getattr(self, "_aph_shrink_bucket",
                                          None):
            # a compaction bucket transition landed in this
            # miditer: the solve width changed and every warm
            # store rebuilds cold (ops/shrink _compact_invalidate)
            # — dispatch everyone this ONE iteration (the same
            # warm-up rule as iter 1) so the duals re-materialize
            # at the new width; partial dispatch resumes next
            # iteration (doc/aph.md §composition)
            full = True
            mask = np.zeros(S, bool)
            mask[:S_real] = True
            mask_dev = None     # the step's twins are not this pass's
        self._aph_shrink_bucket = cur_bucket
        didx = None
        if not full and self._dispatch_capable():
            didx = np.flatnonzero(mask)
        if mask_dev is None:
            self._twins = None
        else:
            # the step's mask and stamps beside the host values they
            # equal once _aph_solve has written this pass's
            stamps = self._last_dispatch.copy()
            stamps[mask] = it
            self._twins = (mask, stamps, mask_dev, stamps_dev)
        self._aph_solve(mask, didx=didx)
        self._aph_status = {
            "frac": frac, "scnt": scnt, "S_real": S_real,
            "dispatched": int(mask.sum()),
            "solve_path": "chunked-skip" if didx is not None
            else ("full" if full else "masked-accept"),
            **(self._phi_stats or {})}
        return True

    # ---- main loop (ref. aph.py:704-815 APH_iterk, :818 APH_main) ----
    def APH_main(self, spcomm=None, finalize=True):
        if spcomm is not None:
            self.spcomm = spcomm
        spcomm = self.spcomm   # cylinder layer may have attached one already
        self._ext("pre_iter0")
        # Iter 0 (ref. phbase Iter0 via aph.py:889): w/prox off. Warm-start
        # semantics match PH.ph_main: a loaded W solves with W on, a loaded
        # xbar survives iter 0 unoverwritten.
        warm = getattr(self, "_warm_started", False)
        warm_xbar = getattr(self, "_warm_started_xbar", False)
        self.solve_loop(w_on=warm, prox_on=False, update=not warm_xbar)
        self.assert_feasible_iter0()
        if not warm:
            self.Update_W()   # W = rho(x - xbar), duals for the first pass
        self.trivial_bound = self.Ebound()
        self.best_bound = self.trivial_bound
        self._iter = 0
        self._ext("post_iter0")
        if self.converger_cls is not None:
            self.converger = self.converger_cls(self)
        global_toc(f"APH iter 0: trivial bound = {self.trivial_bound:.4f}",
                   self.verbose)

        for it in range(1, self.max_iterations + 1):
            rec_on = obs.enabled()
            if rec_on:
                pt0 = self._phase_totals()
                ctr0 = obs.counters_snapshot()
            sp_args = {"iter": it} if rec_on else None
            with obs.span("ph.iteration", cat="ph", args=sp_args) as sp_it:
                go_on = self.iterate(it, spcomm)
            if not go_on:
                break
            if rec_on:
                obs.histogram_observe("ph.iteration_seconds",
                                      sp_it.seconds)
                obs.event("ph.iteration", self.iteration_record(
                    it, sp_it.seconds, pt0, ctr0))
            self._ext("enditer")

        if finalize:
            return self.post_loops()
        return self.conv, None, self.trivial_bound

    def post_loops(self):
        self._ext("post_everything")
        return self.conv, self.Eobjective_value(), self.trivial_bound

    # ---- the APH seconds beside the solve loop's (no session) ----
    def reset_phase_timing(self):
        super().reset_phase_timing()
        self._aph_times = _new_aph_times()

    def phase_timing(self, key=True):
        """``PHBase.phase_timing`` plus ``"aph"``: the engine's
        iterations since the last reset, the host seconds of their
        ``aph.project`` spans (the projective step's launches), of
        their ``aph.gate`` spans (the ONE transfer, which waits for the
        step on the device) and the gate reads (totals)."""
        out = super().phase_timing(key)
        if out is not None:
            out["aph"] = dict(self._aph_times)
        return out

    def _hub_nonants(self):
        return self.nonants_of(self.x)

    # ---- checkpoint state (ckpt/manager hub bundle extras) ----
    # The APH wheel's resume needs more than PH's (W, x̄, x̄², ρ): the
    # projective state (z, y) drives the next θ-step, x feeds the next
    # y-update, and (phis, last-dispatch, dispatched) reproduce the
    # next dispatch selection exactly — without them a resumed wheel
    # would re-dispatch from scratch and the trajectory would fork.

    def aph_state_arrays(self):
        """Host copies of the APH-specific state, real rows only
        (mesh pads are reconstructed on install). Keys carry the
        ``aph_`` prefix so ckpt.bundle treats them as extras."""
        S_real = self._S_orig
        # (allowlisted gate site: checkpoint capture is an explicit
        # D2H at the bundle boundary, never in the iteration loop)
        return {
            "aph_z": np.asarray(self.z)[:S_real],
            "aph_y": np.asarray(self.y_aph)[:S_real],
            "aph_x": np.asarray(self.x)[:S_real],
            "aph_phis": np.asarray(self.phis)[:S_real].astype(np.float64),
            "aph_last_dispatch":
                np.asarray(self._last_dispatch)[:S_real].astype(np.int64),
            "aph_dispatched":
                np.asarray(self._dispatched)[:S_real].astype(np.int64),
        }

    def install_aph_state(self, arrays):
        """Inverse of :meth:`aph_state_arrays`: pad the real rows back
        to the (possibly mesh-padded) S by repeating the last row —
        exactly extensions/wxbar_io.install_state_arrays's convention —
        and restore device/host residency per field."""
        S = self.batch.S
        t = self.dtype

        def _pad(a):
            a = np.asarray(a)
            if a.shape[0] < S:
                reps = np.repeat(a[-1:], S - a.shape[0], axis=0)
                a = np.concatenate([a, reps], axis=0)
            return a

        self.z = jnp.asarray(_pad(arrays["aph_z"]), t)
        self.y_aph = jnp.asarray(_pad(arrays["aph_y"]), t)
        self.x = jnp.asarray(_pad(arrays["aph_x"]), t)
        self.phis = jnp.asarray(_pad(arrays["aph_phis"]), t)
        self._last_dispatch = _pad(
            arrays["aph_last_dispatch"]).astype(np.int64)
        self._dispatched = _pad(arrays["aph_dispatched"]).astype(bool)
