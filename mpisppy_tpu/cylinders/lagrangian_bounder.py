"""Lagrangian outer-bound spokes.

``LagrangianOuterBound`` (ref. mpisppy/cylinders/lagrangian_bounder.py:5-87):
takes the hub's W, solves all subproblems with W on / prox off, and
publishes the expected *certified dual* bound (our Ebound is built from the
ADMM dual vectors, so an inexactly solved subproblem cannot overstate it).

``LagrangerOuterBound`` (ref. mpisppy/cylinders/lagranger_bounder.py:9-95):
takes the hub's *nonants* instead and computes its own x̄ and W locally
(optionally with a rescaled rho) before bounding.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.runtime import wheel_host_section
from .spoke import OuterBoundWSpoke, OuterBoundNonantSpoke

_UNSET = object()


class _AsyncRefresh:
    """One in-flight background bound refresh at a time, newest-wins
    queueing: ``launch(arg)`` starts ``fn(arg)`` on a daemon thread when
    idle (or parks ``arg`` as the pending argument when busy — only the
    newest pending argument survives), ``poll()`` harvests a finished
    result (or None) and auto-relaunches on the pending argument.

    This is what DEMOTES the exact host-LP oracle from the bound loop's
    bottleneck to an asynchronous tightener: the spoke keeps publishing
    cheap device-certified bounds every sync while a ~minutes-long exact
    refresh runs here, and harvests the exact value whenever it lands.
    ``fn`` must be kill-aware (the oracle pool's kill_check) — the wheel
    terminating mid-refresh abandons the thread harmlessly (daemon)."""

    def __init__(self, fn):
        self._fn = fn
        self._lock = threading.Lock()
        self._thread = None
        self._result = _UNSET
        self._pending = _UNSET

    @property
    def busy(self):
        t = self._thread
        return t is not None and t.is_alive()

    def _start(self, arg):
        def run():
            out = self._fn(arg)
            with self._lock:
                self._result = out

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def launch(self, arg):
        with self._lock:
            if self.busy:
                self._pending = arg
            else:
                self._start(arg)

    def poll(self):
        """Finished result (may be None for a failed refresh) or None."""
        with self._lock:
            out = self._result
            self._result = _UNSET
            if not self.busy and self._pending is not _UNSET:
                arg, self._pending = self._pending, _UNSET
                self._start(arg)
        return None if out is _UNSET else out


class LagrangianOuterBound(OuterBoundWSpoke):
    """Four bound engines, composable by options:

    - default: the batched on-device solve + certified dual bound
      (valid at ANY solve accuracy, tight once duals converge);
    - ``lagrangian_device_duals``: the DEVICE-DUAL mode — the primary
      bound source becomes the engine's own dual iterates from the
      (chunked, packed-df32) prox-off solve, pulled f32 (quantized
      duals are still exact duals), repaired onto the dual-feasible
      cone and certified on host in f64 with directed-rounding margins
      (utils/certify.DualBoundCertifier; the repair is the host twin
      of ops/qp_solver.qp_repair_duals), so every published value is
      provably <= the true optimum WITHOUT an LP oracle call. Bounds
      publish early-and-often: one at prep (W=0, seconds after the
      first solve pass) and one per hub sync. When
      ``lagrangian_exact_oracle`` is also on (and the MIP oracle off —
      a MIP bound dominates the LP bound at equal W), the exact
      host-LP pass is DEMOTED to an asynchronous tightener/cross-check
      (_AsyncRefresh): it runs on the newest projected W in the
      background and its exact value is harvested whenever it lands —
      minutes-long host passes stop gating the wheel's first certified
      bound.
    - ``lagrangian_exact_oracle`` (without device duals): per-scenario
      host HiGHS LPs (utils/host_oracle), blocking — exact L(W) of the
      LP relaxation, the analog of the reference's spoke renting a CPU
      simplex per scenario (ref. lagrangian_bounder.py:5-87). Floored
      at the instance's LP integrality gap.
    - ``lagrangian_mip_oracle``: per-scenario host HiGHS **MILPs** with
      W on — the true Lagrangian dual function, matching the
      reference's MIP subproblem solves (ref.
      lagrangian_bounder.py:54-56 → phbase.py:947-949) that carry its
      UC gaps to 0.026-0.073% where LP bounds stall near ~1%. Each
      scenario value is the B&B dual bound (valid at any time_limit /
      mip_rel_gap stop). Refreshes run at ``lagrangian_mip_cadence``
      seconds (default 0: back-to-back) on the newest projected W,
      through a subprocess pool that overlaps the hub's device work and
      aborts on the hub's kill signal mid-refresh.

    Linear objectives only for the oracles and the host certification;
    quadratic models and variable-probability runs fall back to the
    certified device bound. The spoke is asynchronous, so host latency
    never blocks the hub.
    """
    converger_spoke_char = "L"

    def __init__(self, spbase_object, options=None, trace_prefix=None):
        super().__init__(spbase_object, options, trace_prefix)
        # the oracle-eligibility test re-materialized P_diag to host on
        # every sync when it was a property (ADVICE r2) — it is static,
        # so decide once
        self._linear = getattr(self.opt, "vprob", None) is None and \
            float(np.abs(np.asarray(self.opt.batch.P_diag)).max()) == 0.0
        self._exact = bool(self.options.get("lagrangian_exact_oracle",
                                            False)) and self._linear
        self._mip = bool(self.options.get("lagrangian_mip_oracle",
                                          False)) and self._linear
        # device-dual mode (see class docstring): engine duals as the
        # primary bound source, host-certified; exact oracle demoted to
        # an asynchronous tightener
        self._device_duals = bool(self.options.get(
            "lagrangian_device_duals", False))
        self._certify = bool(self.options.get("lagrangian_certify_host",
                                              True)) and self._linear
        self._certifier = None          # lazy DualBoundCertifier | False
        self._tightener = None          # lazy _AsyncRefresh
        self._mip_tl = float(self.options.get("lagrangian_mip_time_limit",
                                              10.0))
        self._mip_gap = float(self.options.get("lagrangian_mip_gap", 1e-4))
        self._mip_cadence = float(self.options.get("lagrangian_mip_cadence",
                                                   0.0))
        # one degenerate scenario LP must not stall the refresh forever
        # (ADVICE r2): timeouts surface as ok=False → device fallback
        self._lp_tl = self.options.get("lagrangian_lp_time_limit", 60.0)
        # LP-EF dual warm start (utils/host_oracle.solve_lp_ef): one
        # host LP solve puts the spoke AT the LP-relaxation Lagrangian
        # maximum before the hub's first W arrives — W convergence
        # stops being the bound bottleneck. Inline (not abortable), so
        # very large batches can disable it.
        self._warm = bool(self.options.get("lagrangian_lp_ef_warmstart",
                                           True)) \
            and (self._exact or self._mip) and not self._device_duals
        self._pool = None
        self._pool_lock = threading.Lock()
        self._oracle_use_lock = threading.Lock()
        self._projector = None
        self._last_mip_at = -float("inf")
        self._last_mip_ok = True
        # warm resume (mpisppy_tpu.ckpt): the checkpointed dual block
        # parked by install_spoke_state; lagrangian_prep bounds at it
        # instead of the W=0 cold prep
        self._resume_W = None
        # what the last DEVICE bound was made of (the benchmark's
        # reference check reads it, tests too): the projected W, the
        # per-scenario certified values whose expectation was
        # published, the hub write-id of the payload
        self.last_bound = None
        self._certified_rows = None

    def reset_wheel_totals(self):
        super().reset_wheel_totals()
        self._certify_tot = {"calls": 0, "seconds": 0.0}

    def wheel_totals(self):
        return dict(super().wheel_totals(), certify=dict(self._certify_tot))

    def _note_bound(self, W, value, rows):
        self.last_bound = {"W": W, "value": float(value), "rows": rows,
                           "source": self._last_hub_id}

    # ---- durable warm state (mpisppy_tpu.ckpt) ----
    def spoke_state(self):
        """+ the spoke's Lagrangian dual block (its engine's W, REAL
        scenarios only — the wxbar portability contract, in case the
        spoke engine is ever mesh-padded): a resumed/respawned
        incarnation prep-bounds at the checkpointed duals instead of
        the trivial W=0 point, so its first COMPUTED bound starts
        where the dead generation's left off (the re-published best
        rides resume_publish either way)."""
        state = super().spoke_state()
        S = getattr(self.opt, "_S_orig", self.opt.batch.S)
        state["W"] = np.asarray(self.opt.W, np.float64)[:S]
        return state

    def install_spoke_state(self, state):
        super().install_spoke_state(state)
        W = state.get("W")
        if W is None:
            return
        W = np.asarray(W, np.float64)
        S_real = getattr(self.opt, "_S_orig", self.opt.batch.S)
        if W.shape != (S_real, self.opt.batch.K):
            return          # foreign shape: keep the cold W=0 prep
        if self.opt.batch.S != S_real:
            # mesh pads carry zero objective weight; zero duals there
            # keep the padded block on the dual-feasible manifold
            W = np.concatenate(
                [W, np.zeros((self.opt.batch.S - S_real, W.shape[1]))])
        self._resume_W = W

    def _oracle(self):
        # construction is locked: the async tightener thread and the
        # spoke's own MIP refresh may race on first use
        with self._pool_lock:
            if self._pool is None:
                from ..utils.host_oracle import OraclePool
                self._pool = OraclePool(
                    self.opt.batch,
                    n_workers=self.options.get("lagrangian_oracle_workers"))
            return self._pool

    def _oracle_bound(self, W=None, **kw):
        """Oracle call with the spoke's failure contract: ANY oracle
        problem (worker subprocess death included) degrades to None so
        the caller falls back to the device bound — a bound spoke must
        never crash the wheel over a host solver hiccup.

        Pool USE is serialized under _oracle_use_lock: the async
        exact-LP tightener thread and the spoke thread's own MIP
        refresh share one worker pool, and OraclePool._run is a
        single-caller protocol (two concurrent callers would interleave
        task/result frames on the same worker pipes and cross-deliver
        values computed at different W). The tightener blocking here is
        harmless — it is the background thread."""
        try:
            with self._oracle_use_lock:
                return self._oracle().lagrangian_bound(
                    self.opt.batch.prob, W, kill_check=self.killed, **kw)
        except Exception:
            return None

    def _project_W(self, W_flat):
        # Project the received W onto the dual-feasible manifold
        # sum_s p_s W_s = 0 per (node, slot) by removing its p-weighted
        # node mean. PH-generated W satisfies this in exact arithmetic,
        # but the hub may run a lower precision (an f32 hot loop leaves
        # O(1e-4·scale) mass), and the Lagrangian bound is only a valid
        # outer bound on that manifold. The projection runs in HOST
        # float64 regardless of engine dtype (host_oracle's shared,
        # membership-precomputing projector): the bound certificate's
        # precision is set by the projector, and an f32 projection
        # would leave an O(eps_f32·|W|) off-manifold residual that the
        # f64/MIP oracle bounds (1e-4-level tightness) cannot absorb.
        if getattr(self.opt, "vprob", None) is not None:
            # variable probabilities: the manifold is vprob-weighted;
            # oracles are disabled here, so the engine projection (same
            # precision as the device bound it feeds) is the right one
            W = jnp.asarray(W_flat, self.opt.dtype)
            return W - self.opt.compute_xbar(W)
        if self._projector is None:
            from ..utils.host_oracle import make_w_projector
            self._projector = make_w_projector(self.opt.batch)
        return self._projector(W_flat)

    # -- device-dual mode (the certified-without-an-oracle path) --
    def _host_certified(self, W):
        """Host f64 safe-rounding certification of the engine's current
        row duals (utils/certify). Returns the certified bound, or None
        when certification is unavailable/uncertifiable — callers fall
        back to the device Ebound value."""
        if not self._certify or self._certifier is False:
            return None
        if self._certifier is None:
            try:
                from ..utils.certify import DualBoundCertifier
                self._certifier = DualBoundCertifier.from_batch(
                    self.opt.batch)
            except Exception as e:
                # construction failure (ineligible layout, host OOM on
                # the sparse build) is permanent for this batch: latch
                # off, but SAY SO — the published bounds silently
                # degrading from host-certified to device-certified
                # must be visible in the trace
                from .. import global_toc
                global_toc(f"{type(self).__name__}: host certification "
                           f"unavailable ({e!r}); publishing the device "
                           "dual certificate instead")
                self._certifier = False
                return None
        try:
            # f32 transfer: quantized duals are still exact duals —
            # validity is free, the tightness cost is ~1e-7 relative,
            # and the (S, m) device→host pull halves (tens of MB at
            # uc1024 scale). The cone repair happens
            # host-side inside the certifier (its _sanitize is the
            # same projection ops/qp_solver.qp_repair_duals runs on
            # device — one repair suffices).
            yA = np.asarray(jnp.asarray(self.opt.yA, jnp.float32),
                            np.float64)
            # host float64 work: the wheel's other cylinders do not
            # wait for it (utils/runtime.wheel_host_section)
            with wheel_host_section(self.opt), \
                    obs.span("lagrangian.certify", cat="wheel") as sp:
                b, rows = self._certifier.bound(
                    yA, None if W is None else np.asarray(W, np.float64))
            self._certify_tot["calls"] += 1
            self._certify_tot["seconds"] += sp.seconds
            if np.isfinite(b):
                self._certified_rows = rows
                return b
            return None
        except Exception as e:
            # evaluation failure may be TRANSIENT (host memory spike at
            # uc1024 scale): log, fall back to the device certificate
            # for THIS refresh, and retry on the next one — do not
            # latch certification off over one hiccup
            if not getattr(self, "_warned_cert_fail", False):
                self._warned_cert_fail = True
                from .. import global_toc
                global_toc(f"{type(self).__name__}: host certification "
                           f"failed this refresh ({e!r}); falling back "
                           "to the device dual certificate (will keep "
                           "retrying)")
            return None

    def _device_bound(self, W):
        """Certified outer bound from the engine's OWN duals at W (None
        = W off): one batched prox-off solve, dual extraction from the
        chunked/packed solve path, host f64 certification when
        eligible, device dual-objective certificate otherwise."""
        opt = self.opt
        if W is None:
            opt.solve_loop(w_on=False, prox_on=False, update=False)
        else:
            opt.W = jnp.asarray(W, opt.dtype)
            opt.solve_loop(w_on=True, prox_on=False, update=False)
        dev = opt.Ebound()
        cert = self._host_certified(W)
        if cert is None:
            self._note_bound(W, dev, opt._last_dual_obj)
            return dev
        self._note_bound(W, cert, self._certified_rows)
        return cert

    def _ensure_tightener(self):
        if self._tightener is None:
            def refresh(W):
                return self._oracle_bound(W, time_limit=self._lp_tl)

            self._tightener = _AsyncRefresh(refresh)
        return self._tightener

    def lagrangian_prep(self):
        """Bound before any W arrives (ref. lagrangian_bounder.py:20-52
        computes the trivial W=0 bound here). With the LP-EF warm start
        the prep bound is the LP-relaxation OPTIMUM (its dual W* is the
        LP-Lagrangian maximizer), and the MIP oracle refreshed at W*
        immediately lands near the full Lagrangian dual — the W=0
        trivial bound is strictly dominated and skipped.

        In device-dual mode the prep bound comes from the engine's own
        first prox-off pass instead (seconds, not the minutes a
        reference-scale exact-LP pass costs on a 1-core host), and the
        exact oracle — when configured — starts as an asynchronous
        tightener at W=0 immediately, so its exact value lands during
        the first hub iterations rather than gating them.

        A RESUMED incarnation (checkpointed dual block installed by
        install_spoke_state) skips the cold W=0 prep entirely and
        bounds at its checkpointed duals — generation N picks up the
        Lagrangian ascent where generation N-1 died."""
        W = self._resume_W
        if W is not None:
            self._resume_W = None
            W = self._project_W(np.asarray(W))
            if self._device_duals:
                self.update_bound(self._device_bound(W))
                if self._exact and not self._mip:
                    self._ensure_tightener().launch(np.asarray(W))
            else:
                b = self._fast_bound(W)
                if b is not None:
                    self.update_bound(b)
            return
        if self._device_duals:
            self.update_bound(self._device_bound(None))
            if self._exact and not self._mip:
                # the exact-LP tightener only exists when the MIP
                # oracle is off: at equal W the MIP bound dominates the
                # LP bound, and one shared worker pool cannot serve a
                # minutes-long background LP pass AND the cadence-fired
                # MIP refresh without starving one of them
                self._ensure_tightener().launch(None)
            return
        if self._warm:
            try:
                from ..utils.host_oracle import solve_lp_ef
                lp_obj, W_star = solve_lp_ef(self.opt.batch)
            except Exception:
                lp_obj, W_star = None, None
            if W_star is not None:
                self.update_bound(lp_obj)
                if self._mip:
                    b = self._mip_refresh(W_star)
                    if b is not None:
                        self.update_bound(b)
                return
            # LP-EF failure: fall through to the W=0 prep bound
        if self._exact or self._mip:
            b = self._oracle_bound(time_limit=self._lp_tl)
            if b is not None:
                self.update_bound(b)
                return
            # oracle failure: fall through to the always-valid device bound
        self.opt.solve_loop(w_on=False, prox_on=False, update=False)
        self.update_bound(self.opt.Ebound())

    def _fast_bound(self, W):
        """LP-relaxation bound at W: exact host LP oracle when enabled,
        else the certified device bound."""
        if self._exact:
            with wheel_host_section(self.opt):      # host LPs
                b = self._oracle_bound(np.asarray(W),
                                       time_limit=self._lp_tl)
            if b is not None:
                return b
            if self.killed():
                return None
            # oracle failure: fall through to the device bound
        self.opt.W = jnp.asarray(W, self.opt.dtype)
        self.opt.solve_loop(w_on=True, prox_on=False, update=False)
        b = self.opt.Ebound()
        self._note_bound(W, b, self.opt._last_dual_obj)
        return b

    def _mip_refresh(self, W):
        """MIP-tight L(W): expensive (B&B per scenario), so it runs on
        the newest W at the configured cadence and aborts on kill."""
        self._last_mip_at = time.monotonic()
        # blocking for the SPOKE (a branch-and-bound per scenario), not
        # for the wheel: its place in the arbiter's cycle is given up
        with wheel_host_section(self.opt):
            b = self._oracle_bound(np.asarray(W), milp=True,
                                   time_limit=self._mip_tl,
                                   mip_gap=self._mip_gap)
        self._last_mip_ok = b is not None
        return b

    def main(self):
        self.lagrangian_prep()
        while not self.got_kill_signal():
            if self._tightener is not None:
                # harvest a finished async exact-LP refresh (device-dual
                # mode); a failed refresh returns None and publishes
                # nothing — the device bounds keep flowing regardless
                tightened = self._tightener.poll()
                if tightened is not None:
                    self.update_bound(tightened)
            fresh, values = self.spoke_from_hub()
            if not fresh or values is None:
                continue
            W, _ = self.unpack_hub(values)
            W = self._project_W(W)
            if self._device_duals:
                # primary: the engine's own certified duals at the
                # newest W — published every sync, seconds each
                self.update_bound(self._device_bound(W))
                if self._exact and not self._mip:
                    # newest-wins: the async exact pass always runs on
                    # the freshest projected W (LP tightener only when
                    # the MIP oracle is off — see lagrangian_prep)
                    self._ensure_tightener().launch(np.asarray(W))
                if self._mip and (time.monotonic() - self._last_mip_at
                                  >= self._mip_cadence):
                    # cadence-fired MIP refresh, blocking like the
                    # legacy path (users enabling the MIP oracle accept
                    # its wall); device bounds keep flowing between
                    # refreshes
                    bound = self._mip_refresh(W)
                    if bound is not None:
                        self.update_bound(bound)
                continue
            if not (self._mip and self._mip_cadence == 0.0
                    and self._last_mip_ok):
                # with back-to-back SUCCEEDING MIP refreshes the LP
                # crawl adds nothing (every published bound is
                # superseded immediately); but if the last refresh
                # failed, the cheap bound must keep flowing or the
                # published bound freezes at its pre-failure value
                bound = self._fast_bound(W)
                if bound is not None:
                    self.update_bound(bound)
            if self._mip and (time.monotonic() - self._last_mip_at
                              >= self._mip_cadence):
                bound = self._mip_refresh(W)
                if bound is not None:   # None: kill/solve failure
                    self.update_bound(bound)

    def finalize(self):
        # closing the pool EOFs any in-flight async tightener's worker
        # reads; its daemon thread then exits through the oracle's
        # failure contract (None result, never raised into the wheel)
        if self._pool is not None:
            self._pool.close()
        return super().finalize()


class LagrangerOuterBound(OuterBoundNonantSpoke):
    converger_spoke_char = "A"

    def __init__(self, spbase_object, options=None):
        super().__init__(spbase_object, options)
        # per-iteration rho rescale factors {iter: factor}
        # (ref. lagranger_bounder.py:20-27 json rescale option)
        self.rho_rescale = dict(self.options.get("lagranger_rho_rescale", {}))
        self._niter = 0

    def _update_weights_and_solve(self, X):
        opt = self.opt
        factor = self.rho_rescale.get(self._niter)
        if factor is not None:
            opt.rho = opt.rho * float(factor)
            opt.invalidate_factors()
        xn = jnp.asarray(X, opt.dtype)
        opt.xbar = opt.compute_xbar(xn)
        opt.W = opt.W + opt.rho * (xn - opt.xbar)
        opt.solve_loop(w_on=True, prox_on=False, update=False)
        return opt.Ebound()

    def main(self):
        while not self.got_kill_signal():
            fresh, values = self.spoke_from_hub()
            if not fresh or values is None:
                continue
            _, X = self.unpack_hub(values)
            self.update_bound(self._update_weights_and_solve(X))
            self._niter += 1
