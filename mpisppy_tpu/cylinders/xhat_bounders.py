"""x̂ (incumbent) inner-bound spokes.

On fresh hub nonants these spokes pick candidate first-stage values, fix
them (rounding integer slots), evaluate the expected objective with the
batched solver, and publish improvements:

- ``XhatLooperInnerBound`` tries the first `xhat_scen_limit` scenarios in
  order (ref. mpisppy/cylinders/xhatlooper_bounder.py:16-97, two-stage).
- ``XhatShuffleInnerBound`` walks a seed-42 shuffled scenario order, one
  candidate per loop, resuming across epochs like the reference's
  ScenarioCycler (ref. xhatshufflelooper_bounder.py:22-286).
- ``XhatSpecificInnerBound`` tries a fixed scenario-per-node dict every
  pass (multistage-capable, ref. xhatspecific_bounder.py:18-120).

The batched evaluator makes the reference's one-at-a-time economics
inverted: evaluating a candidate costs one batched solve, so the "looper"
variants chiefly differ in candidate *order*, exactly as upstream.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from .. import obs
from ..utils.runtime import wheel_host_section
from .spoke import InnerBoundNonantSpoke


class _XhatInnerBound(InnerBoundNonantSpoke):
    converger_spoke_char = "X"

    def __init__(self, spbase_object, options=None):
        super().__init__(spbase_object, options)
        self.best_xhat = None
        # ``xhat_min_interval`` (seconds, default 0): minimum spacing
        # between candidate-evaluation passes. In-process spokes share
        # ONE device stream with the hub, so every dive/eval delays a
        # hub iteration — rate-limiting the spoke trades incumbent
        # freshness for hub cadence (VERDICT r2: wheel cadence was
        # 3-8x solo PH with unthrottled dives)
        self._min_interval = float(
            self.options.get("xhat_min_interval", 0.0))
        self._last_try = -float("inf")
        self._oracle_pool = None
        # ``xhat_pin_vars``: names of the nonant vars a candidate PINS;
        # the rest are DERIVED slots left to the evaluation solve (UC:
        # pin the commitments u, derive the startups st — pinning both
        # independently fights the min-up/down coupling rows and no
        # dived candidate is ever feasible). None = pin everything.
        pin_names = self.options.get("xhat_pin_vars")
        self._pin_mask = None
        if pin_names is not None:
            b = self.opt.batch
            idx = np.asarray(b.nonant_idx)
            col_in = np.zeros(b.n, bool)
            for name in pin_names:
                sl = b.template.var_slices[name]
                col_in[sl] = True
            self._pin_mask = col_in[idx]          # (K,) bool
        # exact-evaluator integrality: None = auto (MILP iff unpinned
        # integer columns exist); models whose unpinned slots are
        # integral at the LP optimum by structure set False (UC)
        self._eval_milp = self.options.get("xhat_eval_milp")
        # incumbent source policy (doc/incumbents.md): "device" = the
        # batched on-device pool/dive sources ONLY — every host
        # OraclePool path is off and the pool is never constructed;
        # "oracle" = host-oracle candidates/evaluations only (the
        # legacy exact path); "auto" (default) = device sources with
        # the host oracle as the opt-in fallback/polish wherever the
        # per-spoke xhat_oracle_* / xhat_exact_eval options ask for it
        mode = str(self.options.get("incumbent_mode", "auto"))
        from ..utils.config import INCUMBENT_MODES
        if mode not in INCUMBENT_MODES:
            raise ValueError(f"unknown incumbent_mode {mode!r}; known: "
                             f"{INCUMBENT_MODES}")
        self._incumbent_mode = mode

    # ---- durable warm state (mpisppy_tpu.ckpt) ----
    def spoke_state(self):
        """+ the standing incumbent: a resumed incarnation re-publishes
        its bound (base class) and keeps the nonant block that
        produced it, so exact re-evaluation / oracle polish still has
        the plan in hand."""
        state = super().spoke_state()
        if self.best_xhat is not None:
            state["best_xhat"] = np.asarray(self.best_xhat,
                                            np.float64)
        return state

    def install_spoke_state(self, state):
        super().install_spoke_state(state)
        xh = state.get("best_xhat")
        if xh is not None:
            self.best_xhat = np.asarray(xh)

    def candidates(self, X):
        """Yield (K,) or (S,K) candidate nonant blocks from hub nonants X."""
        raise NotImplementedError

    def needs_prepare(self):
        """Whether the NEXT candidates() turn reads the prepared block
        — consensus turns (XhatShuffleInnerBound) don't, and skipping
        _prepare_candidates there saves its oracle MILP wall."""
        return True

    def try_candidates(self, X):
        for xhat in self.candidates(X):
            if self.killed():
                # a terminating wheel must not wait out the rest of the
                # candidate stream — each evaluation is a full batched
                # solve (VERDICT r2 weak #5: mid-eval spokes missed the
                # kill window and their finalize was dropped)
                return
            # skip candidates already evaluated (the hub often re-pushes
            # near-identical nonants, and alternating candidate sources
            # re-present unchanged blocks; a full batched/host solve
            # buys nothing) — a small ring, not one slot, so A-B-A
            # alternation still dedups
            key = np.asarray(self.opt.round_nonants(xhat)).tobytes()
            seen = getattr(self, "_seen_keys", None)
            if seen is None:
                from collections import deque
                seen = self._seen_keys = deque(maxlen=8)
            if key in seen:
                continue
            seen.append(key)
            exact_on = self.options.get("xhat_exact_eval", False)
            # ``xhat_device_prescreen``: gate candidates through the
            # batched device evaluation before paying the host oracle.
            # At scales where the device engine's fixed-mode states are
            # themselves gigabytes (S=1024 reference UC), exact-eval
            # wheels turn it OFF and go straight to the host.
            if not exact_on \
                    or self.options.get("xhat_device_prescreen", True):
                obj = self.opt.calculate_incumbent(
                    xhat, pin_mask=self._pin_mask)
                if obj is None or (self.bound is not None
                                   and obj >= self.bound):
                    continue
            else:
                obj = None
            # ``xhat_exact_eval``: re-evaluate the improving candidate
            # on the HOST oracle (fixed nonants, exact dispatch). At
            # df32 scale the device evaluator's tolerance-level
            # feasibility can mis-state penalty-dominated objectives by
            # (violation × VOLL) — the published INNER bound must be a
            # true upper bound, so the host value replaces the device
            # estimate (and a host-infeasible candidate publishes
            # nothing).
            if exact_on:
                status, exact = self._exact_eval(xhat)
                if status != "ok":
                    # the oracle cannot run here: publish NOTHING. The
                    # caller configured exact eval precisely because the
                    # device estimate is untrusted at this scale
                    # (tolerance-level feasibility can mis-state
                    # penalty-dominated objectives by violation × VOLL)
                    # — falling back to it would terminate a "certified"
                    # gap on the very value the option distrusts
                    # (ADVICE r4).
                    continue
                if exact is None or (self.bound is not None
                                     and exact >= self.bound):
                    continue               # host-infeasible or no gain
                obj = exact
            if obj is None:
                continue
            self.best_xhat = self.opt.round_nonants(xhat)
            self.update_bound(obj)

    def _exact_eval(self, xhat):
        """("ok", value-or-None) from the host oracle, or
        ("unavailable", None) when the oracle cannot run here."""
        if self._incumbent_mode == "device":
            # the device policy NEVER constructs the host oracle —
            # callers that configured exact eval anyway fall through to
            # "unavailable" (and thus publish nothing), which is the
            # config contradiction doc/incumbents.md documents
            return "unavailable", None
        if self._oracle_pool is False:
            return "unavailable", None
        try:
            if self._oracle_pool is None:
                from ..utils.host_oracle import OraclePool
                self._oracle_pool = OraclePool(
                    self.opt.batch,
                    n_workers=self.options.get("xhat_oracle_workers"))
            xr = self.opt.round_nonants(xhat)
            # the oracle's LPs / MILPs run on the host: the wheel's
            # other cylinders do not wait for them
            with wheel_host_section(self.opt):
                return "ok", self._oracle_pool.incumbent_value(
                    xr, self.opt.batch.prob,
                    milp=self._eval_milp, pin_mask=self._pin_mask,
                    time_limit=float(self.options.get(
                        "xhat_oracle_time_limit", 60.0)),
                    kill_check=self.killed)
        except Exception as e:
            from .. import global_toc
            global_toc(f"{type(self).__name__}: exact incumbent eval "
                       f"unavailable ({e!r}); NOT publishing inner "
                       "bounds (exact eval was configured because the "
                       "device estimate is untrusted at this scale)")
            if self._oracle_pool is None:
                self._oracle_pool = False
            return "unavailable", None

    def _stash_consensus(self, X):
        """``xhat_consensus_candidates``: build one candidate by
        THRESHOLD-rounding the probability-weighted consensus of the
        hub's nonant block — commit every pinned binary the fleet runs
        at >= ``xhat_consensus_threshold`` (default 0.3) in the mean.
        Per-scenario MILP plans are optimal for their own realization
        and their union over-commits; the consensus candidate sits
        between them (classic UC consensus rounding), with the exact
        evaluator as the feasibility/quality gate. Yielded every other
        pass by the shuffle looper. No-op without a pin mask."""
        if not self.options.get("xhat_consensus_candidates", False) \
                or self._pin_mask is None:
            return
        # identical consecutive consensus blocks (hub plateau / re-push)
        # would rebuild a BIT-IDENTICAL candidate just for the dedup
        # ring to drop it downstream — skip the regeneration entirely
        # and let the stale-consensus fall-through (``_consensus_fresh``)
        # route the pass to the scenario cycle (ISSUE 9 satellite;
        # counter shared with the dive spoke's pool-reuse path)
        key = np.asarray(X).tobytes()
        if key == getattr(self, "_consensus_key", None):
            obs.counter_add("incumbent.pool_reused")
            return
        self._consensus_key = key
        tau = float(self.options.get("xhat_consensus_threshold", 0.3))
        prob = np.asarray(self.opt.prob, dtype=np.float64)
        w = prob / max(prob.sum(), 1e-300)
        cons = w @ np.asarray(X, dtype=np.float64)        # (K,)
        cand = cons.copy()
        pm = self._pin_mask
        cand[pm] = np.where(cons[pm] >= tau, 1.0, 0.0)
        self._consensus_cand = cand

    def _prepare_candidates(self, X):
        """On integer-nonant models, replace the hub's fractional nonant
        block with per-scenario integer-feasible schedules — rounding
        fractional commitments breaks slack-free covering rows. Two
        sources, composable:

        - ``xhat_oracle_candidates`` (default off): per-scenario host
          MILP solves through the oracle pool — EXACT scenario-optimal
          first stages (the reference's xhatshuffle candidates are MIP
          subproblem solutions for the same reason,
          ref. xhatshufflelooper_bounder.py:108); scenario count capped
          by ``xhat_scen_limit`` so large batches stay affordable.
          Measured on 10-scenario UC: the dived incumbents sat 0.48%
          off-optimal where oracle candidates contain the optimum's
          plan.
        - ``xhat_dive_candidates`` (default on): the batched on-device
          dive prox-centered on the hub block — no host solver in the
          loop, the source that scales with the batch."""
        if not bool(np.asarray(self.opt.nonant_integer_mask).any()):
            return X
        out = np.array(np.asarray(X), dtype=np.float64, copy=True)
        filled = np.zeros(self.opt.batch.S, bool)
        # incumbent_mode wiring (doc/incumbents.md): "device" demotes
        # the host OraclePool to never-constructed, "oracle" keeps the
        # host sources only — the device dive is the default source and
        # the oracle the opt-in fallback
        if self.options.get("xhat_oracle_candidates", False) \
                and self._incumbent_mode != "device":
            filled = self._oracle_candidates(out)
            if self.killed():
                return out
        if not filled.all() and self._incumbent_mode != "oracle" \
                and self.options.get("xhat_dive_candidates", True):
            # rows the oracle didn't cover (beyond its scenario limit,
            # or a failed solve) get dived schedules — a subclass like
            # the shuffle looper draws candidates from EVERY row, and a
            # raw fractional row would waste its evaluation pass
            cands, feasible = self.opt.dive_nonant_candidates(
                X, dive_slots=self._pin_mask)
            take = ~filled & np.asarray(feasible)
            out[take] = np.asarray(cands)[take]
            filled |= take
        if not filled.all() and self._pin_mask is not None \
                and self.options.get("xhat_union_fallback", False):
            # ROBUSTIFIED fallbacks for covering-style pinned integers
            # (UC commitments): a single scenario's optimal plan is
            # routinely infeasible for other scenarios (under-committed
            # against their realizations — measured: every per-scenario
            # MILP candidate rejected by the exact evaluator at
            # reference scale). Unfilled rows get the elementwise MAX
            # over the filled candidates ("commit if any scenario's
            # optimum commits"); with nothing filled, the pinned upper
            # bounds (maximum commitment — always covering). The exact
            # evaluator remains the feasibility gate either way.
            pm = self._pin_mask
            if filled.any():
                union = out[filled][:, pm].max(axis=0)
            else:
                union = np.asarray(self.opt.batch.ub)[0][
                    np.asarray(self.opt.batch.nonant_idx)][pm]
            rows = np.flatnonzero(~filled)
            out[np.ix_(rows, np.flatnonzero(pm))] = union
        return out

    def _oracle_candidates(self, out):
        """Fill ``out`` rows 0..xhat_scen_limit-1 in place with the
        scenarios' MILP-exact nonant blocks; returns the (S,) filled
        mask (all-False on oracle failure/kill — failure logged once;
        the pool is not rebuilt after a construction error)."""
        filled = np.zeros(self.opt.batch.S, bool)
        if self._oracle_pool is False:      # earlier construction failed
            return filled
        limit = min(int(self.options.get("xhat_scen_limit", 3)),
                    self.opt.batch.S)
        try:
            if self._oracle_pool is None:
                import os

                from ..utils.host_oracle import OraclePool
                self._oracle_pool = OraclePool(
                    self.opt.batch,
                    n_workers=self.options.get(
                        "xhat_oracle_workers",
                        min(limit, os.cpu_count() or 1)))
            with wheel_host_section(self.opt):      # host MILPs
                res = self._oracle_pool.scenario_values(
                    milp=True,
                    time_limit=float(self.options.get(
                        "xhat_oracle_time_limit", 10.0)),
                    mip_gap=float(self.options.get("xhat_oracle_gap",
                                                   1e-4)),
                    scenarios=range(limit), kill_check=self.killed,
                    return_x=True)
        except Exception as e:
            from .. import global_toc
            global_toc(f"{type(self).__name__}: oracle candidates "
                       f"unavailable ({e!r}); falling back to dives")
            if self._oracle_pool is None:
                self._oracle_pool = False   # don't re-pay construction
            return filled
        if res is None:
            return filled
        xs = res[3]
        idx = np.asarray(self.opt.batch.nonant_idx)
        for s in range(len(xs)):
            if xs[s] is not None:
                out[s] = xs[s][1][idx]
                filled[s] = True
        return filled

    def main(self):
        # PRE-HUB first pass (r5): with oracle candidates as the sole
        # source (dives off), every candidate is hub-independent —
        # per-scenario MILP plans + the union fallback use no hub
        # nonants — so the first incumbent can be built and exactly
        # evaluated WHILE the hub compiles/solves iter0 instead of
        # after its first publish. On the reference-scale uc10 wheel
        # the time-to-gap IS the first-incumbent time (the exact-LP
        # outer bound is tight from the prep pass), so this overlap is
        # worth ~a hub iteration + the MILP wall directly off the
        # crossing time.
        if self.options.get("xhat_oracle_candidates", False) \
                and self._incumbent_mode != "device" \
                and not self.options.get("xhat_dive_candidates", True) \
                and self.options.get("xhat_union_fallback", False) \
                and bool(np.asarray(self.opt.nonant_integer_mask).any()):
            # union fallback required: without it, rows beyond the
            # oracle's scenario limit hold the all-zeros placeholder
            # and the shuffle's first pick could burn a full evaluation
            # on a zero plan — the opposite of the overlap this buys
            X0 = np.zeros((self.opt.batch.S, self.opt.batch.K))
            self._last_try = time.monotonic()
            self.try_candidates(self._prepare_candidates(X0))
        while not self.got_kill_signal():
            if time.monotonic() - self._last_try < self._min_interval:
                # let the hub keep the device stream — and leave the
                # window UNREAD, so the freshest payload is still there
                # (not consumed-and-dropped) when the interval elapses
                continue
            fresh, values = self.spoke_from_hub()
            if not fresh or values is None:
                continue
            self._last_try = time.monotonic()
            _, X = self.unpack_hub(values)
            # consensus snapshot from the RAW hub block (prepare
            # replaces rows with oracle/dive plans; the fractional
            # consensus is only visible here)
            self._stash_consensus(X)
            self.try_candidates(self._prepare_candidates(X)
                                if self.needs_prepare() else X)

    def finalize(self):
        """Return (bound, best_xhat) (ref. xhatshufflelooper_bounder.py:198
        re-fixes the global best in finalize)."""
        if self._oracle_pool not in (None, False):
            self._oracle_pool.close()
        return self.bound, self.best_xhat


class DiveInnerBound(_XhatInnerBound):
    """Device-side batched incumbent search (ISSUE 9 tentpole,
    doc/incumbents.md): on every fresh hub nonant block, manufacture a
    POOL of rounding candidates as one jitted op (ops/incumbent
    .build_pool — consensus vote rounding at multiple thresholds, the
    top-k most-fractional flip neighborhoods, seeded random balls, and
    the slam max/min rows) and evaluate the WHOLE pool as batched
    fix-and-dive repair solves through the engine's donated warm-start
    kernel path (PHBase.evaluate_incumbent_pool — one stacked D2H
    verdict per round, zero host solver subprocesses). The best
    feasible improving candidate publishes through the normal
    InnerBoundNonantSpoke wire, lineage stamps included, so the hub's
    bound-flow ledger sees it like any other spoke.

    ``incumbent_mode`` defaults to "device" here (the whole point);
    "auto" re-admits the host oracle as a POLISH pass — an exact
    re-evaluation of the standing best after ``incumbent_oracle_after``
    rounds without improvement. Candidate knobs:
    ``incumbent_pool_thresholds`` (vote taus),
    ``incumbent_pool_flips`` (local-branching ball),
    ``incumbent_pool_random``/``incumbent_random_ball``/
    ``incumbent_seed`` (seeded exploration rows). When the hub
    re-pushes an IDENTICAL nonant block, the deterministic pool would
    reproduce bit for bit — the spoke skips the rebuild
    (``incumbent.pool_reused``) and evaluates a fresh random
    neighborhood of the same static shape instead (or skips the round
    entirely on models with no binary dive slots)."""

    converger_spoke_char = "D"

    def __init__(self, spbase_object, options=None):
        options = dict(options or {})
        options.setdefault("incumbent_mode", "device")
        super().__init__(spbase_object, options)
        if self._incumbent_mode == "oracle":
            # contradictory by construction: this spoke IS the device
            # pool engine, and "oracle" promises host-oracle sources
            # only — every round would generate and publish exactly the
            # device values the mode excludes. Use an oracle-configured
            # xhatshuffle/xhatlooper spoke instead (doc/incumbents.md).
            raise ValueError(
                "DiveInnerBound requires incumbent_mode 'device' or "
                "'auto'; 'oracle' excludes the device pool this spoke "
                "exists to run — use an xhat spoke with "
                "xhat_oracle_candidates/xhat_exact_eval instead")
        o = self.options
        self._thresholds = tuple(o.get("incumbent_pool_thresholds",
                                       (0.3, 0.5, 0.7)))
        self._flips = int(o.get("incumbent_pool_flips", 8))
        self._n_random = int(o.get("incumbent_pool_random", 4))
        self._ball = int(o.get("incumbent_random_ball", 4))
        self._seed = int(o.get("incumbent_seed", 42))
        self._oracle_after = int(o.get("incumbent_oracle_after", 8))
        # publish-time verification gate: TIGHTER than the pool screen
        # (default 1e-4 xhat_feas_tol) so a half-converged verification
        # solve cannot publish an optimistic inner bound (measured on
        # farmer: 1e-4-passing evals understated the optimum by ~1e-4
        # of problem scale). df32 engines sit at their ~1e-3 residual
        # floor and keep the standard gate — at that scale wheels
        # configure xhat_exact_eval anyway (doc/tpu_numerics.md).
        tol = o.get("incumbent_publish_feas_tol")
        if tol is None:
            tol = 5e-3 if getattr(self.opt, "sub_precision",
                                  "native") == "df32" \
                else max(100.0 * float(getattr(self.opt, "sub_eps", 1e-8)),
                         1e-6)
        self._publish_feas_tol = float(tol)
        # the pool SCREEN's tolerance is the user's ``xhat_feas_tol``
        # where one is set. Its DEFAULT (1e-4) is under a df32 engine's
        # ~1e-3 residual floor: a cold df32 fixed-nonant solve ends by
        # its own criteria with a few rows at 2.6e-4 (7 of 256 UC rows,
        # the max-commitment anchor among them: my chip run, PR 39);
        # screened at 1e-4 those candidates are reset cold every round
        # and the pool never admits one. So a df32 engine's default is
        # its floor. The publish gate above still decides what is
        # published.
        self._screen_kw = {}        # the engine's own (its option)
        if "xhat_feas_tol" not in o and getattr(
                self.opt, "sub_precision", "native") == "df32":
            self._screen_kw["feas_tol"] = 1e-3
        self._rounds = 0
        self._dry = 0
        self._last_X_key = None
        # the published incumbent's per-scenario values (the rows
        # whose expectation is the bound), for whoever checks them
        self.best_xhat_rows = None
        # what the last completed round SCREENED, for whoever checks it
        # (the benchmark's reference check, tests): the (P, K) pool,
        # its verdict, the per-row objectives (row p * S + s) where the
        # engine's batched screen ran, the hub write-id of the payload
        # and the ``perf_counter`` stamp of the screen's end
        self.last_screen = None
        # dive slots: BINARY nonant slots inside the pinned set — the
        # slots a candidate decides. Derived integer nonants (UC
        # startups) stay out via xhat_pin_vars exactly like every other
        # x̂ spoke; continuous slots carry the consensus value.
        b = self.opt.batch
        idx = np.asarray(b.nonant_idx)
        self._lb_row = np.asarray(b.lb)[0][idx]
        self._ub_row = np.asarray(b.ub)[0][idx]
        binary = self.opt.nonant_integer_mask \
            & ((self._ub_row - self._lb_row) <= 1.0 + 1e-9)
        self._dive_mask = binary if self._pin_mask is None \
            else (binary & self._pin_mask)

    def spoke_state(self):
        """+ the dive round counter — the RNG fold index: build_pool
        folds the seed with the round, so restoring it keeps a resumed
        incarnation's random exploration rows FRESH relative to every
        pool the dead generation already evaluated (a reset counter
        would replay them)."""
        state = super().spoke_state()
        state["rounds"] = int(self._rounds)
        return state

    def install_spoke_state(self, state):
        super().install_spoke_state(state)
        rounds = state.get("rounds")
        if rounds is not None:
            self._rounds = int(rounds)

    def main(self):
        while not self.got_kill_signal():
            if time.monotonic() - self._last_try < self._min_interval:
                # leave the window UNREAD so the freshest payload is
                # still there when the interval elapses (see
                # _XhatInnerBound.main)
                continue
            fresh, values = self.spoke_from_hub()
            if not fresh or values is None:
                continue
            self._last_try = time.monotonic()
            _, X = self.unpack_hub(values)
            self.try_pool(np.asarray(X, dtype=np.float64))

    def reset_wheel_totals(self):
        super().reset_wheel_totals()
        self._round_tot = {"rounds": 0, "seconds": 0.0, "pool_s": 0.0,
                           "verify_s": 0.0, "verifications": 0,
                           "round_s": collections.deque(maxlen=1024)}

    def wheel_totals(self):
        return dict(super().wheel_totals(),
                    rounds=dict(self._round_tot,
                                round_s=list(self._round_tot["round_s"])))

    def try_pool(self, X):
        """One round: span ``incumbent.round`` > ``.pool`` (the batched
        screen) / ``.verify`` (the winner's single-candidate
        re-evaluation); seconds in ``wheel_totals()["rounds"]``."""
        with obs.span("incumbent.round", cat="wheel") as sp:
            ran = self._try_pool(X)
        if ran:
            t = self._round_tot
            t["rounds"] += 1
            t["seconds"] += sp.seconds
            t["round_s"].append(sp.seconds)

    def _try_pool(self, X):
        from ..ops import incumbent as _inc
        key = X.tobytes()
        reused = key == self._last_X_key
        self._last_X_key = key
        if reused:
            # identical consecutive consensus block: the deterministic
            # rows would reproduce the previous pool bit for bit — skip
            # the regeneration (ISSUE 9 satellite) and explore instead
            obs.counter_add("incumbent.pool_reused")
        pool = _inc.build_pool(
            X, np.asarray(self.opt.prob), self._dive_mask,
            self.opt.nonant_integer_mask, self._lb_row, self._ub_row,
            thresholds=self._thresholds, flips=self._flips,
            n_random=self._n_random, ball=self._ball, seed=self._seed,
            round_index=self._rounds, random_only=reused)
        if pool is None:       # unchanged block, nothing left to vary
            return False
        self._rounds += 1
        obs.counter_add("incumbent.rounds")
        with obs.span("incumbent.round.pool", cat="wheel") as sp_p:
            self.opt._pool_obj_rows = None
            objs, feas = self.opt.evaluate_incumbent_pool(
                pool, pin_mask=self._pin_mask, **self._screen_kw)
        self._round_tot["pool_s"] += sp_p.seconds
        self.last_screen = {"pool": np.asarray(pool), "objs": objs,
                            "feas": feas,
                            "rows": self.opt._pool_obj_rows,
                            "source": self._last_hub_id,
                            "at": time.perf_counter()}
        # no killed() gate here: the evaluation is already paid, the
        # publish below is one window put (the kill signal rides the
        # OTHER window), and dropping a computed incumbent on the way
        # out would discard exactly the bound a terminating wheel
        # reports (VERDICT r2 weak #5 is about mid-eval waits, not
        # publishes)
        obs.counter_add("incumbent.candidates_evaluated", len(objs))
        obs.counter_add("incumbent.feasible", int(feas.sum()))
        improved = False
        best_val = None
        good = np.flatnonzero(feas & np.isfinite(objs))
        if good.size:
            b = int(good[np.argmin(objs[good])])
            best_val = float(objs[b])
            if self.bound is None or best_val < self.bound:
                cand = self.opt.round_nonants(np.asarray(pool[b]))
                # the pool verdict is the SCREEN; the winner is
                # re-evaluated through the tight single-candidate path
                # before publishing — pool solves run at fixed rho with
                # a shared budget over rows that include infeasible
                # members, so their values are valid-but-loose (0.26%
                # measured on UC round 0) and can even be optimistic
                # when a fallback solve stops half-converged. One
                # warm-started full-batch solve makes the published
                # value evaluator-grade (the same number every other x̂
                # spoke would publish for this candidate).
                with obs.span("incumbent.round.verify",
                              cat="wheel") as sp_v:
                    best_val = self.opt.calculate_incumbent(
                        cand, feas_tol=self._publish_feas_tol,
                        pin_mask=self._pin_mask)
                self._round_tot["verify_s"] += sp_v.seconds
                self._round_tot["verifications"] += 1
                rows = getattr(self.opt, "_incumbent_rows", None)
                if self.options.get("xhat_exact_eval", False) \
                        and self._incumbent_mode != "device" \
                        and best_val is not None:
                    # exact certification before publishing (the
                    # configured-distrust contract of try_candidates)
                    status, exact = self._exact_eval(cand)
                    best_val = exact if status == "ok" else None
                if best_val is not None and (self.bound is None
                                             or best_val < self.bound):
                    self.best_xhat = cand
                    self.best_xhat_rows = rows
                    self.update_bound(best_val)
                    improved = True
                    obs.counter_add("incumbent.improvements")
        obs.event("incumbent.round", {
            "round": self._rounds, "pool": int(len(objs)),
            "feasible": int(feas.sum()),
            "best": obs.finite_or_none(best_val),
            "bound": obs.finite_or_none(self.bound),
            "improved": improved, "reused": bool(reused)})
        self._dry = 0 if improved else self._dry + 1
        if (self._incumbent_mode == "auto" and self.best_xhat is not None
                and self._oracle_after > 0
                and self._dry >= self._oracle_after):
            # oracle POLISH (auto mode only): one exact host evaluation
            # of the standing best after N dry device rounds — the
            # opt-in fallback the tentpole demotes the OraclePool to
            self._dry = 0
            obs.counter_add("incumbent.oracle_polish")
            status, exact = self._exact_eval(self.best_xhat)
            if status == "ok" and exact is not None \
                    and (self.bound is None or exact < self.bound):
                self.update_bound(exact)
        return True


class XhatLooperInnerBound(_XhatInnerBound):
    def candidates(self, X):
        limit = int(self.options.get("xhat_scen_limit", 3))
        for s in range(min(limit, self.opt.batch.S)):
            yield X[s]


class XhatShuffleInnerBound(_XhatInnerBound):
    def __init__(self, spbase_object, options=None):
        super().__init__(spbase_object, options)
        S = self.opt.batch.S
        rng = np.random.RandomState(self.options.get("xhat_seed", 42))
        self._order = rng.permutation(S)        # ref. :108-111 seed 42
        self._pos = 0                           # ScenarioCycler resume point
        self._consensus_turn = False

    def spoke_state(self):
        """+ the cycler position, so a resumed incarnation continues
        the shuffled epoch instead of re-walking its prefix."""
        state = super().spoke_state()
        state["pos"] = int(self._pos)
        return state

    def install_spoke_state(self, state):
        super().install_spoke_state(state)
        pos = state.get("pos")
        if pos is not None:
            self._pos = int(pos) % len(self._order)

    def _consensus_fresh(self):
        """A consensus candidate exists AND its dedup key is not in the
        recent-key ring — i.e. yielding it would actually be evaluated.
        A stale consensus turn must fall through to the scenario cycle
        in the SAME pass (ADVICE r5): returning after a dedup hit
        wasted every other pass while the hub plateaued."""
        cons = getattr(self, "_consensus_cand", None)
        if cons is None:
            return False
        seen = getattr(self, "_seen_keys", None)
        if seen is None:
            return True
        return np.asarray(self.opt.round_nonants(cons)).tobytes() \
            not in seen

    def needs_prepare(self):
        # candidates() flips _consensus_turn then yields: the NEXT turn
        # consumes the consensus candidate (skipping the prepared
        # block) iff the flag is currently False and a FRESH consensus
        # exists — a stale one falls through to the scenario cycle,
        # which does read the prepared block
        return not (not self._consensus_turn and self._consensus_fresh())

    def candidates(self, X):
        # one candidate per fresh-nonant pass; epoch wraps around.
        # With xhat_consensus_candidates, alternate between the
        # consensus-rounded candidate (see _stash_consensus) and the
        # scenario cycle; a consensus already in the dedup ring (hub
        # barely moved) falls through to the scenario cycle so the
        # pass still evaluates something (ADVICE r5).
        self._consensus_turn = not self._consensus_turn
        if self._consensus_turn and self._consensus_fresh():
            yield self._consensus_cand
            return
        s = int(self._order[self._pos])
        self._pos = (self._pos + 1) % len(self._order)
        yield X[s]


class XhatLShapedInnerBound(_XhatInnerBound):
    """Evaluates the L-shaped hub's master candidate x as an incumbent
    (ref. mpisppy/cylinders/lshaped_bounder.py:15-91). The hub broadcasts
    the same first-stage plan to every scenario row, so the candidate is
    just row 0 of the nonant block."""

    def candidates(self, X):
        yield X[0]


class XhatSpecificInnerBound(_XhatInnerBound):
    """`xhat_scenario_dict` maps non-leaf stage (1-based) -> scenario index
    whose values seed that stage's slots; scenarios inherit through the tree
    membership, so this works for multistage (ref. xhatspecific_bounder.py)."""

    def candidates(self, X):
        spec = self.options.get("xhat_scenario_dict", {1: 0})
        b = self.opt.batch
        cand = np.empty((b.S, b.K))
        for t, sl in enumerate(b.stage_slot_slices, start=1):
            chosen = int(spec.get(t, 0))
            B = b.tree.membership(t)                      # (S, N_t)
            # per scenario s, copy stage-t slots from the chosen scenario of
            # s's node; with one chosen scenario per stage, all scenarios in
            # other nodes reuse their own node's representative: pick, per
            # node, the lowest-index scenario if `chosen` is outside the node
            path = b.tree.node_path[:, t - 1]
            for node in range(B.shape[1]):
                members = np.flatnonzero(path == node)
                src = chosen if chosen in members else int(members[0])
                cand[members, sl] = X[src, sl]
        yield cand
