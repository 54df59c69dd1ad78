"""Scenario-sharded APH over the async Synchronizer (multi-process).

The missing half of the reference's APH runtime (ref. mpisppy/opt/aph.py:
818-921 + mpisppy/utils/listener_util/listener_util.py:277-327): ranks
hold scenario shards, a listener thread on each rank keeps reducing the
(x̄, x̄², ȳ) "FirstReduce" and (τ, φ, norms) "SecondReduce" concatenations
*while* the worker solves, and the worker proceeds once enough ranks have
fresh data (``async_frac_needed``) — wall-clock overlap of reduction
communication with subproblem compute, staleness tolerated by design.

Here a "rank" is an OS process owning a contiguous scenario shard
(ir/batch.py shard_batch — the analog of the reference's contiguous
rank map, ref. spbase.py:172) with its own engine and device stream; the
listener exchange rides the native seqlock shm windows through
utils/synchronizer.Synchronizer (the DCN analog; on a multi-host TPU pod
each shard process is a host). The in-process APH (core/aph.py) remains
the single-chip fast path where the reductions are membership matmuls
inside the jitted step; this module is the multi-host deployment shape.

Reduction layout (per-stage node summands, flattened and concatenated —
multistage-safe because membership columns are global, see shard_batch):

  First  = [Σp·x | Σp·x² | Σp·y  per (node, slot) | Σp per node
            | per-shard timestamps]                  (3·Σ N_t k_t + Σ N_t + n)
  Second = [τ, φ, pusq, pvsq, pwsq, pzsq | per-shard timestamps]   (6 + n)

Timestamps live in per-shard slots (each shard sums in only its own), so
the reduced vector carries every shard's iteration count — the
enough-fresh check of the reference's side gig (ref. aph.py:204-324).
Convergence norms ride the same iteration's SecondReduce computed from
the PRE-step (W, z): the conv metric is "one notch behind", exactly the
staleness the reference's worker accepts (ref. listener_util.py:164-182
keep_up).
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from .. import global_toc
from ..ir.batch import shard_batch
from ..utils.runtime import child_jax_env, spawn_environment
from ..utils.synchronizer import Synchronizer
from .aph import APH, aph_conv_metric, aph_theta_step


class APHShard(APH):
    """One shard's APH engine + worker loop. Construct via ``make_shard``;
    drive via ``run`` (which owns the Synchronizer listener)."""

    def __init__(self, batch, options, n_shards, my_shard, shm_prefix=None,
                 windows=None, **kw):
        opts = dict(options or {})
        opts["partial_probabilities"] = True
        super().__init__(batch, opts, **kw)
        self.n_shards = int(n_shards)
        self.my_shard = int(my_shard)
        self.async_frac_needed = float(
            self.options.get("async_frac_needed", 1.0))
        self.async_sleep_secs = float(
            self.options.get("async_sleep_secs", 0.002))
        # per-stage (N_t, k_t) summand shapes
        self._stage_shapes = self.stage_shapes(self.batch)
        nk = sum(N * k for N, k in self._stage_shapes)
        nden = sum(N for N, _ in self._stage_shapes)
        self._nk, self._nden = nk, nden
        # wheel mode: aph_wheel_S = GLOBAL scenario count enables the
        # WX gather; shard 0 additionally carries the hub communicator
        # (set by the wheel launcher). The gather is an ON-DEMAND
        # reduction (summed once per APH iteration in _wheel_sync) —
        # riding the listener beat would republish + re-sum 2·S·K
        # doubles every ~5 ms.
        self._wheel_S = self.options.get("aph_wheel_S")
        ondemand = None
        if self._wheel_S is not None:
            self._wheel_S = int(self._wheel_S)
            self._shard_lo = shard_range(self._wheel_S, self.my_shard,
                                         self.n_shards)[0]
            K = sum(k for _, k in self._stage_shapes)
            ondemand = {"WX": 2 * self._wheel_S * K}
            if int(self.options.get("aph_sync_every", 0)):
                # the wheel's termination break is asynchronous (shard 0
                # decides on gap); the periodic barrier's equal-call-
                # count contract cannot survive it
                raise ValueError("aph_sync_every cannot be combined "
                                 "with wheel mode (aph_wheel_S): the "
                                 "hub's gap termination breaks the "
                                 "barrier call-count alignment")
        lens = self.reduction_lens(self.batch, self.n_shards)
        self.sync = Synchronizer(
            lens, self.n_shards, self.my_shard, shm_prefix=shm_prefix,
            windows=windows, ondemand_lens=ondemand,
            sleep_secs=float(self.options.get("listener_sleep_secs", 0.005)))
        self._g = {r: np.zeros(l) for r, l in lens.items()}
        self._l = {r: np.zeros(l) for r, l in lens.items()}

    # ---- wire layout (the ONE definition thread-mode embedders need to
    # prebuild the shared window table from) ----
    @staticmethod
    def stage_shapes(batch):
        return [(batch.tree.nodes_per_stage[t], sl.stop - sl.start)
                for t, sl in enumerate(batch.stage_slot_slices)]

    @classmethod
    def reduction_lens(cls, batch, n_shards):
        shapes = cls.stage_shapes(batch)
        nk = sum(N * k for N, k in shapes)
        nden = sum(N for N, _ in shapes)
        return {"First": 3 * nk + nden + n_shards,
                "Second": 6 + n_shards}

    # ---- summand packing ----
    def _node_summands(self, arr):
        """Per-stage B_tᵀ(p⊙arr[:, sl]) flattened and concatenated."""
        p = self.prob[:, None]
        outs = []
        for B, sl in zip(self.memberships, self.batch.stage_slot_slices):
            outs.append(jnp.ravel(B.T @ (p * arr[:, sl])))
        return jnp.concatenate(outs)

    def _den_summands(self):
        return jnp.concatenate([B.T @ self.prob for B in self.memberships])

    def _broadcast_nodes(self, flat):
        """Inverse of _node_summands: (Σ N_t k_t,) node values -> (S, K)."""
        out, off = [], 0
        for B, (N, k) in zip(self.memberships, self._stage_shapes):
            blk = jnp.asarray(flat[off:off + N * k].reshape(N, k), self.dtype)
            out.append(B @ blk)
            off += N * k
        return jnp.concatenate(out, axis=1)

    def _expand_den(self, dens):
        """(Σ N_t,) per-node masses -> (Σ N_t k_t,) aligned with the
        flattened per-(node, slot) numerators. A node no published shard
        passes through has zero mass; its quotient must not NaN-poison
        the broadcast matmul (0-column · NaN = NaN) — this shard never
        consumes such nodes (its own summand keeps every node it owns
        positive), so any placeholder is safe; use 1."""
        out, off = [], 0
        for N, k in self._stage_shapes:
            d = dens[off:off + N]
            out.append(np.repeat(np.where(d > 0, d, 1.0), k))
            off += N
        return np.concatenate(out)

    def _wait_fresh(self, red, it, vec):
        """Stage my summand (timestamp = it) and spin until the reduced
        vector shows >= async_frac_needed shards at timestamp >= it (the
        reference worker's spin for the side gig, ref. aph.py:327-448).
        The listener keeps folding stragglers in underneath us. The spin
        polls only the timestamp tail; the full vector is copied once,
        when fresh. A hard-killed peer never publishes anything — the
        deadline turns that into an error instead of an infinite spin."""
        ts = np.zeros(self.n_shards)
        ts[self.my_shard] = it
        self._l[red][:] = np.concatenate([vec, ts])
        need = max(1, int(np.ceil(self.async_frac_needed * self.n_shards)))
        self.sync.compute_global_data(self._l, self._g, rednames=[red],
                                      keep_up=True)
        deadline = time.monotonic() + float(
            self.options.get("aph_wait_timeout", 600.0))
        while True:
            fresh = int((self._g[red][-self.n_shards:] >= it).sum())
            if fresh >= need or self.sync.global_quitting:
                self.sync.compute_global_data(self._l, self._g,
                                              rednames=[red], keep_up=True)
                # a COPY, not a view into self._g: the buffer is
                # overwritten in place by the next compute_global_data /
                # peek_tail, and a caller holding the result across the
                # next reduce would read silently corrupted data
                # (ADVICE r3). The per-iteration memcpy is negligible
                # next to the solves.
                return self._g[red][:-self.n_shards].copy()
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"shard {self.my_shard}: {red} never got "
                    f"{need}/{self.n_shards} fresh shards at iter {it} — "
                    "a peer process likely died without publishing quit")
            time.sleep(self.async_sleep_secs)
            self._g[red][-self.n_shards:] = self.sync.peek_tail(
                red, self.n_shards)

    # ---- wheel citizenship (spin_aph_shard_wheel) ----
    def _wheel_sync(self, xn):
        """Publish this shard's (W, x-nonant) rows into the WX gather;
        on the hub-carrying shard, stage the gathered FULL arrays and
        run the cylinder sync. Returns True when the wheel terminated
        (gap met / spokes satisfied) — a loop-exit for the caller."""
        if self._wheel_S is None:
            return False
        K = self.batch.K
        off = self._wheel_S * K
        lo = self._shard_lo * K
        S_loc = self.batch.S
        buf = np.zeros(2 * off)
        buf[lo:lo + S_loc * K] = \
            np.asarray(self.W, np.float64).reshape(-1)
        buf[off + lo:off + lo + S_loc * K] = \
            np.asarray(xn, np.float64).reshape(-1)
        if self.spcomm is None:
            # non-hub shards PUBLISH only — the read+sum of n_shards
            # 2*S*K vectors per iteration would be pure waste on their
            # hot loop (the hub shard does the one gather below)
            self.sync.publish_now("WX", buf)
            return False
        # on-demand gather (disjoint rows -> the sum is an exact
        # concat, stale for other shards by at most their publish lag)
        g, min_wid = self.sync.reduce_now("WX", buf, return_min_wid=True)
        if min_wid < 1:
            # a shard has not published its first WX summand yet: the
            # gather holds zero rows for it, and staging that would
            # hand spokes partially-zero (W, x) — W-projection keeps
            # outer bounds valid but xhat spokes would burn dive/oracle
            # passes on zero-row candidate blocks (ADVICE r4). Skip the
            # cylinder sync this round; the next gather retries.
            return False
        self.wheel_W = g[:off].reshape(self._wheel_S, K)
        self.wheel_X = g[off:].reshape(self._wheel_S, K)
        self.spcomm.sync()
        return bool(self.spcomm.is_converged())

    # ---- the worker loop (one shard's APH_iterk) ----
    def _work(self):
        warm = getattr(self, "_warm_started", False)
        self.solve_loop(w_on=warm, prox_on=False, update=False)
        # iter-0 feasibility + trivial bound are genuinely collective:
        # the reference runs Iter0 synchronously before the listener
        # starts (ref. aph.py:889); sync_allreduce is that barrier
        ok, _ = self.iter0_feasible_mask()
        feas, bound = self.sync.sync_allreduce(
            np.array([float(np.dot(np.asarray(self.prob), ok)),
                      self.Ebound()]))
        if self.options.get("iter0_infeasibility_abort", True) \
                and abs(feas - 1.0) > 1e-6:
            raise RuntimeError(f"iter 0: global feasible probability {feas} "
                               "!= 1 (ref. phbase.py:1415-1427 abort)")
        self.trivial_bound = self.best_bound = bound
        # global iter-0 xbar (Update_W reads self.xbar; a shard-local mean
        # would seed W inconsistently across shards)
        xn0 = self.nonants_of(self.x)
        nk, nden = self._nk, self._nden
        g0 = self.sync.sync_allreduce(np.concatenate([
            np.asarray(self._node_summands(xn0)),
            np.asarray(self._den_summands())]))
        self.xbar = self._broadcast_nodes(g0[:nk] / self._expand_den(g0[nk:]))
        if not warm:
            # a restored W checkpoint must not be double-updated
            # (same guard as APH_main, core/aph.py)
            self.Update_W()
        if self.use_lag:
            # lagged (W, z) for dispatched solves (ref. aph.py:188-190)
            self._W_lag = self.W
            self._z_lag = self.z
        global_toc(f"APHShard[{self.my_shard}] iter 0: trivial bound = "
                   f"{bound:.4f}", self.verbose and self.my_shard == 0)
        wheel_done = self._wheel_sync(xn0)
        if wheel_done:
            global_toc("APHShard wheel: iter-0 termination",
                       self.verbose and self.my_shard == 0)

        nu, gamma = self.nu, self.gamma
        self.conv = np.inf
        it = self._iter = 0
        while not wheel_done and it < self.max_iterations \
                and not self.sync.global_quitting:
            it += 1
            self._iter = it
            xn = self.nonants_of(self.x)
            if it > 1:
                W_y = self._W_lag if self.use_lag else self.W
                z_y = self._z_lag if self.use_lag else self.z
                y_new = W_y + self.rho * (xn - z_y)
                self.y_aph = jnp.where(
                    jnp.asarray(self._dispatched)[:, None], y_new, self.y_aph)
            first = np.asarray(jnp.concatenate([
                self._node_summands(xn), self._node_summands(xn * xn),
                self._node_summands(self.y_aph), self._den_summands()]))
            gfirst = self._wait_fresh("First", it, first)
            if self.sync.global_quitting:
                break
            den = self._expand_den(gfirst[3 * nk:3 * nk + nden])
            xbar = self._broadcast_nodes(gfirst[:nk] / den)
            xsqbar = self._broadcast_nodes(gfirst[nk:2 * nk] / den)
            ybar = self._broadcast_nodes(gfirst[2 * nk:3 * nk] / den)

            u = xn - xbar
            pusq = float(jnp.dot(self.prob, jnp.sum(u * u, axis=1)))
            pvsq = float(jnp.dot(self.prob, jnp.sum(ybar * ybar, axis=1)))
            phi = float(jnp.dot(self.prob, jnp.sum(
                (self.z - xn) * (self.W - self.y_aph), axis=1)))
            pwsq = float(jnp.dot(self.prob, jnp.sum(self.W * self.W, axis=1)))
            pzsq = float(jnp.dot(self.prob, jnp.sum(self.z * self.z, axis=1)))
            tau_sum = pusq + pvsq / gamma
            second = np.array([tau_sum, phi, pusq, pvsq, pwsq, pzsq])
            gsecond = self._wait_fresh("Second", it, second)
            if self.sync.global_quitting:
                break
            gtau, gphi, gpusq, gpvsq, gpwsq, gpzsq = gsecond

            # the SAME θ-step as the fused single-chip update, fed the
            # Synchronizer-reduced globals (see aph.aph_theta_step).
            # CONSISTENCY CAVEAT (deliberate deviation, ADVICE r3): with
            # async_frac_needed < 1 each shard computes θ from its OWN
            # staleness-dependent view of (τ, φ), so shards can apply
            # slightly different θ in the same iteration — whereas the
            # reference's MPI Allreduce guarantees rank-identical
            # reduced scalars and one θ per iteration (ref.
            # listener_util.py:193-199 asynch=False SecondReduce). This
            # is the price of the wait-free exchange; APH's convergence
            # theory tolerates bounded staleness in (W, z) exactly as it
            # tolerates the dispatch lag, and frac=1 (the default)
            # restores rank-identical scalars because every shard then
            # folds the same n_shards fresh summands. Deployments that
            # need strict reference parity at frac < 1 should
            # periodically barrier via sync_allreduce (aph_sync_every).
            sync_every = int(self.options.get("aph_sync_every", 0))
            synced = False
            if sync_every and it % sync_every == 0:
                # consistent snapshot: barrier-reduce the FULL
                # SecondReduce so every shard applies the SAME θ and
                # sees the SAME conv this iteration (drift cannot
                # accumulate unboundedly). The collective-call-count
                # contract of sync_allreduce demands every shard pass
                # the same barrier sequence — guaranteed because `it`
                # advances uniformly per shard and, below, the
                # convthresh exit is restricted to synced iterations
                # (where conv is rank-identical), so shards cannot
                # leave the loop at different barrier counts. A peer
                # quitting mid-barrier (crash or max-iter exit) is a
                # loop exit for us too, not an error.
                try:
                    # same patience as every other wait in this loop —
                    # the 300 s sync_allreduce default would kill a
                    # shard waiting on a healthy-but-slow peer several
                    # iterations behind (hospital-assisted solves run
                    # tens of seconds per iteration)
                    gsync = self.sync.sync_allreduce(
                        second, timeout=float(
                            self.options.get("aph_wait_timeout", 600.0)))
                except RuntimeError:
                    if self.sync.global_quitting:
                        break
                    raise
                (gtau, gphi, gpusq, gpvsq, gpwsq, gpzsq) = (
                    float(v) for v in gsync[:6])
                synced = True
            self.W, self.z, theta = aph_theta_step(
                u, ybar, self.W, self.z, xbar, gtau, gphi, nu, gamma,
                iter1=(it == 1))
            theta = float(theta)
            self.xbar, self.xsqbar, self.ybar = xbar, xsqbar, ybar
            self.tau, self.phi, self.theta = gtau, gphi, theta
            # conv from THIS SecondReduce's (W, z) norms — they are the
            # pre-step norms, i.e. the previous θ-step's result: the
            # "one notch behind" staleness the reference worker accepts
            self.conv = float(aph_conv_metric(gpusq, gpvsq, gpwsq, gpzsq))

            phis = np.asarray(self.prob * jnp.sum(
                (self.z - xn) * (self.W - self.y_aph), axis=1))
            self.phis = phis
            global_toc(f"APHShard iter {it}: conv={self.conv:.3e} "
                       f"theta={theta:.3e}",
                       self.verbose and self.my_shard == 0 and it % 10 == 0)
            # wheel sync: gather the full (W, x), push to spokes from
            # the hub shard, terminate the loop on gap/hub decision
            if self._wheel_sync(xn):
                global_toc(f"APHShard wheel: termination at iter {it}",
                           self.verbose and self.my_shard == 0)
                break
            # with the periodic barrier on, the convthresh exit is only
            # taken at SYNCED iterations: conv is then rank-identical,
            # so every shard leaves at the same iteration and the
            # barrier call counts stay aligned (see the consistency
            # note above). Without it (pure async), conv is advisory
            # per shard and the exit is wait-free as before — the only
            # remaining collective is the wrap-up reduce, which every
            # shard calls exactly once regardless of exit iteration.
            if self.conv < self.convthresh and (not sync_every or synced):
                break
            frac = 1.0 if it == 1 else self.dispatch_frac
            mask = self._dispatch_mask(it, frac)
            self._aph_solve(mask)

        self.sync.quitting = 1
        # final collective: global expected objective of the CURRENT local
        # solutions. Evaluated from self.x directly — _last_base_obj also
        # covers solves whose results were REJECTED for non-dispatched
        # scenarios (x reverted in _aph_solve), which would price a
        # solution no scenario actually holds when dispatch_frac < 1
        try:
            eobj = self.sync.sync_allreduce(
                np.array([float(self.Eobjective(
                    self.scenario_objectives(self.x)))]),
                abort_on_quit=False, timeout=60.0)[0]
        except TimeoutError:
            # a peer died without reaching the wrap-up collective; its
            # own exception is the root cause — don't mask it with a
            # stall, report "no global objective" instead
            eobj = np.nan
        return self.conv, float(eobj), self.trivial_bound

    def run(self):
        try:
            return self.sync.run(self._work)
        finally:
            self.sync.close()


def shard_range(S, my_shard, n_shards):
    """The contiguous [lo, hi) scenario range of a shard — the ONE
    definition both entry points (in-process make_shard, process worker)
    must agree on (ref. spbase.py:172 _calculate_scenario_ranks)."""
    if n_shards > S:
        raise ValueError(
            f"{n_shards} shards for {S} scenarios would leave empty "
            "shards (the reference requires scenarios >= ranks too, "
            "ref. spbase.py:172)")
    return (S * my_shard) // n_shards, (S * (my_shard + 1)) // n_shards


def make_shard(batch, options, n_shards, my_shard, shm_prefix=None,
               windows=None, **kw):
    """Build shard ``my_shard`` of ``n_shards`` from the FULL batch: slice
    the contiguous range, keep global probabilities."""
    lo, hi = shard_range(batch.S, my_shard, n_shards)
    return APHShard(shard_batch(batch, lo, hi), options, n_shards, my_shard,
                    shm_prefix=shm_prefix, windows=windows, **kw)


# ---- multi-process driver (the deployment shape: one shard per host
# process, shm/DCN exchange; ref. aph.py:818 APH_main under mpiexec) ----

def _shard_worker(model, num_scens, creator_kwargs, options, n_shards,
                  my_shard, prefix, q, wheel=None):
    """``wheel``: optional dict {run_id, spoke_kinds, hub_options} —
    shard 0 then opens the spoke windows the launcher created and
    carries an APHShardHub through the APH loop (every shard gets
    options["aph_wheel_S"] so the WX gather exists group-wide)."""
    try:
        # the platform ("cpu" unless options say otherwise) is in this
        # process's environment from its first instruction — the
        # launcher starts it under utils/runtime.spawn_environment
        from ..utils.runtime import setup_jax_runtime

        setup_jax_runtime(f32=bool((options or {}).get("f32", False)))
        import importlib

        mod = importlib.import_module(f"mpisppy_tpu.models.{model}")
        from ..ir.batch import build_batch, subtree

        # lower ONLY this shard's scenarios (the reference builds per-rank
        # locals the same way, ref. spbase.py:242 _create_scenarios) — the
        # model-lowering step is the expensive part at large S
        tree = mod.make_tree(num_scens)
        lo, hi = shard_range(num_scens, my_shard, n_shards)
        batch = build_batch(mod.scenario_creator, subtree(tree, lo, hi),
                            creator_kwargs=creator_kwargs)
        eng = APHShard(batch, options, n_shards, my_shard, shm_prefix=prefix)
        hub = None
        if wheel is not None and my_shard == 0:
            from ..cylinders.hub import APHShardHub
            from ..utils.multiproc import open_spoke_proxies

            proxies = open_spoke_proxies(wheel["spoke_kinds"],
                                         wheel["run_id"], num_scens,
                                         batch.K)
            hub = APHShardHub(eng, spokes=proxies,
                              options=wheel.get("hub_options") or {})
            hub.classify_spokes()
            hub.windows_made = True
            hub.setup_hub()
            eng.spcomm = hub
        try:
            conv, eobj, triv = eng.run()
        finally:
            if hub is not None:
                # release the spoke processes whatever happened to the
                # APH loop (the launcher joins them afterwards)
                hub.send_terminate()
        if hub is not None:
            outer, inner = hub.hub_finalize()
            for proxy in hub.spokes:
                proxy.hub_window.close(unlink=False)
                proxy.my_window.close(unlink=False)
            q.put((my_shard, (conv, eobj, triv, eng._iter, outer, inner)))
        else:
            q.put((my_shard, (conv, eobj, triv, eng._iter)))
    except Exception as e:           # surface, don't hang the parent —
        # construction failures (shm open timeout, spbase validation)
        # must reach the queue too, not just run() failures
        q.put((my_shard, e))
        raise


def spin_aph_shards(model: str, num_scens: int, options, n_shards: int,
                    creator_kwargs=None, join_timeout=600.0, _wheel=None):
    """Spawn one OS process per scenario shard and run APHShard in each.
    Returns shard 0's (conv, Eobjective, trivial_bound, iters). The spawn
    context is used so children initialize JAX cleanly."""
    import multiprocessing as mp
    import os
    import secrets

    shard_range(num_scens, 0, n_shards)   # fail fast on empty shards
    ctx = mp.get_context("spawn")
    prefix = f"/aphs{os.getpid():x}{secrets.token_hex(3)}"
    q = ctx.Queue()
    procs = [ctx.Process(target=_shard_worker,
                         args=(model, num_scens, creator_kwargs,
                               dict(options or {}), n_shards, i, prefix, q,
                               _wheel if i == 0 else None),
                         daemon=True)
             for i in range(n_shards)]
    with spawn_environment(child_jax_env(options)):
        for p in procs:
            p.start()
    results = {}
    try:
        import queue as _queue

        for _ in range(n_shards):
            try:
                shard, res = q.get(timeout=join_timeout)
            except _queue.Empty:
                dead = [i for i, p in enumerate(procs) if not p.is_alive()]
                raise RuntimeError(
                    f"APH shards never reported within {join_timeout:.0f}s; "
                    f"dead shard processes: {dead or 'none (hung)'}")
            if isinstance(res, Exception):
                raise RuntimeError(f"APH shard {shard} failed: {res!r}")
            results[shard] = res
    finally:
        for p in procs:
            p.join(timeout=30.0)
            if p.is_alive():
                p.terminate()
        # terminated/crashed children never reach Synchronizer.close();
        # reap whatever segments the group left in /dev/shm
        from ..utils.synchronizer import cleanup_shm

        cleanup_shm(prefix)
    return results[0]


def spin_aph_shard_wheel(cfg, n_shards: int, join_timeout=600.0,
                         spoke_ready_timeout=300.0):
    """The reference's "APH hub + bound spokes under mpiexec" deployment
    shape (ref. mpisppy/cylinders/hub.py:606 APHHub over rank groups):
    one OS process per scenario shard running APHShard over the async
    Synchronizer, PLUS one OS process per spoke cylinder (the same
    worker utils/multiproc uses), with shard 0 carrying the wheel's hub
    communicator (cylinders/hub.APHShardHub). ``cfg`` is a RunConfig
    whose hub is "aph"; returns (conv, Eobjective, trivial_bound,
    iters, best_outer, best_inner)."""
    import multiprocessing as mp
    import os
    import secrets

    from ..utils.multiproc import spawn_spoke_processes, wait_spoke_hellos
    from ..ir.batch import build_batch, subtree
    import importlib

    cfg.validate()
    mod = importlib.import_module(f"mpisppy_tpu.models.{cfg.model}")
    # K without lowering the whole batch: lower scenario 0 only
    probe = build_batch(mod.scenario_creator,
                        subtree(mod.make_tree(cfg.num_scens), 0, 1),
                        creator_kwargs=cfg.model_kwargs)
    S, K = cfg.num_scens, probe.K

    run_id = f"/apw{os.getpid():x}{secrets.token_hex(3)}"
    ctx = mp.get_context("spawn")
    owned, spoke_procs = [], []
    try:
        proxies, spoke_procs, owned = spawn_spoke_processes(
            cfg, run_id, ctx, S, K)
        # wait for every spoke's startup hello so a fast APH run cannot
        # terminate before the spokes are wired (the parent-side
        # proxies are only used for this wait; shard 0 opens its own)
        wait_spoke_hellos(cfg, proxies, spoke_procs, spoke_ready_timeout)

        options = dict(cfg.algo.to_options())
        options.update(cfg.hub_options)
        options["aph_wheel_S"] = S
        hub_options = {}
        if cfg.rel_gap is not None:
            hub_options["rel_gap"] = cfg.rel_gap
        if cfg.abs_gap is not None:
            hub_options["abs_gap"] = cfg.abs_gap
        wheel = {"run_id": run_id,
                 "spoke_kinds": [sp.kind for sp in cfg.spokes],
                 "hub_options": hub_options}
        res = spin_aph_shards(cfg.model, S, options, n_shards,
                              creator_kwargs=cfg.model_kwargs,
                              join_timeout=join_timeout, _wheel=wheel)
        return res
    finally:
        for p in spoke_procs:
            p.join(timeout=30.0)
            if p.is_alive():
                p.terminate()
        for w in owned:
            w.close(unlink=True)
