"""Device-side batched incumbent search: candidate pools over the nonants.

The reference gets MIP-quality incumbents by handing every candidate to a
commercial B&B solver (ref. mpisppy/cylinders/xhatshufflelooper_bounder.py
:108 uses solved MIP subproblem first stages); the TPU port's host analog
(utils/host_oracle.OraclePool) pays per-scenario HiGHS subprocesses — at
reference UC scale that host wall is the binding constraint on
time-to-gap (the round-5 chip run: the uc1024 incumbent sat 7.4% off for
841 s while oracle MILPs ground away). SURVEY.md ranks "batched MIP-quality
incumbents without a B&B solver" the #1 hard part.

This module is the device answer (doc/incumbents.md): manufacture a POOL
of rounding candidates from the hub's consensus block as ONE jitted op
over the (scenario x var) nonant matrix, then evaluate the whole pool as
ordinary chunks of batched fix-and-dive repair solves
(core/ph.PHBase.evaluate_incumbent_pool): each candidate's binaries are
FIXED (bound-tightening l = u = x̂_b on the standard form, batched over
the pool axis) and the continuous recourse re-solves through the
existing donated warm-start kernel path. No host solver anywhere in the
loop; the pool is literally another chunk of the pipelined dispatch, so
gate syncs stay O(1) per round and sharded meshes split the rows across
devices.

Pool anatomy (``build_pool``), P = len(thresholds) + flips + n_random + 4
(two slam rows + two bound rows):

- VOTE rows: per-variable scenario-probability-weighted vote rounding of
  the consensus at multiple thresholds (commit every dive slot the fleet
  runs at >= tau in the mean — the classic UC consensus rounding,
  generalizing xhat_bounders._stash_consensus's single threshold);
- FLIP rows: the local-branching ball — the top-k MOST fractional dive
  slots of the consensus each flipped individually on the tau=0.5 base
  candidate (the slots the fleet most disagrees on are where a single
  flip most plausibly improves the rounding);
- RANDOM rows: seeded radius-``ball`` random flip neighborhoods of the
  base candidate (jax PRNG folded with the round index — deterministic
  per (seed, round), fresh diversity across rounds);
- SLAM rows: the per-variable max/min over scenarios — the existing slam
  heuristics' candidates (cylinders/slam_heuristic.py) as pool members,
  so the pool's best is at least as good as the best slam by
  construction whenever the slam rows are feasible;
- BOUND rows: the dive slots slammed to their upper / lower bounds
  (maximum / minimum commitment). The max-commitment row is the
  covering-model feasible ANCHOR — always demand-covering and constant
  across hours, so min-up/down coupling cannot reject it — exactly the
  role xhat_bounders' ``xhat_union_fallback`` plays for the oracle
  candidates; rounded vote profiles routinely violate those coupling
  rows, and a pool with no feasible member publishes nothing.

``pool_verdict`` fuses the feasibility screen and the expected-objective
reduction into one device program so the caller pays exactly ONE stacked
D2H verdict per round.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def slam_rows(X):
    """(up, down): per-variable max/min over the scenario axis of a
    (S, K) nonant block — the slam heuristics' two candidates
    (ref. mpisppy/cylinders/slam_heuristic.py:24-153, the
    local-then-Allreduce(MAX/MIN) two-step collapsed to one axis
    reduction). The ONE host implementation, shared by the slam spokes
    and mirrored in-trace by ``_build_pool``'s slam block."""
    X = np.asarray(X)
    return X.max(axis=0), X.min(axis=0)


def pool_size(n_dive, thresholds=(0.3, 0.5, 0.7), flips=8, n_random=4):
    """Static pool row count for the given dive-slot count — the shape
    contract between ``build_pool`` and the compiled evaluation
    programs (P is identical for the deterministic and the
    ``random_only`` builds, so one solve program serves every round).
    The +4 is the two slam rows plus the two bound rows."""
    n_dive = int(n_dive)
    return (len(tuple(thresholds)) + min(int(flips), n_dive)
            + (int(n_random) if n_dive else 0) + 4)


@partial(jax.jit, static_argnames=("thresholds", "flips", "ball",
                                   "n_random", "random_only"))
def _build_pool(X, prob, dive_mask, int_mask, dive_idx, lb_row, ub_row,
                seed, round_index,
                *, thresholds, flips, ball, n_random, random_only):
    """The jitted pool builder (one op over the (S, K) nonant matrix).

    ``dive_mask`` (K,) bool: the BINARY nonant slots a candidate
    decides (vote-rounded / flipped); everything else carries the raw
    consensus value and is typically left unpinned by the evaluator's
    ``pin_mask``. ``int_mask`` (K,) bool: all integer slots — snapped
    to integral values so every row is evaluation-ready.
    ``random_only``: replace the deterministic blocks with seeded
    random neighborhoods of the base candidate — SAME static row count,
    used when the hub block is unchanged and rebuilding the
    deterministic rows would reproduce the previous pool bit for bit
    (the incumbent.pool_reused path, doc/incumbents.md)."""
    w = prob / jnp.maximum(prob.sum(), 1e-300)
    cons = w @ X                                            # (K,)
    base = jnp.where(dive_mask, (cons >= 0.5).astype(X.dtype), cons)

    def flip_at(sel):
        return base.at[sel].set(1.0 - base[sel])

    key = jax.random.fold_in(jax.random.PRNGKey(seed), round_index)

    def rand_cand(i):
        ki = jax.random.fold_in(key, i)
        sel = jax.random.choice(ki, dive_idx, (ball,), replace=False)
        return flip_at(sel)

    n_total = len(thresholds) + flips + n_random + 4
    if random_only:
        pool = jax.vmap(rand_cand)(jnp.arange(n_total))
    else:
        rows = [jnp.where(dive_mask, (cons >= tau).astype(X.dtype),
                          cons)[None]
                for tau in thresholds]
        if flips:
            # most-fractional-first: the slots the fleet most disagrees
            # on (non-dive slots key to -1 so they never enter the ball)
            frac = jnp.where(dive_mask, jnp.abs(cons - jnp.round(cons)),
                             -1.0)
            _, top = jax.lax.top_k(frac, flips)
            rows.append(jax.vmap(flip_at)(top))
        if n_random:
            rows.append(jax.vmap(rand_cand)(jnp.arange(n_random)))
        up, down = jnp.max(X, axis=0), jnp.min(X, axis=0)
        rows.append(jnp.stack([up, down]))
        # bound rows: max/min commitment on the dive slots (see the
        # module docstring — the covering-model feasible anchor)
        rows.append(jnp.stack(
            [jnp.where(dive_mask, ub_row, cons),
             jnp.where(dive_mask, lb_row, cons)]))
        pool = jnp.concatenate(rows)
    # integral snap on EVERY integer slot (vote/flip rows are already
    # 0/1 on the dive slots; slam/consensus values may be fractional)
    return jnp.where(int_mask[None, :], jnp.round(pool), pool)


def build_pool(X, prob, dive_mask, integer_mask, lb_row=None, ub_row=None,
               *, thresholds=(0.3, 0.5, 0.7), flips=8, n_random=4, ball=4,
               seed=42, round_index=0, random_only=False):
    """(P, K) candidate pool from the hub's (S, K) nonant block (device
    array; see ``_build_pool`` for the row anatomy). Host wrapper: it
    resolves the STATIC sizes (flips/ball clamp to the dive-slot count,
    random rows need dive slots at all) so the jitted builder compiles
    once per configuration. Returns None for a ``random_only`` build
    with no dive slots — there is no neighborhood to vary, so the
    caller skips the round instead of re-evaluating an identical
    pool."""
    dive_mask = np.asarray(dive_mask, bool)
    n_dive = int(dive_mask.sum())
    flips_eff = min(int(flips), n_dive)
    n_rand_eff = int(n_random) if n_dive else 0
    ball_eff = max(1, min(int(ball), n_dive)) if n_dive else 1
    if random_only and n_dive == 0:
        return None
    dive_idx = np.flatnonzero(dive_mask) if n_dive \
        else np.zeros(1, np.int64)          # placeholder, never selected
    K = np.asarray(X).shape[-1]
    lb_row = np.zeros(K) if lb_row is None else np.asarray(lb_row,
                                                           np.float64)
    ub_row = np.ones(K) if ub_row is None else np.asarray(ub_row,
                                                          np.float64)
    return _build_pool(
        jnp.asarray(X), jnp.asarray(prob), jnp.asarray(dive_mask),
        jnp.asarray(np.asarray(integer_mask, bool)),
        jnp.asarray(dive_idx), jnp.asarray(lb_row), jnp.asarray(ub_row),
        jnp.uint32(int(seed) & 0xFFFFFFFF),
        jnp.uint32(int(round_index) & 0xFFFFFFFF),
        thresholds=tuple(float(t) for t in thresholds), flips=flips_eff,
        ball=ball_eff, n_random=n_rand_eff, random_only=bool(random_only))


@partial(jax.jit, static_argnames=("P", "S"))
def pool_verdict(obj_rows, pri_res, pri_rel, prob, live, feas_tol, *, P, S):
    """Fused feasibility screen + Eobjective over the (P*S,) solved
    rows -> a (2, P) verdict [expected objective; all-scenarios-feasible
    flag]. A row passes on EITHER the absolute or the relative primal
    residual (the engine-wide feasibility predicate); rows of
    zero-probability mesh pad scenarios (``live`` False) are exempt —
    they duplicate a real scenario and carry no objective weight. ONE
    ``np.asarray`` of the result is the round's single D2H."""
    feas = (pri_res <= feas_tol) | (pri_rel <= feas_tol)
    feas = feas.reshape(P, S) | ~live[None, :]
    eobj = obj_rows.reshape(P, S) @ prob
    return jnp.concatenate([eobj[None],
                            feas.all(axis=1)[None].astype(eobj.dtype)])
