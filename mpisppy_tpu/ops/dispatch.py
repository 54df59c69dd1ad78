"""Device-side φ-dispatch for APH (doc/aph.md).

The reference's APH worker re-ranks its scenario pool on the host every
iteration: most-negative post-step φ first, least-recently-dispatched
fill for the shortfall (ref. mpisppy/opt/aph.py:592-640 _dispatch_list).
``core/aph.py`` kept that as host numpy over a full (S,) D2H pull of
phis — at S=100k that is an 800 KB blocking transfer plus an O(S log S)
host sort sitting on the critical path between the projective step and
the dispatched solves.

This module moves the whole selection on device:

- :func:`dispatch_select` — the jitted rank-based selection. Both pools
  and their tie-breaks are encoded as one lexicographic key and sorted
  with two stable argsorts (LSD radix), so the result is bit-identical
  to the host reference (``APH._dispatch_mask``) including tie order.
  The key is INTEGER (group, rank) — a float composite key such as
  ``last_dispatch * S + idx`` would silently collide once S·iter
  exceeds the 24-bit f32 mantissa, and the engine dtype is f32 whenever
  x64 is off (utils/runtime enables it only under ``--x64``).
- :func:`dispatch_gate` / :func:`scalar_gate` — the PR 13 packed-row
  discipline applied to APH's per-iteration host traffic: every scalar
  the host loop reads (τ, φ, θ, conv + the φ-histogram stats analyze
  renders) and the dispatch mask ride ONE device vector, read by ONE
  D2H transfer per iteration (``aph.gate_syncs``).
- the dispatch-bucket registry — serve-cache-style fingerprints over
  the (n_chunks, chunk, S, K) shapes a partial-dispatch solve compiles
  for, so ``dispatch.bucket.compile`` counts exactly the bucket
  transitions and steady-state iterations are compile-free
  (``dispatch.bucket.cache_hit``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..ckpt.bundle import config_fingerprint


@partial(jax.jit, static_argnames=("scnt", "S_real"))
def dispatch_select(phis, last_dispatch, scnt: int, S_real: int):
    """Device twin of ``APH._dispatch_mask`` for the partial case
    (``scnt < S_real``): the ``scnt`` most-negative-φ scenarios, then
    least-recently-dispatched fill, as a boolean (S,) mask.

    Selection = take the first ``scnt`` rows of the ascending
    lexicographic order of (group, rank, index) where
      group 0: real rows with φ < 0, ranked by ascending φ;
      group 1: remaining real rows, ranked by ``last_dispatch``
               (oldest first — the fill pool);
      group 2: zero-probability mesh pad rows (never dispatched).
    Two stable argsorts implement the radix: sort by the secondary
    rank, then stably by group; stability makes the index the final
    tie-break, matching the host reference's stable fill sort."""
    S = phis.shape[0]
    idx = jnp.arange(S, dtype=jnp.int32)
    real = idx < S_real
    neg = (phis < 0) & real
    group = jnp.where(neg, 0, jnp.where(real, 1, 2)).astype(jnp.int32)
    # ascending-φ rank within the negative pool (inverse permutation of
    # a stable argsort — non-pool rows push to the end via +inf)
    p = jnp.argsort(jnp.where(neg, phis, jnp.inf), stable=True)
    phi_rank = jnp.zeros(S, jnp.int32).at[p].set(idx)
    sec = jnp.where(neg, phi_rank, last_dispatch.astype(jnp.int32))
    perm1 = jnp.argsort(sec, stable=True)
    order = perm1[jnp.argsort(group[perm1], stable=True)]
    mask = jnp.zeros(S, bool).at[order[:scnt]].set(True)
    return mask


def _phi_stats(phis, S_real: int):
    """φ-histogram row for the gate: (min, max, negative count) over
    the real rows (pad rows carry probability 0 ⇒ φ ≡ 0 and would
    pollute max/count)."""
    pr = phis[:S_real]
    return jnp.stack([jnp.min(pr), jnp.max(pr),
                      jnp.sum(pr < 0).astype(pr.dtype)])


@partial(jax.jit, static_argnames=("scnt", "S_real"))
def dispatch_gate(tau, phi, theta, conv, phis, last_dispatch,
                  scnt: int, S_real: int):
    """One packed device row for APH's per-iteration host read:
    ``[τ, φ, θ, conv, φ_min, φ_max, φ_neg_count] ++ mask`` — the
    projective-step scalars, the φ stats, and the dispatch selection,
    concatenated so the host loop syncs exactly once (the PR 13
    ``(3,)``-packed-stats discipline, scaled up)."""
    mask = dispatch_select(phis, last_dispatch, scnt=scnt, S_real=S_real)
    head = jnp.concatenate([jnp.stack([tau, phi, theta, conv]),
                            _phi_stats(phis, S_real)])
    return jnp.concatenate([head, mask.astype(head.dtype)])


@partial(jax.jit, static_argnames=("S_real",))
def scalar_gate(tau, phi, theta, conv, phis, S_real: int):
    """The full-dispatch twin of :func:`dispatch_gate`: every real row
    dispatches, so only the scalar head ships — no selection runs and
    the trajectory stays bit-identical to the pre-dispatch engine."""
    return jnp.concatenate([jnp.stack([tau, phi, theta, conv]),
                            _phi_stats(phis, S_real)])


GATE_HEAD = 7   # scalar head width of both gate spellings


# dispatch-layout row ops. A dispatch pass launches ONE program each
# way around its chunk solves (constant shapes per chunk COUNT, keyed by
# the bucket registry below): ``gather_chunks`` on the way in,
# ``place_chunks`` on the way back. ``gather_rows`` / ``scatter_rows``
# are the same expressions for one field, the spelling the many-field
# programs are checked against. Ids may repeat trailing ids (the
# chunk-pad convention); duplicates carry bit-identical values, so the
# outcome does not depend on which of them lands.

@jax.jit
def gather_rows(full, idx):
    return full[idx]


@jax.jit
def gather_chunks(fields, ids):
    """Every chunk's rows of every field in one program: ``fields`` a
    tuple of (S, ·) arrays, ``ids`` the chunks' scenario ids stacked
    (n_chunks, chunk), an OPERAND. Returns a tuple over chunks of tuples
    over fields, which Python unpacks with no further launch."""
    return tuple(tuple(f[ids[ci]] for f in fields)
                 for ci in range(ids.shape[0]))


def _inverse_index(S, idx):
    """Where row s of an (S, ·) array comes from in the placed rows (0
    where it does not), and whether it does: the only scatters of a
    placement, each (S,) wide."""
    pos = jnp.zeros(S, jnp.int32).at[idx].set(
        jnp.arange(idx.shape[0], dtype=jnp.int32))
    hit = jnp.zeros(S, bool).at[idx].set(True)
    return pos, hit


def _place(full, rows, pos, hit):
    S = full.shape[0]
    return jnp.where(hit.reshape((S,) + (1,) * (full.ndim - 1)),
                     rows[pos], full)


@jax.jit
def scatter_rows(full, idx, rows):
    """``full`` with ``rows`` at ``idx``, PLACED by a gather through
    the inverse index and a row select, never a wide scatter: at UC
    width the v5e compiler refuses ``full.at[idx].set(rows)`` on the
    emulated-float64 (256, 26,016) store (the scatter's row window asks
    for 20.7 MB of scoped VMEM against a limit of 16). Only the (S,)
    inverse index and hit mask are scattered; every row of ``full`` is
    rewritten, 53 MB at that width."""
    return _place(full, rows, *_inverse_index(full.shape[0], idx))


@jax.jit
def place_chunks(fulls, ids, rows):
    """A dispatch pass's whole way back in one program: ``fulls`` a
    tuple of (S, ·) arrays, ``rows`` per field the tuple of its solved
    chunks' rows (concatenated here), ``ids`` as ``gather_chunks``
    takes them. The inverse index and the hit mask are built ONCE and
    every field is placed as ``scatter_rows`` places one. Nothing is
    donated: the engine's callers may hold any of ``fulls``
    (benchmarks/drivers/aph_hot keeps x, yA, yB and each pass's
    pri_rel), and the store's flowed factor never enters.

    The fields go ONE AFTER ANOTHER, widest first, each tied to the one
    before by an ``optimization_barrier``: left free, the v5e compiler
    interleaves all fifteen, their float64 halves outgrow the on-chip
    memory a single field's fit in, and the program takes 4.5 ms at UC
    width where fifteen programs took 2.0 between them; in this order
    it takes 3.0 (chip microbenchmark, PR 35; the rest is the halves'
    recombination, which the compiler keeps for the program's end)."""
    pos, hit = _inverse_index(fulls[0].shape[0], ids.reshape(-1))
    out = [None] * len(fulls)
    prev = None
    for k in sorted(range(len(fulls)), key=lambda k: -fulls[k].size):
        full, parts = fulls[k], rows[k]
        if prev is not None:
            (full, parts), out[prev] = lax.optimization_barrier(
                ((full, parts), out[prev]))
        out[k] = _place(full, jnp.concatenate(parts), pos, hit)
        prev = k
    return tuple(out)


# serve-cache-style shape-bucket registry (module-level, process-global
# like the jit cache it mirrors): a partial-dispatch pass compiles its
# scatter-back programs per padded dispatch width — fingerprint the
# shape tuple so a wheel pays one compile per bucket TRANSITION and the
# counters prove it (``dispatch.bucket.compile`` vs ``.cache_hit``).
_BUCKET_REGISTRY: dict = {}


def bucket_fingerprint(fields: dict) -> str:
    """Stable 16-hex shape-bucket id (same hashing as serve/cache and
    checkpoint fingerprints — ckpt/bundle.config_fingerprint)."""
    return config_fingerprint(fields)


def bucket_registry():
    """Read-only view for tests/telemetry."""
    return dict(_BUCKET_REGISTRY)


def register_bucket(fields: dict) -> bool:
    """Book one dispatch-shape bucket use: first sighting of a
    fingerprint is a compile (new scatter-back shapes reach XLA),
    repeats are cache hits. Returns whether this was the first
    sighting."""
    fp = bucket_fingerprint(fields)
    new = fp not in _BUCKET_REGISTRY
    if new:
        _BUCKET_REGISTRY[fp] = {"fields": dict(fields), "hits": 0}
        obs.counter_add("dispatch.bucket.compile")
    else:
        _BUCKET_REGISTRY[fp]["hits"] += 1
        obs.counter_add("dispatch.bucket.cache_hit")
    return new
