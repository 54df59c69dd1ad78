"""Batched simplex-constrained QP solver (FWPH's per-scenario weight QP).

FWPH maintains, per scenario, a convex-combination QP over previously
generated subproblem solutions ("columns"): the reference builds a Pyomo QP
with weight vars `a`, x = Σ a_j x_j links, and hands it to Gurobi
(ref. mpisppy/fwph/fwph.py:691-777 _initialize_QP_subproblems, :943-987
_set_QP_objective). Here the x variables are eliminated (x = aᵀX with X the
(C, n) column stack), leaving a C-dimensional QP over the probability
simplex per scenario:

    min_a  b·a + w·(aG) + (ρ/2)‖aG − x̄‖²    s.t. a ≥ 0, Σa = 1

with G = X[:, nonant] (C, K), b = X c the per-column base costs. C is a
small static pad (rolling column buffer), so the whole thing batches over
scenarios as (S, C) / (S, C, K) tensors and solves with accelerated
projected gradient, no host loop.

The three products over G — the Hessian ``G diag(ρ) Gᵀ``, the linear
term's ``G (w − ρ x̄)`` and ``xn = a G`` — go through
``qp_solver._batched_matvec`` / ``_batched_rmatvec``: a per-scenario
float64 block lowers as one multiply-and-sum fusion on the TPU, where a
batched float64 ``dot_general`` is a loop nest of eight-limb emulation
(doc/kernels.md §3d; doc/fwph.md has this program's own readings), and
as the library dot elsewhere. The projection keeps its sort: at C = 16
a trip with it and a trip with the O(C²) count form cost the same on
the chip (2.49 and 2.51 ms the 400; doc/fwph.md).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .qp_solver import _batched_matvec, _batched_rmatvec


@jax.named_scope("fwph.qp.project")
def project_simplex(v):
    """Batched Euclidean projection onto the probability simplex
    (Held et al.; sort-based, jit-friendly). v: (..., C)."""
    C = v.shape[-1]
    mu = jnp.sort(v, axis=-1)[..., ::-1]
    cssv = jnp.cumsum(mu, axis=-1) - 1.0
    rho_idx = jnp.arange(1, C + 1)
    cond = mu - cssv / rho_idx > 0
    k = jnp.sum(cond, axis=-1, keepdims=True)  # number of positive coords
    tau = jnp.take_along_axis(cssv, k - 1, axis=-1) / k
    return jnp.maximum(v - tau, 0.0)


@jax.named_scope("fwph.qp.hessian")
def _hessian(G, rho):
    """G diag(ρ) Gᵀ, (S, C, C): column j is the matvec of the weighted
    block with pool column j's own nonants."""
    Gr = G * rho[:, None, :]
    return jnp.stack([_batched_matvec(Gr, G[:, j, :])
                      for j in range(G.shape[1])], axis=-1)


@partial(jax.jit, static_argnames=("iters",))
def simplex_qp_solve(G, b, w, rho, xbar, a0, iters=300):
    """Solve the weight QP for every scenario.

    G: (S, C, K) column nonant blocks; b: (S, C) base costs; w: (S, K) dual
    weights; rho: (S, K); xbar: (S, K) prox center; a0: (S, C) warm start.
    Returns (a, xn) with xn = aG the QP-optimal nonant values.

    FISTA with a per-scenario Lipschitz bound L = ‖G diag(ρ) Gᵀ‖_F + sum
    of linear curvature; the objective is smooth so acceleration gives
    1/t² decay — plenty for the SDM's Γ tolerance.
    """
    # gradient: ∇ = b + G(w − ρ x̄) + G diag(ρ) Gᵀ a
    lin = b + _batched_matvec(G, w - rho * xbar)               # (S, C)
    H = _hessian(G, rho)                                       # (S, C, C)
    L = jnp.sqrt(jnp.sum(H * H, axis=(1, 2))) + 1e-12          # (S,)
    step = (1.0 / L)[:, None]

    def body(carry, _):
        a, y, t = carry
        grad = lin + _batched_matvec(H, y)
        a_new = project_simplex(y - step * grad)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = a_new + ((t - 1.0) / t_new) * (a_new - a)
        return (a_new, y_new, t_new), None

    with jax.named_scope("fwph.qp.fista"):
        (a, _, _), _ = jax.lax.scan(body, (a0, a0, jnp.ones((), a0.dtype)),
                                    None,
                                    length=iters)
    return a, _batched_rmatvec(G, a)


def qp_objective_value(G, b, w, rho, xbar, a):
    """φ(a) per scenario (for Γ calculations)."""
    xn = (a[:, None, :] @ G)[:, 0, :]
    return (jnp.sum(b * a, axis=-1) + jnp.sum(w * xn, axis=-1)
            + 0.5 * jnp.sum(rho * (xn - xbar) ** 2, axis=-1))
