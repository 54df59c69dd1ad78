"""Plain reference for a wheel's BOUNDS: what a Lagrangian outer-bound
spoke and an x-hat inner-bound spoke may publish, recomputed by HiGHS
on the host from the instance's data alone (sparse A, vectors, the
nonant index). Imports nothing of the program: it is never given a
factor, a scale, a dual or a packed block the program made.

 (i)   ``lagrangian_value``   the exact LP value of one scenario's
       ``f_s(x) + W_s . x_nonant`` subproblem. A certified scenario
       bound may lie under it, never above it.
 (ii)  ``recourse_value``     the exact LP value of one scenario with
       the pinned nonants fixed at x-hat, and whether it is feasible.
       A published incumbent's scenario value may lie above it, never
       under it.
 (iii) ``w_dual_feasible_err`` / ``w_is_dual_feasible``: a Lagrangian
       bound is an outer bound only where ``sum_s p_s W_s = 0`` slot by
       slot (two-stage: one node).
 (iv)  ``extensive_form``     z* of a TOY instance by
       ``scipy.optimize.milp`` (the CPU tests' ground truth; the
       deployment's extensive form is far out of HiGHS's reach).
"""

import numpy as np
from scipy.optimize import Bounds, LinearConstraint, milp
from scipy.sparse import block_diag, csr_matrix, hstack, vstack


def sparse(A):
    return A if isinstance(A, csr_matrix) else csr_matrix(np.asarray(A))


def _lp(A, c, l, u, lb, ub, integrality=None):
    f = lambda v: np.asarray(v, float)
    return milp(c=f(c), constraints=LinearConstraint(A, f(l), f(u)),
                bounds=Bounds(f(lb), f(ub)), integrality=integrality,
                options={"presolve": True})


def lagrangian_value(A, c, c0, l, u, lb, ub, W_s, nonant_idx):
    """min (c + W_s on the nonant columns) . x + c0 over
    l <= A x <= u, lb <= x <= ub (the LP relaxation, as the spoke's
    device solve relaxes integrality). Returns the optimal value."""
    q = np.asarray(c, float).copy()
    q[np.asarray(nonant_idx)] += np.asarray(W_s, float)
    res = _lp(A, q, l, u, lb, ub)
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the Lagrangian LP: "
                           f"{res.status} {res.message}")
    return float(res.fun) + float(c0)


def recourse_value(A, c, c0, l, u, lb, ub, xhat, pin_idx):
    """(value, feasible) of one scenario with columns ``pin_idx`` fixed
    at ``xhat`` (same length) and every other column free in its box:
    the cheapest recourse to that first-stage plan. ``feasible`` False
    (value +inf) where HiGHS proves there is none."""
    lb = np.asarray(lb, float).copy()
    ub = np.asarray(ub, float).copy()
    pin = np.asarray(pin_idx)
    lb[pin] = ub[pin] = np.asarray(xhat, float)
    res = _lp(A, c, l, u, lb, ub)
    if res.status == 2:                     # infeasible
        return np.inf, False
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the recourse LP: "
                           f"{res.status} {res.message}")
    return float(res.fun) + float(c0), True


def w_dual_feasible_err(W, prob):
    """max over the slots of |sum_s p_s W_s|, as a share of the largest
    |W| entry (or 1): 0 on the dual-feasible manifold."""
    W, prob = np.asarray(W, float), np.asarray(prob, float)
    resid = np.abs(prob @ W) / prob.sum()
    return float(resid.max() / max(1.0, np.abs(W).max()))


def w_is_dual_feasible(W, prob, tol=1e-9):
    return w_dual_feasible_err(W, prob) <= tol


def extensive_form(A, c, c0, l, u, lb, ub, prob, nonant_idx,
                   integer=None):
    """z* of the two-stage extensive form at TOY size: S copies of the
    scenario block, the nonant columns of every copy tied to copy 0's.
    ``c, l, u, lb, ub`` are (S, .), ``A`` is the shared (m, n) matrix;
    ``integer`` (n,) bool turns integrality on for those columns (None:
    the LP relaxation)."""
    c, prob = np.asarray(c, float), np.asarray(prob, float)
    S, n = c.shape
    idx = np.asarray(nonant_idx)
    K = idx.size
    A = sparse(A)
    blocks = block_diag([A] * S, format="csr")
    pick = csr_matrix((np.ones(K), (np.arange(K), idx)), shape=(K, n))
    ties = vstack([hstack([pick] + [csr_matrix((K, n))] * (s - 1)
                          + [-pick] + [csr_matrix((K, n))] * (S - 1 - s))
                   for s in range(1, S)], format="csr") if S > 1 \
        else csr_matrix((0, n * S))
    cons = vstack([blocks, ties], format="csr")
    lo = np.concatenate([np.asarray(l, float).ravel(),
                         np.zeros(ties.shape[0])])
    hi = np.concatenate([np.asarray(u, float).ravel(),
                         np.zeros(ties.shape[0])])
    integ = None if integer is None else np.tile(
        np.asarray(integer, bool).astype(int), S)
    res = _lp(cons, (prob[:, None] * c).ravel(), lo, hi,
              np.asarray(lb, float).ravel(), np.asarray(ub, float).ravel(),
              integrality=integ)
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the extensive form: "
                           f"{res.status} {res.message}")
    return float(res.fun) + float(prob @ np.asarray(c0, float))
