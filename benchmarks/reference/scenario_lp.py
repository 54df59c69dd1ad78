"""Plain reference for the UC cells: the same scenario subproblem the
device solves, handed to HiGHS on the host, and the consensus step
recomputed in numpy float64. Imports nothing of the program: it is
given the instance's data (sparse A, vectors), never a factor, a scale
or a packed block the program made.
"""

import numpy as np
from scipy.optimize import Bounds, LinearConstraint, milp
from scipy.sparse import csr_matrix


def sparse(A):
    return csr_matrix(np.asarray(A))


def solve_lp(A, c, c0, l, u, lb, ub):
    """min c.x + c0 s.t. l <= A x <= u, lb <= x <= ub, by HiGHS (the
    LP relaxation: the device loop relaxes integrality too). Returns
    the optimal objective."""
    res = milp(c=np.asarray(c, float),
               constraints=LinearConstraint(A, np.asarray(l, float),
                                            np.asarray(u, float)),
               bounds=Bounds(np.asarray(lb, float), np.asarray(ub, float)),
               options={"presolve": True})
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the reference LP: "
                           f"{res.status} {res.message}")
    return float(res.fun) + float(c0)


def primal_violation(A, x, l, u, lb, ub):
    """Per scenario row of ``x`` (S, n): the largest violation of
    l <= A x <= u and lb <= x <= ub, as a share of that row's largest
    |A x| entry (or 1): a plain float64 recomputation of what the
    program's residual gate guards. ``l, u`` are (S, m), ``lb, ub``
    (S, n)."""
    x = np.asarray(x, float)
    ax = np.asarray((A @ x.T).T)
    row = np.maximum(np.maximum(np.asarray(l, float) - ax,
                                ax - np.asarray(u, float)), 0.0).max(axis=1)
    col = np.maximum(np.maximum(np.asarray(lb, float) - x,
                                x - np.asarray(ub, float)), 0.0).max(axis=1)
    scale = np.maximum(1.0, np.abs(ax).max(axis=1))
    return np.maximum(row, col) / scale


def consensus(xn, prob):
    """(xbar, conv) of the nonant block ``xn`` (S, K) under ``prob``:
    the probability-weighted mean, and the PH convergence metric
    (expected mean absolute deviation from it)."""
    xn, prob = np.asarray(xn, float), np.asarray(prob, float)
    xbar = prob @ xn / prob.sum()
    conv = float(prob @ np.abs(xn - xbar).sum(axis=1) / xn.shape[1])
    return xbar, conv
