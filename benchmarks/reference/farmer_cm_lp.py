"""Plain reference for the wide farmer: Birge & Louveaux's farmer
(Introduction to Stochastic Programming, section 1.1) scaled the way
mpi-sppy's ``examples/farmer/farmer.py`` scales it, written down from
the book's numbers in numpy / scipy sparse and solved by HiGHS.

``crops_multiplier`` cm tiles the three crops cm times (3 cm crops,
500 cm acres). Scenario number s takes the yields of ``s % 3`` (below
average, average, above average), and from scenario 3 on adds
``RandomState(s).rand(3 cm)`` to them, one draw a crop in declaration
order: the upstream rule. The yields multiply the acreage INSIDE two
rows a crop, so every scenario has its own constraint matrix:

    min  c.a + buy.w - sub.p - super.q
    s.t. sum a <= 500 cm
         y_k a_k + w_k - p_k - q_k >= feed_k       (cattle feed)
         p_k + q_k - y_k a_k <= 0                  (cannot sell more)
         0 <= a <= 500 cm, 0 <= p <= quota, q, w >= 0

Columns: a (3 cm), p sub-quota sold, q super-quota sold, w purchased.
Rows: the acreage row, the 3 cm feed rows, the 3 cm selling rows
(193 x 384 at cm = 32). Imports nothing of the program: it never sees
``batch.A``, a scale or a factor; a program that built another problem
disagrees with this file entry for entry.
"""

import numpy as np
from scipy.optimize import Bounds, LinearConstraint, milp
from scipy.sparse import csr_matrix, hstack, identity, vstack

# consensus: for the driver, beside this file's own
from scenario_lp import consensus, solve_lp  # noqa: F401

YIELDS = np.array([[2.0, 2.4, 16.0],      # below average
                   [2.5, 3.0, 20.0],      # average
                   [3.0, 3.6, 24.0]])     # above average
PLANTING_COST = np.array([150.0, 230.0, 260.0])
QUOTA = np.array([100000.0, 100000.0, 6000.0])
SUB_PRICE = np.array([170.0, 150.0, 36.0])
SUPER_PRICE = np.array([0.0, 0.0, 10.0])
FEED = np.array([200.0, 240.0, 0.0])
BUY_PRICE = np.array([238.0, 210.0, 100000.0])
ACRES = 500.0


def yields(scen, cm):
    """The 3 cm yields of scenario number ``scen``."""
    y = np.tile(YIELDS[int(scen) % 3], cm)
    if int(scen) // 3:
        y = y + np.random.RandomState(int(scen)).rand(3 * cm)
    return y


def vectors(cm):
    """(c, lb, ub, l, u): what no scenario changes."""
    C = 3 * cm
    tile = lambda a: np.tile(a, cm)
    c = np.concatenate([tile(PLANTING_COST), -tile(SUB_PRICE),
                        -tile(SUPER_PRICE), tile(BUY_PRICE)])
    lb = np.zeros(4 * C)
    ub = np.concatenate([np.full(C, ACRES * cm), tile(QUOTA),
                         np.full(2 * C, np.inf)])
    l = np.concatenate([[-np.inf], tile(FEED), np.full(C, -np.inf)])
    u = np.concatenate([[ACRES * cm], np.full(C, np.inf), np.zeros(C)])
    return c, lb, ub, l, u


def matrix(y):
    """One scenario's (1 + 6 cm, 12 cm) matrix from its yields."""
    C = y.size
    I, Z = identity(C, format="csr"), csr_matrix((C, C))
    Y = csr_matrix((y, (np.arange(C), np.arange(C))), shape=(C, C))
    top = csr_matrix(np.concatenate([np.ones(C), np.zeros(3 * C)]))
    return vstack([top, hstack([Y, -I, -I, I]),
                   hstack([-Y, I, I, Z])]).tocsr()


def scenario_lps(scens, cm):
    """The optimal objective of every scenario in ``scens``, by HiGHS."""
    c, lb, ub, l, u = vectors(cm)
    return np.array([solve_lp(matrix(yields(s, cm)), c, 0.0, l, u, lb, ub)
                     for s in scens])


def primal_violation(scens, cm, x):
    """Per row of ``x`` (S, 12 cm), scenario ``scens[i]``'s: the
    largest violation of its OWN rows and of the column box, as a share
    of that scenario's largest |A x| entry (or 1)."""
    _c, lb, ub, l, u = vectors(cm)
    x = np.asarray(x, float)
    out = np.empty(len(scens))
    for i, s in enumerate(scens):
        ax = matrix(yields(s, cm)) @ x[i]
        row = np.maximum(np.maximum(l - ax, ax - u), 0.0).max()
        col = np.maximum(np.maximum(lb - x[i], x[i] - ub), 0.0).max()
        out[i] = max(row, col) / max(1.0, np.abs(ax).max())
    return out


def wait_and_see(objs, prob):
    """sum_s p_s LP_s: what the engine's trivial bound certifies from
    below."""
    return float(np.asarray(prob, float) @ np.asarray(objs, float))


def extensive_form(scens, cm, prob):
    """The extensive form (one acreage vector for all scenarios) by
    HiGHS, at test size: (objective, a)."""
    c, lb, ub, l, u = vectors(cm)
    C, S = 3 * cm, len(scens)
    prob = np.asarray(prob, float)
    mats = [matrix(yields(s, cm)).tocsc() for s in scens]
    first = vstack([A[:, :C] for A in mats])
    second = vstack([hstack([csr_matrix((A.shape[0], 3 * C * i)),
                             A[:, C:],
                             csr_matrix((A.shape[0], 3 * C * (S - 1 - i)))])
                     for i, A in enumerate(mats)])
    res = milp(c=np.concatenate([c[:C]] + [p * c[C:] for p in prob]),
               constraints=LinearConstraint(hstack([first, second]).tocsr(),
                                            np.tile(l, S), np.tile(u, S)),
               bounds=Bounds(np.concatenate([lb[:C]] + [lb[C:]] * S),
                             np.concatenate([ub[:C]] + [ub[C:]] * S)),
               options={"presolve": True})
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the extensive form: "
                           f"{res.status} {res.message}")
    return float(res.fun), res.x[:C]
