"""Plain reference for the serve cell: the extensive form of the
3-scenario farmer (Birge & Louveaux, Introduction to Stochastic
Programming, section 1.1; mpi-sppy ``examples/farmer``), written out as
one LP and solved by HiGHS. The data is the book's, typed here: nothing
is imported from the program.
"""

import numpy as np
from scipy.optimize import linprog

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


def ef_optimum(planting_cost=PLANTING_COST):
    """Optimal expected cost (min convention; profit is its negative)
    of the equiprobable 3-scenario farmer with the given first-stage
    cost vector."""
    S, C = YIELDS.shape
    nv = C + S * 3 * C          # acres | per scenario: sub, super, buy
    c = np.zeros(nv)
    c[:C] = planting_cost
    A, b = [], []
    row = np.zeros(nv)
    row[:C] = 1.0
    A.append(row)
    b.append(ACRES)
    bounds = [(0.0, ACRES)] * C
    for s in range(S):
        o = C + s * 3 * C
        sub, sup, buy = o, o + C, o + 2 * C
        c[sub:sub + C] = -SUB_PRICE / S
        c[sup:sup + C] = -SUPER_PRICE / S
        c[buy:buy + C] = BUY_PRICE / S
        for k in range(C):
            # feed: y a + buy - sub - super >= feed
            r = np.zeros(nv)
            r[k], r[buy + k], r[sub + k], r[sup + k] = \
                -YIELDS[s, k], -1.0, 1.0, 1.0
            A.append(r)
            b.append(-FEED[k])
            # sold: sub + super - y a <= 0
            r = np.zeros(nv)
            r[k], r[sub + k], r[sup + k] = -YIELDS[s, k], 1.0, 1.0
            A.append(r)
            b.append(0.0)
        bounds += [(0.0, q) for q in QUOTA] + [(0.0, None)] * (2 * C)
    res = linprog(c, A_ub=np.array(A), b_ub=np.array(b), bounds=bounds,
                  method="highs")
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the farmer EF: "
                           f"{res.message}")
    return float(res.fun)
