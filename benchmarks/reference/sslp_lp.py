"""Plain reference for the SSLP cells: SIPLIB's stochastic server
location problem (Ntaimo & Sen, J. Global Optim. 2005; instances
``sslp_<m>_<n>_<S>``: m server sites, n clients, S scenarios) written
down from the paper's formulation, straight from the instance's
numbers, in numpy / scipy sparse, and solved by HiGHS:

    min  sum_j c_j x_j - sum_ij q_ij y_ij + sum_j q_j0 y_j0
    s.t. sum_j y_ij = h_i(w)                for every client i
         sum_i d_ij y_ij - y_j0 <= u x_j    for every server j
         sum_j x_j <= v
         0 <= x, y <= 1 (LP relaxation, as the device loop), y_j0 >= 0

Columns: x_j (m), then y_ij server by server (column m + j n + i), then
y_j0 (m). Rows: the n assignment rows, the m capacity rows, the budget
row. Imports nothing of the program: it never sees ``batch.A``, a
factor, a scale or a packed block. The instance's numbers are seeded
draws in the SIPLIB generator's ranges (``instance``; the repo ships
no .dat reader, ``benchmarks/configs/sslp_10_50_df32.json`` lists them
under ``assumed``), made here by the rule the configuration states, so
that a program that built another problem disagrees with this file
entry for entry.
"""

import numpy as np
from scipy.optimize import Bounds, LinearConstraint, milp
from scipy.sparse import csr_matrix, hstack, identity, kron, vstack

# consensus and primal_violation: for the driver, beside this file's own
from scenario_lp import consensus, primal_violation, solve_lp  # noqa: F401


def instance(num_servers, num_clients, base_seed=1, capacity=188.0,
             server_budget=None, overflow_penalty=1000.0):
    """The scenario-independent numbers: c_j ~ U[40, 80]; q_ij = d_ij
    ~ U[0, 25] (one unit of revenue per unit of demand), as an (m, n)
    array; q_j0, u, v. One ``RandomState(base_seed)`` stream, drawn in
    the order c, (a client-demand vector the published data does not
    use), q."""
    rng = np.random.RandomState(base_seed)
    c = rng.uniform(40.0, 80.0, size=num_servers)
    rng.uniform(1.0, 10.0, size=num_clients)
    q = rng.uniform(0.0, 25.0, size=(num_servers, num_clients))
    v = num_servers if server_budget is None else server_budget
    return {"m": int(num_servers), "n": int(num_clients), "c": c, "q": q,
            "d": q, "q0": float(overflow_penalty), "u": float(capacity),
            "v": float(v)}


def presence(scen, num_clients, prob=0.5):
    """h(w) of scenario number ``scen``: each client present with
    probability ``prob``, from ``RandomState(1000 + scen)``; a draw with
    nobody present gets one client."""
    rng = np.random.RandomState(1000 + int(scen))
    h = (rng.rand(num_clients) < prob).astype(np.float64)
    if not h.any():
        h[rng.randint(num_clients)] = 1.0
    return h


def matrices(inst):
    """(A, c, lb, ub, l0, u0): the one constraint matrix (61 x 520 for
    sslp_10_50, scipy csr), the cost, the column box, and the rows'
    bounds with the assignment rows' rhs left at 0 (``rows`` fills
    them in)."""
    m, n = inst["m"], inst["n"]
    Im = identity(m, format="csr")
    assign = hstack([csr_matrix((n, m)), kron(np.ones((1, m)), identity(n)),
                     csr_matrix((n, m))])
    # row j: d_j. over server j's block of y, -u on x_j, -1 on y_j0
    dem = csr_matrix((inst["d"].reshape(-1),
                      (np.repeat(np.arange(m), n), np.arange(m * n))),
                     shape=(m, m * n))
    cap = hstack([-inst["u"] * Im, dem, -Im])
    budget = csr_matrix(np.concatenate([np.ones(m), np.zeros(m * n + m)]))
    A = vstack([assign, cap, budget]).tocsr()
    c = np.concatenate([inst["c"], -inst["q"].reshape(-1),
                        np.full(m, inst["q0"])])
    lb = np.zeros(m + m * n + m)
    ub = np.concatenate([np.ones(m + m * n), np.full(m, np.inf)])
    l0 = np.concatenate([np.zeros(n), np.full(m, -np.inf), [-np.inf]])
    u0 = np.concatenate([np.zeros(n), np.zeros(m), [inst["v"]]])
    return A, c, lb, ub, l0, u0


def rows(inst, h, l0, u0):
    """One scenario's row bounds: h in the assignment rows."""
    l, u = l0.copy(), u0.copy()
    l[:inst["n"]] = u[:inst["n"]] = h
    return l, u


def scenario_lps(inst, hs):
    """The LP-relaxed optimal objective of every scenario in ``hs``
    (S, n), by HiGHS."""
    A, c, lb, ub, l0, u0 = matrices(inst)
    return np.array([solve_lp(A, c, 0.0, *rows(inst, h, l0, u0), lb, ub)
                     for h in hs])


def wait_and_see(objs, prob):
    """sum_s p_s LP_s: what the engine's trivial bound certifies from
    below."""
    return float(np.asarray(prob, float) @ np.asarray(objs, float))


def extensive_form(inst, hs, prob):
    """The LP-relaxed extensive form (one x for all scenarios) by
    HiGHS, at test size: (objective, x)."""
    A, c, lb, ub, l0, u0 = matrices(inst)
    m, S = inst["m"], len(hs)
    A = A.tocsc()
    Ax, Ay = A[:, :m], A[:, m:]
    big = hstack([vstack([Ax] * S), kron(identity(S), Ay)]).tocsr()
    # the budget row is first-stage only: one copy would do, S do no harm
    lu = [rows(inst, h, l0, u0) for h in hs]
    prob = np.asarray(prob, float)
    cost = np.concatenate([c[:m]] + [p * c[m:] for p in prob])
    res = milp(c=cost,
               constraints=LinearConstraint(
                   big, np.concatenate([l for l, _ in lu]),
                   np.concatenate([u for _, u in lu])),
               bounds=Bounds(np.concatenate([lb[:m]] + [lb[m:]] * S),
                             np.concatenate([ub[:m]] + [ub[m:]] * S)),
               options={"presolve": True})
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the extensive form: "
                           f"{res.status} {res.message}")
    return float(res.fun), res.x[:m]
