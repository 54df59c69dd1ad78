"""Plain reference for the FWPH cell: Frank-Wolfe progressive hedging
(Boland, Christiansen, Dandurand, Eberhard, Linderoth, Luedtke,
Oliveira 2018, Algorithms 2 and 3; mpi-sppy ``mpisppy/fwph/fwph.py``)
in numpy float64 and scipy / HiGHS, for a two-stage problem. Imports
nothing of the program: it is given the instance's data (sparse A,
vectors, the nonant index) and the state the engine held, never a
factor, a scale, a dual or a packed block the program made.

 (i)   ``simplex_qp_reference``  one scenario's weight QP
           min_a  b.a + w.(aG) + sum_k rho_k/2 (aG - xbar)_k^2,
           a >= 0, sum a = 1
       by SLSQP on the C-dimensional reduced problem and an active-set
       polish on its support (no projected gradient, no sort). Returns
       the minimiser, its value and its own KKT residual: the
       Frank-Wolfe gap  a.grad - min_i grad_i  over |value|, which
       bounds value - optimum from above, so the reference certifies
       itself.
 (ii)  ``sdm_pass``              one pass of the simplicial
       decomposition (Algorithm 2's body) from gathered arrays: w_t,
       the two linearizations, Gamma, the slot the new column lands in.
 (iii) ``outer_update``          x-bar, the second moment, W and conv
       from the QP iterate (Algorithm 3, lines 9-11).
 (iv)  ``lagrangian_value``      the exact LP value of one scenario's
       ``(c + w_t on the nonant columns) . x`` by HiGHS (a COPY of
       ``wheel_bounds.lagrangian_value``, so that the two cells'
       references do not move together).
 (v)   ``fwph_run`` / ``extensive_form``  for TOY instances: a whole
       FWPH run whose linearized subproblem HiGHS solves exactly, and
       the extensive form by ``scipy.optimize.milp``.

Where the ENGINE (``mpisppy_tpu/core/fwph.py``) departs from the paper
and from upstream, and this file follows the engine so that the two can
be compared number for number:
  - a FIXED pool of C slots, every slot a copy of the iter-0 solution
    at the start, overwritten round-robin (slot = columns written mod
    C), where upstream's column set grows by one a pass;
  - the linearized subproblem is the LP RELAXATION (the engine solves
    it by ADMM; here HiGHS), where upstream solves the MIP: the vertex
    is a vertex of the relaxed polytope, and the bound is the
    Lagrangian bound of the relaxation. ``fwph_run(integer=...)`` turns
    the MIP on, as upstream has it;
  - the bound is read at the FIRST pass of an outer iteration, where
    sum_s p_s w_s = 0 holds (the engine builds it from its dual
    vectors; here it is the exact optimal value).
"""

import numpy as np
from scipy.optimize import Bounds, LinearConstraint, milp, minimize
from scipy.sparse import block_diag, csr_matrix, hstack, vstack


def sparse(A):
    return A if isinstance(A, csr_matrix) else csr_matrix(np.asarray(A))


# ---------------------------------------------------------------- (i)
def qp_terms(G, b, w, rho, xbar):
    """(H, g) of one scenario: phi(a) = a.H.a / 2 + g.a + a constant."""
    G, rho = np.asarray(G, float), np.asarray(rho, float)
    g = np.asarray(b, float) + G @ (
        np.asarray(w, float) - rho * np.asarray(xbar, float))
    return (G * rho) @ G.T, g


def qp_value(G, b, w, rho, xbar, a):
    """phi(a) of one scenario, from the definition (not through H)."""
    xn = np.asarray(a, float) @ np.asarray(G, float)
    d = xn - np.asarray(xbar, float)
    return float(np.asarray(b, float) @ a + np.asarray(w, float) @ xn
                 + 0.5 * np.asarray(rho, float) @ (d * d))


def _fw_gap(H, g, a):
    grad = H @ a + g
    return float(a @ grad - grad.min())


def _support_polish(H, g, a, tol=1e-11):
    """The equality-constrained minimiser on a's support (least
    squares: H may be singular), kept if it is feasible."""
    F = np.flatnonzero(a > tol)
    kkt = np.block([[H[np.ix_(F, F)], np.ones((F.size, 1))],
                    [np.ones((1, F.size)), np.zeros((1, 1))]])
    sol = np.linalg.lstsq(kkt, np.concatenate([-g[F], [1.0]]),
                          rcond=None)[0]
    out = np.zeros_like(a)
    out[F] = sol[:-1]
    return out if out.min() >= 0 and abs(out.sum() - 1) < 1e-12 else a


def simplex_qp_reference(G, b, w, rho, xbar):
    """One scenario's weight QP. ``G``: (C, K) nonant block of the
    pool; ``b``: (C,) base costs; ``w``, ``rho``, ``xbar``: (K,).
    Returns ``{"a", "value", "kkt"}``."""
    H, g = qp_terms(G, b, w, rho, xbar)
    C = g.size
    scale = max(np.abs(H).max(), np.abs(g).max(), 1e-300)
    Hs, gs = H / scale, g / scale
    fun = lambda a: 0.5 * a @ Hs @ a + gs @ a
    jac = lambda a: Hs @ a + gs
    cons = {"type": "eq", "fun": lambda a: a.sum() - 1.0,
            "jac": lambda a: np.ones(C)}
    best = None
    # the cheapest vertex and the barycentre as starts: a singular H
    # (a pool of equal columns) leaves SLSQP's quasi-Newton model flat
    starts = [np.eye(C)[np.argmin(0.5 * np.diag(Hs) + gs)],
              np.full(C, 1.0 / C)]
    for a0 in starts:
        res = minimize(fun, a0, jac=jac, bounds=[(0.0, 1.0)] * C,
                       constraints=[cons], method="SLSQP",
                       options={"ftol": 1e-16, "maxiter": 500})
        a = np.clip(res.x, 0.0, None)
        a /= a.sum()
        for cand in (a, _support_polish(Hs, gs, a)):
            gap = _fw_gap(Hs, gs, cand)
            if best is None or gap < best[0]:
                best = (gap, cand)
    gap, a = best
    value = qp_value(G, b, w, rho, xbar, a)
    return {"a": a, "value": value,
            "kkt": gap * scale / max(abs(value), 1e-300)}


# --------------------------------------------------------------- (ii)
def sdm_pass(W, rho, xbar, xn_t, a, base, c, c0, x_star, nonant_idx,
             prob, columns_written, n_slots):
    """One SDM pass from gathered arrays (S scenarios): ``W``, ``rho``,
    ``xbar``, ``xn_t``: (S, K); ``a``, ``base``: (S, C) weights and
    base costs c.column of the pool BEFORE this pass's column;
    ``x_star``: (S, n) the linearized subproblem's solution at w_t.
    Returns ``w_t``, ``lin_t``, ``lin_star`` (per scenario), ``gamma``
    = E[lin_t - lin_star], ``E_lin_t`` and the ``slot`` written."""
    f = lambda v: np.asarray(v, float)
    W, rho, xbar, xn_t, a, base, c, c0, x_star, prob = (
        f(v) for v in (W, rho, xbar, xn_t, a, base, c, c0, x_star, prob))
    idx = np.asarray(nonant_idx)
    w_t = W + rho * (xn_t - xbar)
    lin_t = (base * a).sum(axis=1) + c0 + (w_t * xn_t).sum(axis=1)
    lin_star = (c * x_star).sum(axis=1) + c0 \
        + (w_t * x_star[:, idx]).sum(axis=1)
    return {"w_t": w_t, "lin_t": lin_t, "lin_star": lin_star,
            "gamma": float(prob @ (lin_t - lin_star)),
            "E_lin_t": float(prob @ lin_t),
            "slot": int(columns_written) % int(n_slots)}


def w_manifold_err(w, prob):
    """max over the slots of |sum_s p_s w_s| over the largest |w| entry
    (two-stage: one node): 0 where a Lagrangian bound is an outer
    bound."""
    w, prob = np.asarray(w, float), np.asarray(prob, float)
    return float(np.abs(prob @ w / prob.sum()).max()
                 / max(np.abs(w).max(), 1e-300))


# -------------------------------------------------------------- (iii)
def outer_update(xn_t, prob, W, rho):
    """x-bar, E[x^2], the new W and conv (expected mean absolute
    deviation from x-bar) from the QP iterate ``xn_t`` (S, K)."""
    xn_t, prob = np.asarray(xn_t, float), np.asarray(prob, float)
    xbar = prob @ xn_t / prob.sum()
    xsqbar = prob @ (xn_t * xn_t) / prob.sum()
    W_new = np.asarray(W, float) + np.asarray(rho, float) * (xn_t - xbar)
    conv = float(prob @ np.abs(xn_t - xbar).sum(axis=1) / xn_t.shape[1])
    return {"xbar": xbar, "xsqbar": xsqbar, "W": W_new, "conv": conv}


# --------------------------------------------------------------- (iv)
def _lp(A, c, l, u, lb, ub, integrality=None):
    f = lambda v: np.asarray(v, float)
    return milp(c=f(c), constraints=LinearConstraint(A, f(l), f(u)),
                bounds=Bounds(f(lb), f(ub)), integrality=integrality,
                options={"presolve": True})


def lagrangian_solve(A, c, c0, l, u, lb, ub, w_s, nonant_idx,
                     integer=None):
    """(value, x) of min (c + w_s on the nonant columns) . x + c0 over
    l <= A x <= u, lb <= x <= ub: the LP relaxation, or the MIP where
    ``integer`` (n,) bool names integer columns."""
    q = np.asarray(c, float).copy()
    q[np.asarray(nonant_idx)] += np.asarray(w_s, float)
    integ = None if integer is None else np.asarray(integer, bool).astype(int)
    res = _lp(A, q, l, u, lb, ub, integrality=integ)
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the Lagrangian "
                           f"subproblem: {res.status} {res.message}")
    return float(res.fun) + float(c0), np.asarray(res.x, float)


def lagrangian_value(A, c, c0, l, u, lb, ub, w_s, nonant_idx):
    """The exact LP value: a certified scenario bound may lie under it,
    never above it."""
    return lagrangian_solve(A, c, c0, l, u, lb, ub, w_s, nonant_idx)[0]


# ---------------------------------------------------------------- (v)
def fwph_run(A, c, c0, l, u, lb, ub, prob, nonant_idx, rho, outer_iters,
             fw_iter_limit, n_slots, fw_conv_thresh=1e-4, integer=None):
    """A whole FWPH run at TOY size with every linearized subproblem
    solved exactly, under the engine's pool rule. ``c, l, u, lb, ub``:
    (S, .); ``A``: the shared (m, n) matrix; ``rho``: scalar or (K,).
    Returns ``{"bounds": [trivial, after iteration 1, ...], "conv",
    "xbar", "W"}`` (``bounds`` is the monotone published trail)."""
    f = lambda v: np.asarray(v, float)
    c, c0, l, u, lb, ub, prob = (f(v) for v in (c, c0, l, u, lb, ub, prob))
    A = sparse(A)
    idx = np.asarray(nonant_idx)
    S, K, C = c.shape[0], idx.size, int(n_slots)
    rho = np.broadcast_to(f(rho), (S, K))

    def solve_all(w):
        out = [lagrangian_solve(A, c[s], c0[s], l[s], u[s], lb[s], ub[s],
                                w[s], idx, integer) for s in range(S)]
        return np.array([v for v, _ in out]), np.stack([x for _, x in out])

    vals, x0 = solve_all(np.zeros((S, K)))
    columns = np.repeat(x0[:, None, :], C, axis=1)
    written = 0
    xn_t = x0[:, idx]
    xbar = prob @ xn_t / prob.sum()
    W = rho * (xn_t - xbar)
    a = np.full((S, C), 1.0 / C)
    bounds = [float(prob @ vals)]
    conv = None
    for _ in range(int(outer_iters)):
        for k in range(int(fw_iter_limit)):
            w_t = W + rho * (xn_t - xbar)
            vals, x_star = solve_all(w_t)
            if k == 0:
                bounds.append(max(bounds[-1], float(prob @ vals)))
            step = sdm_pass(W, rho, xbar, xn_t, a,
                            np.einsum("scn,sn->sc", columns, c), c, c0,
                            x_star, idx, prob, written, C)
            columns[:, step["slot"], :] = x_star
            written += 1
            G = columns[:, :, idx]
            base = np.einsum("scn,sn->sc", columns, c)
            for s in range(S):
                a[s] = simplex_qp_reference(G[s], base[s], W[s], rho[s],
                                            xbar)["a"]
            xn_t = np.einsum("sc,sck->sk", a, G)
            if abs(step["gamma"]) < fw_conv_thresh * max(
                    1.0, abs(step["E_lin_t"])):
                break
        up = outer_update(xn_t, prob, W, rho)
        xbar, W, conv = up["xbar"], up["W"], up["conv"]
    return {"bounds": bounds, "conv": conv, "xbar": xbar, "W": W}


def extensive_form(A, c, c0, l, u, lb, ub, prob, nonant_idx,
                   integer=None):
    """z* of the two-stage extensive form at TOY size: S copies of the
    scenario block, the nonant columns of every copy tied to copy 0's.
    ``integer`` (n,) bool turns integrality on for those columns (None:
    the LP relaxation)."""
    c, prob = np.asarray(c, float), np.asarray(prob, float)
    S, n = c.shape
    idx = np.asarray(nonant_idx)
    K = idx.size
    A = sparse(A)
    blocks = block_diag([A] * S, format="csr")
    pick = csr_matrix((np.ones(K), (np.arange(K), idx)), shape=(K, n))
    zero = csr_matrix((K, n))
    ties = vstack([hstack([pick] + [zero] * (s - 1) + [-pick]
                          + [zero] * (S - 1 - s))
                   for s in range(1, S)], format="csr") if S > 1 \
        else csr_matrix((0, n * S))
    cons = vstack([blocks, ties], format="csr")
    pad = np.zeros(ties.shape[0])
    integ = None if integer is None else np.tile(
        np.asarray(integer, bool).astype(int), S)
    res = _lp(cons, (prob[:, None] * c).ravel(),
              np.concatenate([np.asarray(l, float).ravel(), pad]),
              np.concatenate([np.asarray(u, float).ravel(), pad]),
              np.asarray(lb, float).ravel(), np.asarray(ub, float).ravel(),
              integrality=integ)
    if res.status != 0:
        raise RuntimeError(f"HiGHS did not solve the extensive form: "
                           f"{res.status} {res.message}")
    return float(res.fun) + float(prob @ np.asarray(c0, float))
