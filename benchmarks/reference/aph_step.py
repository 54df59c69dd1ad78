"""Plain reference for the APH cell: ONE iteration of asynchronous
projective hedging's outer mathematics (Algorithm 2 of the APH paper,
as mpi-sppy's ``mpisppy/opt/aph.py`` runs it) in numpy float64, for a
two-stage problem. Imports nothing of the program: it is given the
state the engine held BEFORE the iteration (the scenarios' nonant
values, W, z, y, the probabilities, rho, who was dispatched last and
when) and returns what the iteration must leave behind, the dispatch
selection included. The scenario solves are not its business
(``scenario_lp.py`` holds those to HiGHS).

Upstream, line for line (SURVEY section 3.4):
  Update_y (``aph.py:157-186``)       y_s = W_s + rho (x_s - z_s) for the
                                      scenarios dispatched last pass; the
                                      others keep theirs; y = 0 through
                                      iteration 1
  FirstReduce (``:393-407``)          xbar, ybar: probability-weighted means
  side gig (``:269-316``)             u = x - xbar, v = ybar,
                                      tau = sum p (|u|^2 + |v|^2 / gamma),
                                      phi = sum p <z - x, W - y>
  Update_theta_zw (``:451-486``)      theta = nu phi / tau if tau > 0 and
                                      phi > 0 else 0; W += theta u;
                                      z += theta ybar / gamma (z := xbar at
                                      iteration 1)
  Compute_Convergence (``:497-523``)  |u|_p / |W|_p + |v|_p / |z|_p
  _dispatch_list (``:592-640``)       the ceil(frac S) scenarios of most
                                      negative post-step phi_s, ascending;
                                      the shortfall filled by the least
                                      recently dispatched; iteration 1
                                      dispatches everyone

Departures from upstream, each the deployment's and stated in the
configuration: no listener thread and no ``async_frac_needed`` (every
reduction sees every scenario's current value: the share needed is 1
by construction); ties in either pool go to the lower scenario index
(upstream leaves them to Python's sort of (phi, name) pairs);
zero-probability pad rows (``S_real`` onwards) are never dispatched.
"""

import numpy as np


def select(phis, last_dispatch, scnt, S_real=None):
    """The dispatch mask: of the first ``S_real`` rows, the ``scnt``
    with the most negative ``phis`` (ascending), then the least recently
    dispatched; the index breaks every tie."""
    phis = np.asarray(phis, float)
    S = phis.shape[0]
    S_real = S if S_real is None else int(S_real)
    mask = np.zeros(S, bool)
    if scnt >= S_real:
        mask[:S_real] = True
        return mask
    idx = np.arange(S_real)
    neg = phis[:S_real] < 0
    # one ascending order over (pool, rank in the pool, index)
    rank = np.where(neg, phis[:S_real],
                    np.asarray(last_dispatch, float)[:S_real])
    order = np.lexsort((idx, rank, ~neg))
    mask[order[:scnt]] = True
    return mask


def aph_step(xn, W, z, y, prob, rho, dispatched, last_dispatch, nu,
             gamma, it, frac, S_real=None):
    """One APH iteration from the state before it. ``xn``, ``W``, ``z``,
    ``y``: (S, K); ``prob``: (S,); ``rho``: (S, K) or a scalar;
    ``dispatched``: (S,) bool, the rows solved by the last pass;
    ``last_dispatch``: (S,) iteration stamps. Returns a dict: ``y``,
    ``xbar``, ``ybar`` (K,), ``u``, ``tau``, ``phi``, ``theta``, ``W``,
    ``z``, ``conv``, ``phis`` (post-step, per scenario) and ``mask``."""
    xn, W, z, y = (np.asarray(a, float) for a in (xn, W, z, y))
    prob = np.asarray(prob, float)
    if it > 1:
        fresh = W + np.asarray(rho, float) * (xn - z)
        y = np.where(np.asarray(dispatched, bool)[:, None], fresh, y)
    xbar = prob @ xn / prob.sum()
    ybar = prob @ y / prob.sum()
    u = xn - xbar
    pusq = float(prob @ (u * u).sum(axis=1))
    pvsq = float(prob.sum() * (ybar * ybar).sum())
    tau = pusq + pvsq / gamma
    phi = float(prob @ ((z - xn) * (W - y)).sum(axis=1))
    theta = nu * phi / tau if (tau > 0 and phi > 0) else 0.0
    W_new = W + theta * u
    z_new = np.broadcast_to(xbar, z.shape).copy() if it == 1 \
        else z + theta * ybar / gamma
    pwsq = float(prob @ (W_new * W_new).sum(axis=1))
    pzsq = float(prob @ (z_new * z_new).sum(axis=1))
    conv = np.sqrt(pusq) / np.sqrt(pwsq) + np.sqrt(pvsq) / np.sqrt(pzsq) \
        if (pwsq > 0 and pzsq > 0) else float("inf")
    phis = prob * ((z_new - xn) * (W_new - y)).sum(axis=1)
    n_real = xn.shape[0] if S_real is None else int(S_real)
    scnt = max(1, int(np.ceil(n_real * (1.0 if it == 1 else frac))))
    return {"y": y, "xbar": xbar, "ybar": ybar, "u": u, "tau": tau,
            "phi": phi, "theta": theta, "W": W_new, "z": z_new,
            "conv": float(conv), "phis": phis,
            "mask": select(phis, last_dispatch, scnt, S_real)}
