"""SSLP: two-stage stochastic server location (Ntaimo & Sen).

Same problem class as the reference's sslp example (ref. examples/sslp/
sslp.py:18-110, which instantiates an abstract Pyomo model from
sslp_<m>_<n>_<s> .dat files): first stage opens servers (binary y_i, cost
c_i), second stage assigns present clients to open servers (x_ij) for
revenue r_ij, subject to server capacity u; client presence h_j(ξ) is the
stochastic element. Instances here are generated from a seeded RNG in the
published SSLP data ranges instead of .dat files, scalable via
(num_servers, num_clients).

The PUBLISHED formulation (SIPLIB's SSLP test set, Ntaimo & Sen 2005,
instances sslp_<m>_<n>_<S>: m server sites, n clients, S scenarios) is
behind creator kwargs whose defaults keep the reduced model above:

    min  sum_j c_j x_j - sum_ij q_ij y_ij + sum_j q_j0 y_j0
    s.t. sum_j x_j <= v                               ("ServerBudget")
         sum_i d_ij y_ij - y_j0 <= u x_j   for all j  ("ServerCapacity")
         sum_j y_ij = h_i(w)               for all i  ("ClientAssignment")
         x, y binary; y_j0 >= 0

``overflow`` adds the overflow columns y_j0 ("Overflow") at the
published penalty q_j0 = ``OVERFLOW_PENALTY``, ``server_budget`` the
first-stage row, ``demand_is_revenue``
sets d_ij = q_ij (one unit of revenue per unit of demand, as the
published data), ``capacity`` the servers' u. sslp_10_50 so built has
n = 10 + 500 + 10 = 520 columns and m = 50 + 10 + 1 = 61 rows, ONE
matrix for every scenario: h enters the assignment rows' rhs only
(``scenario_vector_patch``). doc/scenario_models.md has the command
line.
"""

from __future__ import annotations

import re

import numpy as np

from ..ir.model import Model
from ..ir.tree import two_stage_tree

# q_j0 of the published data: what a unit of overflow costs a server
OVERFLOW_PENALTY = 1000.0


def instance_data(num_servers=5, num_clients=25, base_seed=1):
    """Instance-level (scenario-independent) data, seeded like the SSLP
    generators: c_i ~ U[40,80], client demand d_j ~ U[1,10], revenue
    r_ij ~ U[0,25], capacity scaled so ~half the servers suffice."""
    rng = np.random.RandomState(base_seed)
    c = rng.uniform(40.0, 80.0, size=num_servers)
    d = rng.uniform(1.0, 10.0, size=num_clients)
    r = rng.uniform(0.0, 25.0, size=(num_servers, num_clients))
    u = 2.0 * d.sum() / num_servers
    return {"c": c, "d": d, "r": r, "u": u}


def client_presence(scennum, num_clients, presence_prob=0.5):
    """h_j(ξ) ~ Bernoulli(presence_prob), seeded per scenario (the SSLP
    uncertainty model: a client either shows up or doesn't)."""
    rng = np.random.RandomState(1000 + scennum)
    h = (rng.rand(num_clients) < presence_prob).astype(np.float64)
    if not h.any():
        h[rng.randint(num_clients)] = 1.0
    return h


def scenario_creator(scenario_name, num_servers=5, num_clients=25,
                     presence_prob=0.5, base_seed=1, overflow=False,
                     server_budget=None, capacity=None,
                     demand_is_revenue=False) -> Model:
    scennum = int(re.search(r"(\d+)$", scenario_name).group(1))
    data = instance_data(num_servers, num_clients, base_seed)
    h = client_presence(scennum, num_clients, presence_prob)
    nS, nC = num_servers, num_clients
    u = data["u"] if capacity is None else float(capacity)

    m = Model(scenario_name, sense="min")
    y = m.var("OpenServer", nS, lb=0.0, ub=1.0, integer=True, stage=1)
    x = m.var("Assign", nS * nC, lb=0.0, ub=1.0, integer=True, stage=2)

    # each present client assigned exactly once (ref. sslp abstract model's
    # client satisfaction constraint); absent clients: x forced to 0
    assign_of_client = np.zeros((nC, nS * nC))
    for j in range(nC):
        assign_of_client[j, j::nC] = 1.0
    m.constr(assign_of_client @ x == h, name="ClientAssignment")

    # server capacity with open-gate: sum_j d_j x_ij <= u * y_i; the
    # published data prices a client's demand on a server at its revenue
    # there (d_ij = q_ij), and lets a server overflow at a penalty
    demand = data["r"] if demand_is_revenue \
        else np.broadcast_to(data["d"], (nS, nC))
    demand_on_server = np.zeros((nS, nS * nC))
    for i in range(nS):
        demand_on_server[i, i * nC:(i + 1) * nC] = demand[i]
    gate = -u * np.eye(nS)
    load = (demand_on_server @ x) + (gate @ y)
    cost2 = x.dot(-data["r"].reshape(-1))            # revenue: negative cost
    if overflow:
        over = m.var("Overflow", nS, lb=0.0, stage=2)
        load = load + ((-np.eye(nS)) @ over)
        cost2 = cost2 + over.dot(np.full(nS, OVERFLOW_PENALTY))
    m.constr(load <= 0.0, name="ServerCapacity")
    if server_budget is not None:
        m.constr(np.ones((1, nS)) @ y <= float(server_budget),
                 name="ServerBudget")

    m.stage_cost(1, y.dot(data["c"]))
    m.stage_cost(2, cost2)
    return m


def scenario_vector_patch(scenario_name, num_servers=5, num_clients=25,
                          presence_prob=0.5, **_):
    """Structure-shared fast path for build_batch(vector_patch=...): the
    ONLY scenario-dependent data is the clients' presence h(w), the rhs
    of the assignment rows. 2000 scenarios of sslp_10_50 are one
    (61, 520) matrix build and 2000 vectors of 50, not 2000 matrix
    builds. Drift against scenario_creator is caught by build_batch's
    scenario-0 identity assertion plus
    tests/test_models.py::test_sslp_published_shape_at_10_50."""
    scennum = int(re.search(r"(\d+)$", scenario_name).group(1))
    h = client_presence(scennum, num_clients, presence_prob)
    return {("l", "ClientAssignment"): h, ("u", "ClientAssignment"): h}


def make_tree(num_scens, **_):
    names = [f"Scenario{i}" for i in range(num_scens)]
    return two_stage_tree(names, nonant_names=["OpenServer"])


def scenario_denouement(rank, scenario_name, values):
    pass
