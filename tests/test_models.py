"""Model families: structure checks + EF objective cross-validation.

Each model's EF (LP relaxation) is solved twice: by our batched ADMM
ExtensiveForm engine and independently by scipy's HiGHS on an explicitly
assembled EF LP. Matching objectives validate the whole lowering chain
(DSL -> standard form -> batch -> EF merge) per model family. Mirrors the
reference's sig-digit EF assertions (ref. mpisppy/tests/test_ef_ph.py:66,149).
"""

import numpy as np
import pytest
from scipy.optimize import linprog
from scipy.sparse import lil_matrix

from mpisppy_tpu.core.ef import ExtensiveForm
from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import sizes, sslp, netdes, battery


def ef_linprog(batch):
    """Independent EF LP: S copies of (c, A, l<=Ax<=u, lb<=x<=ub) with
    nonant columns tied to scenario 0 by equality rows; prob-weighted
    objective. Solved by HiGHS."""
    S, n, m, K = batch.S, batch.n, batch.m, batch.K
    idx = np.asarray(batch.nonant_idx)
    N = S * n
    cost = (batch.prob[:, None] * batch.c).reshape(-1)

    A_ub_blocks, b_ub = [], []
    A_eq_blocks, b_eq = [], []
    for s in range(S):
        A, l, u = batch.A_of(s), batch.l[s], batch.u[s]
        eq = np.isfinite(l) & np.isfinite(u) & (l == u)
        ub_rows = np.isfinite(u) & ~eq
        lb_rows = np.isfinite(l) & ~eq
        for rows, sign, rhs in ((ub_rows, 1.0, u), (lb_rows, -1.0, -l)):
            if rows.any():
                blk = lil_matrix((rows.sum(), N))
                blk[:, s * n:(s + 1) * n] = sign * A[rows]
                A_ub_blocks.append(blk)
                b_ub.append(rhs[rows])
        if eq.any():
            blk = lil_matrix((eq.sum(), N))
            blk[:, s * n:(s + 1) * n] = A[eq]
            A_eq_blocks.append(blk)
            b_eq.append(l[eq])
        if s > 0:   # nonanticipativity: x_s[k] == x_0[k]
            blk = lil_matrix((K, N))
            for kk, col in enumerate(idx):
                blk[kk, s * n + col] = 1.0
                blk[kk, col] = -1.0
            A_eq_blocks.append(blk)
            b_eq.append(np.zeros(K))

    from scipy.sparse import vstack
    bounds = []
    for s in range(S):
        for j in range(n):
            lo, hi = batch.lb[s, j], batch.ub[s, j]
            bounds.append((None if not np.isfinite(lo) else lo,
                           None if not np.isfinite(hi) else hi))
    res = linprog(cost,
                  A_ub=vstack(A_ub_blocks).tocsr() if A_ub_blocks else None,
                  b_ub=np.concatenate(b_ub) if b_ub else None,
                  A_eq=vstack(A_eq_blocks).tocsr() if A_eq_blocks else None,
                  b_eq=np.concatenate(b_eq) if b_eq else None,
                  bounds=bounds, method="highs")
    assert res.status == 0, res.message
    return res.fun + float(batch.prob @ batch.c0)


CASES = [
    ("sizes", lambda: build_batch(sizes.scenario_creator, sizes.make_tree(3),
                                  creator_kwargs={"scenario_count": 3})),
    ("sslp", lambda: build_batch(sslp.scenario_creator, sslp.make_tree(4),
                                 creator_kwargs={"num_servers": 3,
                                                 "num_clients": 8})),
    ("netdes", lambda: build_batch(netdes.scenario_creator,
                                   netdes.make_tree(4),
                                   creator_kwargs={"num_nodes": 5})),
    ("battery", lambda: build_batch(battery.scenario_creator,
                                    battery.make_tree(3),
                                    creator_kwargs={"T": 12})),
]


@pytest.mark.parametrize("name,mk", CASES, ids=[c[0] for c in CASES])
def test_ef_matches_scipy(name, mk):
    batch = mk()
    want = ef_linprog(batch)
    ef = ExtensiveForm(batch, {"subproblem_max_iter": 60000,
                               "subproblem_eps": 1e-9})
    got, _ = ef.solve_extensive_form()
    assert got == pytest.approx(want, rel=2e-3, abs=2e-2), \
        f"{name}: ADMM EF {got} vs HiGHS {want}"


@pytest.mark.parametrize("name,mk", CASES, ids=[c[0] for c in CASES])
def test_ph_bound_sandwich(name, mk):
    batch = mk()
    ef_obj = ef_linprog(batch)
    ph = PH(batch, {"defaultPHrho": 5.0, "PHIterLimit": 30,
                    "convthresh": 1e-6, "subproblem_max_iter": 4000})
    conv, eobj, trivial = ph.ph_main()
    # trivial (wait-and-see) bound is a certified outer bound on the EF-LP
    assert trivial <= ef_obj + 1e-2 * max(1.0, abs(ef_obj))


def test_sizes_structure_and_rho_setter():
    batch = build_batch(sizes.scenario_creator, sizes.make_tree(3),
                        creator_kwargs={"scenario_count": 3})
    # nonants: 10 produced + 55 cut pairs
    assert batch.K == 10 + 55
    rho = sizes._rho_setter(batch)
    assert rho.shape == (65,)
    assert np.all(rho > 0)
    spec = sizes.id_fix_list_fct(batch)
    assert spec["nb"].shape == (65,)
    # scenario demand multipliers: 0.7 / 1.0 / 1.3 of first-stage demands
    assert sizes.demand_multiplier(0, 3) == 0.7
    assert sizes.demand_multiplier(2, 3) == 1.3
    assert len(set(sizes.demand_multiplier(i, 10) for i in range(10))) == 10


def test_sizes_10_scenarios_builds():
    batch = build_batch(sizes.scenario_creator, sizes.make_tree(10),
                        creator_kwargs={"scenario_count": 10})
    assert batch.S == 10
    assert abs(batch.prob.sum() - 1.0) < 1e-9


def test_sslp_feasibility_invariant():
    """Each present client is assigned; capacity respected at the EF opt."""
    batch = build_batch(sslp.scenario_creator, sslp.make_tree(4),
                        creator_kwargs={"num_servers": 3, "num_clients": 8})
    ef = ExtensiveForm(batch, {"subproblem_max_iter": 60000,
                               "subproblem_eps": 1e-9})
    _, x_batch = ef.solve_extensive_form()
    vals = {name: np.asarray(x_batch)[:, sl]
            for name, sl in batch.template.var_slices.items()}
    for s in range(4):
        h = sslp.client_presence(s, 8)
        assign = vals["Assign"][s].reshape(3, 8)
        assert np.allclose(assign.sum(axis=0), h, atol=1e-4)


SSLP_PUBLISHED = dict(overflow=True, server_budget=10, capacity=188.0,
                      demand_is_revenue=True)


def test_sslp_published_shape_at_10_50():
    """SIPLIB's sslp_10_50 as published (Ntaimo & Sen 2005): 10 site
    binaries, 500 assignment binaries, 10 overflow columns; 50
    assignment rows, 10 capacity rows, the server-budget row. The
    randomness is in the assignment rows' rhs alone, so build_batch
    stores ONE shared matrix, through the vector patch and without
    it."""
    kw = dict(num_servers=10, num_clients=50, **SSLP_PUBLISHED)
    fast = build_batch(sslp.scenario_creator, sslp.make_tree(12),
                       creator_kwargs=kw,
                       vector_patch=sslp.scenario_vector_patch)
    assert (fast.n, fast.m, fast.K) == (520, 61, 10)
    assert fast.shared_A and fast.A.shape == (61, 520)
    assert int(np.asarray(fast.integer).sum()) == 510
    assert list(fast.template.var_slices) == ["OpenServer", "Assign",
                                              "Overflow"]
    assert list(fast.template.con_slices) == [
        "ClientAssignment", "ServerCapacity", "ServerBudget"]
    full = build_batch(sslp.scenario_creator, sslp.make_tree(12),
                       creator_kwargs=kw)
    assert full.shared_A
    np.testing.assert_array_equal(fast.A, full.A)
    for fld in ("c", "c0", "P_diag", "l", "u", "lb", "ub", "c_stage",
                "c0_stage", "prob"):
        np.testing.assert_array_equal(getattr(fast, fld),
                                      getattr(full, fld), err_msg=fld)
    # rhs-only randomness: everything but the 50 assignment rows is one
    # row repeated
    rows = fast.template.con_slices["ClientAssignment"]
    assert (rows.start, rows.stop) == (0, 50)
    for fld in ("c", "lb", "ub"):
        assert (getattr(fast, fld) == getattr(fast, fld)[0]).all()
    assert (fast.l[:, 50:] == fast.l[0, 50:]).all()
    assert (fast.u[:, 50:] == fast.u[0, 50:]).all()
    assert len({tuple(r) for r in fast.l[:, :50]}) == 12
    np.testing.assert_array_equal(fast.l[:, :50], fast.u[:, :50])


def test_sslp_default_kwargs_keep_the_reduced_model():
    """The 5 x 25 instances every earlier test builds: no overflow
    column, no budget row, client demands d_j, capacity 2 sum(d) / m."""
    b = build_batch(sslp.scenario_creator, sslp.make_tree(4))
    assert (b.n, b.m, b.K) == (5 + 125, 25 + 5, 5)
    assert list(b.template.var_slices) == ["OpenServer", "Assign"]
    data = sslp.instance_data()
    cap = b.A[25:, :]
    np.testing.assert_array_equal(cap[:, :5], -data["u"] * np.eye(5))
    np.testing.assert_array_equal(cap[0, 5:30], data["d"])
    patched = build_batch(sslp.scenario_creator, sslp.make_tree(4),
                          vector_patch=sslp.scenario_vector_patch)
    np.testing.assert_array_equal(patched.l, b.l)


def test_battery_flow_balance_at_opt():
    batch = build_batch(battery.scenario_creator, battery.make_tree(3),
                        creator_kwargs={"T": 12})
    ef = ExtensiveForm(batch, {"subproblem_max_iter": 60000,
                               "subproblem_eps": 1e-9})
    _, x_batch = ef.solve_extensive_form()
    vals = {name: np.asarray(x_batch)[:, sl]
            for name, sl in batch.template.var_slices.items()}
    eff = battery.DEFAULTS["eff"]
    for s in range(3):
        x, p, q = vals["StateOfCharge"][s], vals["Charge"][s], vals["Discharge"][s]
        resid = x[1:] - x[:-1] - eff * p[:-1] + q[:-1] / eff
        assert np.max(np.abs(resid)) < 1e-3


def test_uc_vector_patch_matches_creator():
    """The structure-shared fast path (build_batch(vector_patch=...))
    reproduces the full per-scenario-creator batch EXACTLY — every
    vector field, with the constraint matrix stored once. This is the
    drift guard that lets reference-scale benches trust the patch."""
    import numpy as np
    from mpisppy_tpu.models import uc as ucm

    for kw in ({"num_gens": 3, "num_hours": 8},
               {"num_gens": 4, "num_hours": 6, "min_up_down": True,
                "ramping": True, "relax_integrality": False}):
        full = build_batch(ucm.scenario_creator, ucm.make_tree(5),
                           creator_kwargs=kw)
        fast = build_batch(ucm.scenario_creator, ucm.make_tree(5),
                           creator_kwargs=kw,
                           vector_patch=ucm.scenario_vector_patch)
        assert fast.shared_A
        # the full path auto-compacts shared A too
        assert full.shared_A
        np.testing.assert_array_equal(np.asarray(fast.A),
                                      np.asarray(full.A))
        for fld in ("c", "c0", "P_diag", "l", "u", "lb", "ub",
                    "c_stage", "c0_stage", "prob"):
            np.testing.assert_array_equal(
                np.asarray(getattr(fast, fld)),
                np.asarray(getattr(full, fld)), err_msg=fld)


def test_uc_min_up_down_and_ramping():
    """The optional Rajan-Takriti windows and ramp rows: structure, the
    constrained optimum dominates the base one, and a fast-cycling
    commitment violates the min-uptime rows."""
    import numpy as np
    from mpisppy_tpu.models import uc as ucm

    kw = {"num_gens": 3, "num_hours": 8, "relax_integrality": False}
    b0 = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                     creator_kwargs=kw)
    b1 = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                     creator_kwargs={**kw, "min_up_down": True,
                                     "ramping": True})
    G, T = 3, 8
    # min_uptime + min_downtime add 2*G*T rows; ramps add 2*G*(T-1)
    assert b1.m == b0.m + 2 * G * T + 2 * G * (T - 1)

    # a schedule that cycles every other hour violates min-uptime for
    # the slow unit: evaluate the min_uptime rows (the G*T rows right
    # after the base block) on a crafted commitment
    ut, dt_ = ucm.min_up_down_times(G)
    assert ut[0] >= 4 and ut[-1] == 1     # slow baseload, fast peaker
    A = np.asarray(b1.A_of(0))
    n = b1.n
    x = np.zeros(n)
    u = np.zeros((G, T))
    u[:, ::2] = 1.0                       # on at even hours only
    st = np.zeros((G, T))
    st[:, 0] = u[:, 0]
    st[:, 1:] = np.maximum(0.0, u[:, 1:] - u[:, :-1])
    x[:G * T] = u.reshape(-1)             # u block, g-major
    x[G * T:2 * G * T] = st.reshape(-1)   # st block
    up_rows = slice(b0.m, b0.m + G * T)
    lhs = A[up_rows] @ x                  # window-sum(st) - u  per (g,t)
    viol = lhs - np.asarray(b1.u)[0][up_rows]
    # the slow unit's window accumulates several startups while u <= 1
    assert viol.max() > 0.9
    # a constant-on schedule satisfies the same rows
    x2 = np.zeros(n)
    x2[:G * T] = 1.0
    st2 = np.zeros((G, T)); st2[:, 0] = 1.0
    x2[G * T:2 * G * T] = st2.reshape(-1)
    lhs2 = A[up_rows] @ x2
    assert (lhs2 <= np.asarray(b1.u)[0][up_rows] + 1e-9).all()


def test_uc_t0_state_and_su_sd_ramps():
    """r5 fidelity options (VERDICT r4 #6): warm-fleet T0 state
    (UnitOnT0State/PowerGeneratedT0 shape) and distinct
    startup/shutdown ramp allowances. Asserts the T0 machinery BINDS:
    obligation bounds pin early commitments, the t=0 ramp rows anchor
    to PowerGeneratedT0, and the warm-fleet optimum differs from the
    cold-start one."""
    import numpy as np
    from mpisppy_tpu.models import uc as ucm

    G, T = 8, 10
    base_kw = dict(num_gens=G, num_hours=T, relax_integrality=True,
                   min_up_down=True, ramping=True)
    warm_kw = dict(base_kw, t0_state=True, startup_shutdown_ramps=True)
    cold = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                       creator_kwargs=base_kw,
                       vector_patch=ucm.scenario_vector_patch)
    warm = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                       creator_kwargs=warm_kw,
                       vector_patch=ucm.scenario_vector_patch)
    # t=0 ramp rows exist: one extra (up, down) pair per generator
    assert warm.m == cold.m + 2 * G

    on0, spent0, p0 = ucm.t0_fleet_state(G)
    ut, dt_ = ucm.min_up_down_times(G)
    lb = np.asarray(warm.lb)[0]
    ub = np.asarray(warm.ub)[0]
    # remaining min-up/down obligations pin early commitments
    pinned_on = sum(int(max(0, min(T, ut[g] - spent0[g])))
                    for g in range(G) if on0[g])
    pinned_off = sum(int(max(0, min(T, dt_[g] - spent0[g])))
                     for g in range(G) if not on0[g])
    assert pinned_on > 0 and pinned_off > 0
    assert int((lb[:G * T] == 1.0).sum()) == pinned_on
    assert int((ub[:G * T] == 0.0).sum()) == pinned_off

    # the t=0 ramp-up rhs carries PowerGeneratedT0 + RU*on0
    fl = ucm.fleet(G)
    ramp = 0.5 * (fl["pmax"] - fl["pmin"]) + 0.1 * fl["pmax"]
    sl = warm.template.con_slices["ramp_up"]
    rhs_up = np.asarray(warm.u)[0][sl][::T]      # t=0 row of each gen
    np.testing.assert_allclose(rhs_up, p0 + ramp * on0, rtol=1e-12)

    # warm-fleet economics differ from cold-start
    from mpisppy_tpu.core.ph import PHBase
    objs = []
    for b in (cold, warm):
        ph = PHBase(b, {"subproblem_max_iter": 2000,
                        "subproblem_eps": 1e-7})
        obj = ph.solve_loop(w_on=False, prox_on=False)
        objs.append(float(np.asarray(ph.Eobjective(obj))))
    assert abs(objs[1] - objs[0]) > 1e-6 * abs(objs[0])


def test_uc_quick_start_set():
    """quick_start: the QS subset's capacity serves reserve without
    commitment (reference QuickStart parameter) — reserve rows lose
    their u coefficients, the rhs shifts by the QS capacity, and the
    relaxed reserve makes the optimum no more expensive."""
    from mpisppy_tpu.models import uc as ucm

    G, T = 8, 10
    base_kw = dict(num_gens=G, num_hours=T, relax_integrality=True,
                   min_up_down=True, ramping=True)
    b0 = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                     creator_kwargs=base_kw,
                     vector_patch=ucm.scenario_vector_patch)
    bq = build_batch(ucm.scenario_creator, ucm.make_tree(2),
                     creator_kwargs=dict(base_kw, quick_start=True),
                     vector_patch=ucm.scenario_vector_patch)
    qs = ucm.quick_start_set(G)
    assert qs.any() and not qs.all()
    Aq = np.asarray(bq.A if bq.A.ndim == 2 else bq.A[0])
    sl = bq.template.con_slices["reserve"]
    fl = ucm.fleet(G)
    for g in range(G):
        cols = slice(g * T, (g + 1) * T)
        coeffs = Aq[sl, cols]
        if qs[g]:
            assert np.all(coeffs == 0.0)
        else:
            assert np.allclose(np.diag(coeffs[:T, :T]), fl["pmax"][g])
    qs_cap = float(fl["pmax"][qs].sum())
    np.testing.assert_allclose(np.asarray(bq.l)[0][sl],
                               np.asarray(b0.l)[0][sl] - qs_cap)
    # economics on scipy ground truth (ADMM objectives at the residual
    # floor are too loose for an inequality this tight): relaxing
    # reserve can only cheapen scenario 0's LP
    from scipy.optimize import linprog

    def truth(b):
        A = np.asarray(b.A if b.A.ndim == 2 else b.A[0])
        u_s, l_s = np.asarray(b.u)[0], np.asarray(b.l)[0]
        fin_u, fin_l = np.isfinite(u_s), np.isfinite(l_s)
        lp = linprog(np.asarray(b.c)[0],
                     A_ub=np.vstack([A[fin_u], -A[fin_l]]),
                     b_ub=np.concatenate([u_s[fin_u], -l_s[fin_l]]),
                     bounds=list(zip(np.asarray(b.lb)[0],
                                     np.asarray(b.ub)[0])),
                     method="highs")
        assert lp.status == 0
        return lp.fun + float(np.asarray(b.c0)[0])

    assert truth(bq) <= truth(b0) + 1e-9 * abs(truth(b0))
