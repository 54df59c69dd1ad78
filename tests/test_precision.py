"""Precision policy, stall exit, segmentation, and hybrid-bound tests.

Covers the round-2 kernel redesign: dtype-dispatched factorization
(f64 explicit inverse / f32 Cholesky), qp_solve_mixed escalation,
qp_solve_segmented equivalence, the opt-in stall exit, the host exact
Lagrangian oracle, and dive-based x̂ candidates on integer nonants.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.core.ph import PHBase, PH
from mpisppy_tpu.models import uc, farmer
from mpisppy_tpu.ops.qp_solver import (PreparedFactor, QPData, qp_setup,
                                       qp_solve, qp_solve_mixed,
                                       qp_solve_segmented, qp_cold_state,
                                       _factorize)


def _uc_batch(S=4, G=3, T=6, integer=False):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T,
                                       "relax_integrality": not integer})


def _qp(batch, dtype):
    A0 = jnp.asarray(np.asarray(batch.A_of(0)), dtype)
    P0 = jnp.asarray(np.asarray(batch.P_diag)[0], dtype)
    data = QPData(P0, A0, jnp.asarray(batch.l, dtype),
                  jnp.asarray(batch.u, dtype), jnp.asarray(batch.lb, dtype),
                  jnp.asarray(batch.ub, dtype))
    q = jnp.asarray(batch.c, dtype)
    factors = qp_setup(data, q_ref=q)
    return data, q, factors


def test_factorize_dtype_dispatch():
    """f64 stores the explicit inverse (F @ M ~ I); f32 the Cholesky
    factor (L @ L.T ~ M)."""
    b = _uc_batch()
    for dtype in (jnp.float64, jnp.float32):
        data, q, factors = _qp(b, dtype)
        F = _factorize(factors, jnp.ones((), dtype))
        A_s, P_s = factors.A_s, factors.P_s
        g = factors.Eb * factors.D
        M = A_s.T @ (factors.rho_A[:, None] * A_s) \
            + jnp.diag(P_s + factors.sigma + g * g * factors.rho_b)
        n = M.shape[0]
        if dtype == jnp.float64:
            err = jnp.max(jnp.abs(F @ M - jnp.eye(n, dtype=dtype)))
            assert float(err) < 1e-8
        else:
            # the shared f32 factor comes PREPARED for the x-update's
            # substitution (qp_solver.PreparedFactor): .tri is L itself
            assert isinstance(F, PreparedFactor) and F.tri.dtype == dtype
            L = F.tri
            err = jnp.max(jnp.abs(L @ L.T - M)) / jnp.max(jnp.abs(M))
            assert float(err) < 1e-4


def test_segmented_matches_monolithic():
    """qp_solve_segmented reaches the same solution as one long call.

    The comparison runs on farmer (which the kernel solves to the
    requested 1e-8 tolerance within the budget, so the optimum is pinned
    down) — on a stall-prone LP both paths stop at different points of
    the same residual plateau and no pointwise equality holds."""
    b = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    data, q, factors = _qp(b, jnp.float64)
    st1 = qp_cold_state(factors, data)
    st1, x1, _, _ = qp_solve(factors, data, q, st1, max_iter=6000,
                             eps_abs=1e-8, eps_rel=1e-8)
    st2 = qp_cold_state(factors, data)
    st2, x2, _, _ = qp_solve_segmented(factors, data, q, st2,
                                       max_iter=6000, segment=250,
                                       eps_abs=1e-8, eps_rel=1e-8)
    assert float(st1.pri_rel.max()) < 1e-6      # both actually converged
    assert float(st2.pri_rel.max()) < 1e-6
    scale = float(jnp.max(jnp.abs(x1))) + 1.0
    assert float(jnp.max(jnp.abs(x1 - x2))) / scale < 1e-4


def test_mixed_reaches_f64_quality():
    """The f32-bulk + f64-tail escalation ends at f64-quality residuals."""
    b = _uc_batch()
    data, q, factors = _qp(b, jnp.float64)
    st = qp_cold_state(factors, data)
    st, x, yA, yB = qp_solve_mixed(factors, data, q, st, max_iter=1500,
                                   tail_iter=1500, eps_abs=1e-6,
                                   eps_rel=1e-6)
    assert st.x.dtype == jnp.float64
    assert float(st.pri_rel.max()) < 1e-3


def test_stall_exit_bounds_iterations():
    """With the stall gate on, a plateaued solve exits long before the
    budget; the polish still repairs the point."""
    b = _uc_batch()
    data, q, factors = _qp(b, jnp.float64)
    st = qp_cold_state(factors, data)
    st, *_ = qp_solve(factors, data, q, st, max_iter=30000,
                      eps_abs=1e-12, eps_rel=1e-12, stall_rel=1e-3)
    assert int(st.iters) < 30000          # did not burn the budget
    assert float(st.pri_rel.max()) < 1e-2


def test_ph_precision_mixed_option():
    # production-shaped options: loose hot-loop criteria (the polish
    # carries the point the rest of the way), mixed escalation
    ph = PHBase(_uc_batch(), {"defaultPHrho": 50.0,
                              "subproblem_max_iter": 1200,
                              "subproblem_eps": 1e-6,
                              "subproblem_eps_hot": 1e-4,
                              "subproblem_eps_dua_hot": 1e-3,
                              "subproblem_precision": "mixed",
                              "subproblem_tail_iter": 1500},
                dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    st = ph._qp_states[True]
    assert float(np.asarray(st.pri_rel).max()) < 1e-3


def test_mixed_segment_lo_matches_default():
    """A longer f32 segment (subproblem_segment_lo — the dispatch-count
    lever for high-latency device links) must not change the solution
    quality the mixed escalation delivers."""
    b = _uc_batch()
    data, q, factors = _qp(b, jnp.float64)
    st1 = qp_cold_state(factors, data)
    st1, x1, *_ = qp_solve_mixed(factors, data, q, st1, max_iter=1500,
                                 tail_iter=1500, eps_abs=1e-6,
                                 eps_rel=1e-6, segment=250)
    st2 = qp_cold_state(factors, data)
    st2, x2, *_ = qp_solve_mixed(factors, data, q, st2, max_iter=1500,
                                 tail_iter=1500, eps_abs=1e-6,
                                 eps_rel=1e-6, segment=250,
                                 segment_lo=1500)
    assert float(st2.pri_rel.max()) < 1e-3
    scale = float(jnp.max(jnp.abs(x1))) + 1.0
    assert float(jnp.max(jnp.abs(x1 - x2))) / scale < 1e-3


def test_ph_precision_mixed_requires_f64():
    with pytest.raises(ValueError):
        PHBase(_uc_batch(), {"subproblem_precision": "mixed"},
               dtype=jnp.float32)


def _split_qp(batch):
    """QPData with A as a SplitMatrix (the df32 big-instance repr)."""
    from mpisppy_tpu.ops.qp_solver import SplitMatrix, split_f32_np

    hi, lo = split_f32_np(np.asarray(batch.A_of(0), np.float64))
    dt = jnp.float64
    data = QPData(jnp.asarray(np.asarray(batch.P_diag)[0], dt),
                  SplitMatrix(jnp.asarray(hi), jnp.asarray(lo)),
                  jnp.asarray(batch.l, dt), jnp.asarray(batch.u, dt),
                  jnp.asarray(batch.lb, dt), jnp.asarray(batch.ub, dt))
    q = jnp.asarray(batch.c, dt)
    return data, q, qp_setup(data, q_ref=q)


def test_df32_split_matvec_accuracy():
    """The three-pass split matvec agrees with exact f64 to the f32
    accumulation floor (~1e-7 relative), far below plain-f32 input
    quantization + accumulation at UC-like magnitudes."""
    from mpisppy_tpu.ops.qp_solver import SplitMatrix, _Ax, split_f32

    rng = np.random.RandomState(0)
    A = rng.randn(400, 300) * np.exp(rng.randn(400, 300) * 3)
    x = rng.randn(5, 300) * 1e3
    exact = x @ A.T
    Asp = split_f32(jnp.asarray(A))
    got = np.asarray(_Ax(Asp, jnp.asarray(x)))
    plain = np.asarray(_Ax(jnp.asarray(A, jnp.float32),
                           jnp.asarray(x, jnp.float32)), np.float64)
    scale = np.abs(exact).max()
    assert np.abs(got - exact).max() / scale < 1e-6
    # never worse than plain f32 (the split removes input quantization;
    # what remains is the shared f32 accumulation noise, whose size
    # depends on the backend's dot implementation)
    assert np.abs(got - exact).max() \
        <= 1.5 * np.abs(plain - exact).max() + 1e-12 * scale


def test_df32_factorize_is_f32_preconditioner():
    """df32 factorization yields a finite f32 Cholesky factor of M —
    the preconditioner the IR-wrapped x-update refines against (the
    refinement accuracy itself is covered end-to-end by
    test_df32_solve_matches_f64)."""
    from mpisppy_tpu.ops.qp_solver import _factorize, merged64

    b = _uc_batch()
    data, q, factors = _split_qp(b)
    F = _factorize(factors, jnp.ones((), jnp.float64))
    assert isinstance(F, PreparedFactor) \
        and F.tri.dtype == jnp.float32
    assert all(bool(jnp.isfinite(a).all()) for a in F)
    L = F.tri
    A_s64 = np.asarray(merged64(factors.A_s))
    g = np.asarray(factors.Eb * factors.D)
    M = A_s64.T @ (np.asarray(factors.rho_A)[:, None] * A_s64) \
        + np.diag(np.asarray(factors.P_s) + float(factors.sigma)
                  + g * g * np.asarray(factors.rho_b))
    rel = np.abs(np.asarray(L, np.float64) @ np.asarray(L, np.float64).T
                 - M).max() / np.abs(M).max()
    assert rel < 1e-5


def test_df32_solve_matches_f64():
    """A full df32 escalated solve (f32 bulk on A.hi + split tail)
    reaches the f64 solution on UC within solver tolerance."""
    b = _uc_batch()
    d64, q64, f64f = _qp(b, jnp.float64)
    st = qp_cold_state(f64f, d64)
    st, x_ref, _, _ = qp_solve_segmented(f64f, d64, q64, st,
                                         max_iter=6000, segment=1000,
                                         eps_abs=1e-8, eps_rel=1e-8)
    data, q, factors = _split_qp(b)
    st2 = qp_cold_state(factors, data)
    st2, x_df, yA, yB = qp_solve_mixed(factors, data, q, st2,
                                       max_iter=1500, tail_iter=3000,
                                       eps_abs=1e-7, eps_rel=1e-7)
    # the df32 residual floor is ~kappa(M) * f32-accumulation-noise
    # (the IR bound): ~1.5e-4 on this instance, but the f32 noise term
    # is BACKEND-dependent (the CPU stand-in's dot accumulates in a
    # different order than the MXU; measured 3.25e-4 here vs ~1.5e-4
    # on chip). Gate at 5e-4 — backend-proof, still an order of
    # magnitude under the ~1e-2 pure-f32 plateau the escalation
    # exists to beat — instead of the 3e-4 that tracked one backend.
    assert float(st2.pri_rel.max()) < 5e-4
    # df32 runs with the polish structurally OFF (its per-scenario
    # factors are what the representation exists to avoid), so on this
    # DEGENERATE prox-off LP the objective closes slowly from above —
    # assert near-feasible near-optimality, not exactness (exact
    # bounds/incumbents at df32 scale come from the host oracle)
    from mpisppy_tpu.ops.qp_solver import qp_dual_objective, qp_objective
    obj_ref = np.asarray(qp_objective(d64, q64, 0.0, x_ref))
    obj_df = np.asarray(qp_objective(d64, q64, 0.0, x_df))
    # tolerance-level infeasibility can under- or over-shoot the
    # optimum by ~(violation × VOLL) on UC's penalty-dominated
    # objective — ±3% brackets the achievable band at the df32 floor
    # (exact incumbents/bounds at df32 scale come from the host oracle)
    np.testing.assert_allclose(obj_df, obj_ref, rtol=3e-2)
    # certified dual bound from the df32 duals is VALID (<= true min)
    dual = np.asarray(qp_dual_objective(data, q, 0.0, yA, yB,
                                        x_witness=x_df))
    assert (dual <= obj_ref + 1e-4 * np.abs(obj_ref)).all()


def test_df32_ph_engine_end_to_end():
    """PHBase with subproblem_precision='df32': spbase builds the split
    A, the engine runs the escalated driver, and the trajectory matches
    a native-f64 engine."""
    from mpisppy_tpu.ops.qp_solver import SplitMatrix

    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 1500,
            "subproblem_eps": 1e-7, "subproblem_tail_iter": 2000}
    ph64 = PHBase(_uc_batch(S=4), dict(opts), dtype=jnp.float64)
    phdf = PHBase(_uc_batch(S=4),
                  {**opts, "subproblem_precision": "df32"},
                  dtype=jnp.float64)
    assert isinstance(phdf.qp_data.A, SplitMatrix)
    # prox-off solves land on different vertices of the degenerate
    # optimal face per precision mode, and PH's consensus trajectory
    # amplifies vertex choices — so the comparison is STRUCTURAL:
    # both engines contract, solve to grade, and price the consensus
    # within a fraction of a percent after a few iterations
    for ph in (ph64, phdf):
        for it in range(4):
            if it == 0:
                ph.solve_loop(w_on=False, prox_on=False)
            else:
                ph.solve_loop(w_on=True, prox_on=True)
            ph.W = ph.W_new
    assert float(np.asarray(phdf._qp_states[True].pri_rel).max()) < 5e-3
    assert phdf.conv < 10 * max(ph64.conv, 1e-3)
    # pricing after 4 iterations is sensitive to which optimal vertex
    # each inexact solve lands on (measured swings of ~0.7% across
    # benign kernel changes); the band reflects that, the tight
    # per-solve quality guarantees live in test_df32_solve_matches_f64
    assert phdf.Eobjective_value() == pytest.approx(
        ph64.Eobjective_value(), rel=2e-2)
    # chunked df32 (the production big-instance shape) behaves the same
    phc = PHBase(_uc_batch(S=4),
                 {**opts, "subproblem_precision": "df32",
                  "subproblem_chunk": 2},
                 dtype=jnp.float64)
    for it in range(4):
        if it == 0:
            phc.solve_loop(w_on=False, prox_on=False)
        else:
            phc.solve_loop(w_on=True, prox_on=True)
        phc.W = phc.W_new
    assert np.isfinite(phc.conv)
    # solves reach the same grade as the non-chunked engine
    assert float(np.asarray(phc._qp_states[True].pri_rel).max()) < 5e-3
    # per-chunk rho/warm-start trajectories add another layer of
    # vertex-choice noise on this degenerate instance, and the default
    # fused kernel path (doc/kernels.md) removes the segment-boundary
    # stall/rho-cadence semantics on top — measured 3.5% pricing swing
    # at IDENTICAL solve grade (pri_rel 2.1e-4 fused vs 2.6e-4
    # segmented); the band brackets that. Kernel-mode equivalence has
    # its own suite (tests/test_kernels.py); exact pricing at df32
    # scale comes from the host oracle.
    assert phc.Eobjective_value() == pytest.approx(
        ph64.Eobjective_value(), rel=5e-2)


def test_exact_oracle_matches_device_bound_on_farmer():
    """Host HiGHS Lagrangian == certified device bound at W=0 (both are
    the wait-and-see bound) on the exactly-solvable farmer LP."""
    from mpisppy_tpu.utils.host_oracle import exact_lagrangian_bound

    b = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    exact = exact_lagrangian_bound(b, b.prob)
    ph = PH(b, {"PHIterLimit": 0, "defaultPHrho": 1.0})
    ph.ph_main(finalize=False)
    assert exact == pytest.approx(-115405.56, abs=1.0)
    # certified device bound is a valid lower bound on the exact value
    assert ph.trivial_bound <= exact + 1e-6
    assert ph.trivial_bound >= exact - abs(exact) * 1e-3


def test_exact_oracle_lagrangian_spoke_bound_valid():
    """Exact-oracle spoke bound at a projected W stays a valid outer
    bound (<= EF optimum) and beats the W=0 bound after PH progress."""
    from mpisppy_tpu.utils.host_oracle import exact_lagrangian_bound
    from mpisppy_tpu.core.ef import ExtensiveForm

    b = _uc_batch(S=3, integer=False)
    ef_obj, _ = ExtensiveForm(_uc_batch(S=3)).solve_extensive_form()
    ph = PH(b, {"defaultPHrho": 50.0, "PHIterLimit": 15,
                "convthresh": -1.0, "subproblem_max_iter": 1500,
                "subproblem_eps": 1e-7})
    ph.ph_main(finalize=False)
    W = np.asarray(ph.W - ph.compute_xbar(ph.W))
    lag = exact_lagrangian_bound(b, b.prob, W)
    ws = exact_lagrangian_bound(b, b.prob)
    assert lag is not None
    assert lag <= ef_obj + abs(ef_obj) * 1e-7
    assert lag >= ws - 1e-6               # W can only tighten past W=0


@pytest.mark.slow
def test_chunked_solve_loop_matches_unchunked():
    """Scenario microbatching (subproblem_chunk) reproduces the
    unchunked PH trajectory on a shared-structure batch: same xbar, W,
    objectives, and certified bound within solve tolerance — including
    an uneven final chunk."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 4000,
            "subproblem_eps": 1e-9}
    ph_a = PHBase(_uc_batch(S=8), dict(opts), dtype=jnp.float64)
    ph_b = PHBase(_uc_batch(S=8), {**opts, "subproblem_chunk": 3},
                  dtype=jnp.float64)
    assert ph_a.shared_structure
    for ph in (ph_a, ph_b):
        ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
    np.testing.assert_allclose(np.asarray(ph_b.xbar),
                               np.asarray(ph_a.xbar), atol=2e-5)
    # per-scenario OPTIMAL VALUES are unique (and must agree); the
    # argmins are not — degenerate LP columns admit alternate vertices,
    # so W (built from xn) is compared only through its manifold
    # property, not elementwise
    np.testing.assert_allclose(np.asarray(ph_b._last_solved_obj),
                               np.asarray(ph_a._last_solved_obj),
                               rtol=2e-3)   # ADMM plateau accuracy
    Wn = np.asarray(ph_b.W_new)
    p = np.asarray(ph_b.prob)
    assert np.abs(p @ Wn).max() < 1e-6 * (1 + np.abs(Wn).max())
    assert ph_b.conv == pytest.approx(ph_a.conv, abs=1e-5)
    assert ph_b.Eobjective_value() == pytest.approx(
        ph_a.Eobjective_value(), rel=1e-6)
    # certified bound path (prox-off) under chunking: per-chunk shared
    # rho adapts on the CHUNK's residual statistics, so small tight-eps
    # chunks can plateau at a different accuracy than the full batch —
    # the certified bound stays VALID (<= the true Lagrangian value) by
    # construction, which is the property that matters
    ph_a.solve_loop(w_on=True, prox_on=False, update=False)
    ph_b.solve_loop(w_on=True, prox_on=False, update=False)
    ea, eb = ph_a.Ebound(), ph_b.Ebound()
    # the unchunked solve converged to 1e-14 => its certified bound IS
    # L(W) to machine accuracy; the chunked bound must sit at or below
    assert eb <= ea + 1e-6 * abs(ea)
    # the concatenated state view serves the feasibility consumers
    assert np.asarray(ph_b._qp_states[False].pri_rel).shape == (8,)


@pytest.mark.slow
def test_chunked_dive_candidates_integer_feasible():
    """dive_nonant_candidates under scenario microbatching (with a
    padded uneven final chunk) still produces integral, feasible
    candidates that evaluate to finite incumbents."""
    b = _uc_batch(S=8, G=3, T=6, integer=True)
    ph = PHBase(b, {"defaultPHrho": 50.0, "subproblem_max_iter": 1500,
                    "subproblem_eps": 1e-7, "subproblem_chunk": 3},
                dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    # a chunked PROX-ON solve first: it stores a lazy state view at the
    # same mode key the prox-centered dive warm-starts from — the dive
    # must materialize it, not crash on the view (review regression)
    ph.solve_loop(w_on=True, prox_on=True)
    ph.W = ph.W_new
    cands, feas = ph.dive_nonant_candidates(np.asarray(ph.xbar))
    assert feas.any()
    imask = ph.nonant_integer_mask
    k = int(np.flatnonzero(feas)[0])
    assert np.abs(cands[k][imask] - np.round(cands[k][imask])).max() < 1e-4
    inc = ph.calculate_incumbent(cands[k], feas_tol=1e-3)
    assert inc is not None and np.isfinite(inc)


def test_chunked_rho_pathology_recovery():
    """A chunk whose warm-started rho_scale went pathological (per-chunk
    shared rho adapts on chunk statistics) must be retried from a reset
    factorization instead of accepting a grossly unconverged solve."""
    from mpisppy_tpu.ops.qp_solver import _factorize

    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 1200,
            "subproblem_eps": 1e-6, "subproblem_chunk": 4}
    ph = PHBase(_uc_batch(S=8), opts, dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    # poison chunk 0's rho so its next warm-started solve stalls
    sts = ph._qp_states[("chunks", True)]
    factors, _ = ph._get_factors(True)
    bad_rho = jnp.full_like(sts[0].rho_scale, 1e-6)
    sts[0] = sts[0]._replace(rho_scale=bad_rho,
                             L=_factorize(factors, bad_rho))
    ph.solve_loop(w_on=True, prox_on=True)
    pri = np.asarray(ph._qp_states[True].pri_rel)
    assert pri.max() < 1e-2, f"recovery did not engage: {pri.max():.1e}"


@pytest.mark.slow
def test_chunked_hospital_rescues_flagged_rows():
    """The scenario hospital re-solves rows flagged far-from-feasible in
    NON-shared mode (own scaling against the assembled q — the cure for
    shared-setup stalls) and scatters solutions + residual rows back."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 1500,
            "subproblem_eps": 1e-6, "subproblem_chunk": 3,
            "subproblem_hospital_max": 4}
    ph = PHBase(_uc_batch(S=8), opts, dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    factors, data = ph._get_factors(True)
    slices = ph._chunk_index(3)
    states = ph._qp_states[("chunks", True)]
    n = ph.batch.n
    m = ph.batch.m
    recs = []
    for ci, (idx_c, real) in enumerate(slices):
        st = states[ci]
        if ci == 1:     # flag one row of chunk 1 as grossly unconverged
            st = st._replace(pri_rel=st.pri_rel.at[0].set(1.0))
        recs.append([st, jnp.zeros((3, n)), jnp.zeros((3, m)),
                     jnp.zeros((3, n)), None, None])
    kw = dict(prox_on=True, precision=ph.sub_precision,
              sub_max_iter=ph.sub_max_iter, sub_eps=ph.sub_eps,
              sub_eps_hot=ph.sub_eps_hot,
              sub_eps_dua_hot=ph.sub_eps_dua_hot,
              tail_iter=ph.sub_tail_iter, stall_rel=ph.sub_stall_rel,
              segment=ph.sub_segment, polish_hot=ph.sub_polish_hot,
              polish_chunk=0, segment_lo=ph.sub_segment_lo)
    ph._hospitalize(True, slices, recs, data, thr=1e-2, w_on=True,
                    prox_on=True, kw=kw)
    # the flagged row was cured and its solution scattered back
    assert float(recs[1][0].pri_rel[0]) < 1e-2
    assert float(jnp.abs(recs[1][1][0]).max()) > 0.0
    # unflagged rows untouched
    assert float(jnp.abs(recs[0][1]).max()) == 0.0


def test_blacklist_readmission_recovers_row():
    """A scenario frozen on the hospital blacklist earns a fresh
    recovery attempt every ``subproblem_blacklist_readmit`` solves of
    its mode (VERDICT r3: permanent blacklists silently poison x̄/W) —
    and a row that is in fact curable leaves the blacklist cured."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 1200,
            "subproblem_eps": 1e-6, "subproblem_chunk": 4,
            "subproblem_blacklist_readmit": 2}
    ph = PHBase(_uc_batch(S=8), opts, dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)          # mode-True call #1
    # freeze scenario 5 (chunk 1, row 1) as a standing casualty: both
    # blacklists claim it, so neither chunk retry nor hospital touches
    # it on the next solve...
    key = True
    ph._chunk_no_retry[key] = {0, 1}
    ph._hospital_no_retry[key] = {5}
    # ...until the re-admission boundary (call #2 with readmit=2)
    # clears both sets and the row's ordinary (already converged)
    # solve passes the gate without ever re-entering a blacklist
    ph.solve_loop(w_on=True, prox_on=True)          # mode-True call #2
    assert ph._chunk_no_retry.get(key) == set()
    assert 5 not in ph._hospital_no_retry.get(key, set())
    assert float(np.asarray(ph._qp_states[key].pri_rel).max()) < 1e-2


def test_chunked_requires_shared_structure():
    from mpisppy_tpu.models import netdes

    b = build_batch(netdes.scenario_creator, netdes.make_tree(3))
    if PHBase(b, {}).shared_structure:
        pytest.skip("netdes batch became shared-structure")
    ph = PHBase(b, {"subproblem_chunk": 2})
    with pytest.raises(ValueError):
        ph.solve_loop(w_on=False, prox_on=False)


def test_dive_nonant_candidates_integer_feasible():
    """Dived candidates are integral on integer nonant slots and
    evaluate to a finite incumbent."""
    b = _uc_batch(S=3, integer=True)
    ph = PHBase(b, {"defaultPHrho": 50.0, "subproblem_max_iter": 1500,
                    "subproblem_eps": 1e-7})
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    cands, feas = ph.dive_nonant_candidates(np.asarray(ph.xbar))
    assert feas.any()
    imask = ph.nonant_integer_mask
    k = int(np.flatnonzero(feas)[0])
    frac = np.abs(cands[k][imask] - np.round(cands[k][imask]))
    assert frac.max() < 1e-4
    inc = ph.calculate_incumbent(cands[k], feas_tol=1e-3)
    assert inc is not None and np.isfinite(inc)
